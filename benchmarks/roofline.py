"""Roofline report: aggregates the dry-run JSONs (launch/dryrun.py) into the
EXPERIMENTS.md §Roofline table and emits CSV rows.  Also benchmarks the
consensus + gauss_vi kernels (interpret mode) at model-scale parameter
counts as microbenchmarks."""
from __future__ import annotations

import glob
import json
import os

import jax
import jax.numpy as jnp

from benchmarks.common import Timer, emit

RESULTS = os.path.join(os.path.dirname(__file__), "results")


def load_results(mesh: str = "single") -> list[dict]:
    rows = []
    for path in sorted(glob.glob(os.path.join(RESULTS, f"dryrun_*_{mesh}.json"))):
        with open(path) as f:
            rows.append(json.load(f))
    return rows


def run() -> None:
    for mesh in ("single", "multi"):
        rows = load_results(mesh)
        ok = 0
        for r in rows:
            name = f"roofline_{r['arch']}_{r['shape']}_{mesh}"
            if r["status"] != "ok":
                emit(name, 0.0, f"status={r['status']}")
                continue
            ok += 1
            t = r["roofline_seconds"]
            emit(
                name,
                t[r["dominant"]] * 1e6,  # dominant-term seconds -> us
                f"dominant={r['dominant']};compute_s={t['compute']:.3e};"
                f"memory_s={t['memory']:.3e};collective_s={t['collective']:.3e};"
                f"useful_flops={r['useful_flops_ratio']:.2f}",
            )
        if rows:
            emit(f"roofline_{mesh}_summary", 0.0, f"ok={ok}/{len(rows)}")

    # kernel microbenchmarks (interpret mode: correctness-path timing only)
    p = 1 << 20
    n = 9
    ks = jax.random.split(jax.random.key(0), 3)
    W = jax.nn.softmax(jax.random.normal(ks[0], (n, n)), axis=1)
    mean = jax.random.normal(ks[1], (n, p))
    rho = jax.random.normal(ks[2], (n, p)) * 0.3
    from repro.kernels.consensus import consensus_fused_network

    consensus_fused_network(W, mean, rho)  # compile
    t = Timer()
    reps = 3
    for _ in range(reps):
        jax.block_until_ready(consensus_fused_network(W, mean, rho))
    emit("kernel_consensus_1M_params", t.us(reps), f"n_agents={n};interpret=True")

    from repro.kernels.gauss_vi import sample_and_kl_fused

    mu = mean[0]
    eps = mean[1]
    sample_and_kl_fused(mu, rho[0], eps, mu * 0, rho[1])
    t = Timer()
    for _ in range(reps):
        jax.block_until_ready(sample_and_kl_fused(mu, rho[0], eps, mu * 0, rho[1]))
    emit("kernel_gauss_vi_1M_params", t.us(reps), "interpret=True")
