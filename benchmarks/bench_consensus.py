"""Consensus-path benchmark: leaf-loop einsum vs flat-fused network kernel.

Sweeps (N_agents x P x topology) and times one eq.-(6) round through

  * ``leaf_loop``    — the paper-faithful reference: Python loop over the
    model pytree's leaves, one einsum chain per leaf
    (``core.posterior.consensus_all_agents`` on a ``GaussianPosterior``);
  * ``flat_fused``   — the same math on the contiguous [N, P]
    ``FlatPosterior`` buffers as ONE fused computation
    (``core.flat.consensus_flat``: Pallas network kernel on TPU, single
    fused XLA einsum elsewhere);
  * ``flat_segments`` — the edge-list form (``core.flat
    .consensus_flat_segments`` on the topology's edge arrays).

Wall-clock (median of ``iters`` jitted calls, after warmup) is reported per
path, together with the analytic roofline (``launch.costmodel
.consensus_roofline``): on CPU the Pallas kernels run in interpreter mode,
whose wall-clock says nothing about TPU, so the HBM-pass model is the
load-bearing number there — the interpreter run is kept only as a
correctness probe (max |err| vs the fused XLA reference).

Since the wire-dtype PR the doc also carries a ``wire`` block: the fused
network consensus timed at each wire dtype (fp32 / bf16 exchange of the
(prec, prec*mu) sufficient statistics, fp32 accumulate) next to the
modeled collective bytes (``consensus_roofline``'s ``wire`` term — bf16
halves them) and the measured max deviation vs the fp32 reference.

Output: ``BENCH_consensus.json`` — see ROADMAP.md "Performance" for how to
read it; the perf trajectory is tracked from this file PR-over-PR.
"""
from __future__ import annotations

import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.flat import (
    FlatLayout,
    FlatPosterior,
    consensus_flat,
    consensus_flat_reference,
    consensus_flat_segments,
    flat_posterior_from_pytree,
)
from repro.core.graphs import (
    SparseGraph,
    bidirectional_ring_w,
    complete_w,
    star_w,
    watts_strogatz_sparse,
)
from repro.core.posterior import GaussianPosterior, consensus_all_agents
from repro.launch.costmodel import consensus_roofline

DEFAULT_JSON = "BENCH_consensus.json"


def _ragged_params(key, n_agents: int, p_target: int, n_leaves: int):
    """A deliberately ragged mixed-shape parameter pytree of ~p_target
    scalars per agent, mimicking a real model's many differently-shaped
    leaves (the case where per-leaf dispatch overhead hurts most)."""
    ks = jax.random.split(key, n_leaves)
    per = max(p_target // n_leaves, 8)
    tree = {}
    for i, k in enumerate(ks):
        # cycle through 1-D / 2-D / odd-sized shapes
        if i % 3 == 0:
            shape = (per,)
        elif i % 3 == 1:
            shape = (max(per // 16, 2), 16)
        else:
            shape = (max(per // 7, 1), 7)
        tree[f"leaf_{i:02d}"] = jax.random.normal(k, (n_agents,) + shape)
    return tree


def _posts_for(key, n_agents: int, p_target: int, n_leaves: int):
    k1, k2 = jax.random.split(key)
    mean = _ragged_params(k1, n_agents, p_target, n_leaves)
    rho = jax.tree.map(
        lambda m, k: jax.random.normal(k, m.shape) * 0.3 - 1.0,
        mean,
        dict(zip(mean, jax.random.split(k2, len(mean)))),
    )
    return GaussianPosterior(mean=mean, rho=rho)


def _topology(name: str, n: int) -> np.ndarray:
    if name == "complete":
        return complete_w(n)
    if name == "ring":
        return bidirectional_ring_w(n)
    if name == "star":
        return star_w(n - 1, a=0.5)
    raise ValueError(f"unknown topology {name!r}")


def _time(fn, args, iters: int) -> float:
    """Median wall-clock us of ``fn(*args)``.

    ``fn`` must be jitted with the posteriors passed as ARGUMENTS — a jitted
    closure capturing them as constants lets XLA constant-fold the whole
    consensus at compile time and times nothing.
    """
    jax.block_until_ready(fn(*args))  # warmup / compile
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append((time.perf_counter() - t0) * 1e6)
    return float(np.median(ts))


def bench_one(
    n_agents: int,
    p_target: int,
    topology: str,
    n_leaves: int = 32,
    iters: int = 10,
    check_interpret: bool = False,
    seed: int = 0,
) -> dict:
    posts = _posts_for(jax.random.key(seed), n_agents, p_target, n_leaves)
    flat = flat_posterior_from_pytree(posts, leading_axes=1)
    W = jnp.asarray(_topology(topology, n_agents), jnp.float32)
    g = SparseGraph.from_dense(np.asarray(W))
    edges = tuple(jnp.asarray(a) for a in g.edge_arrays())
    p = flat.layout.n_params

    leaf_fn = jax.jit(lambda po, w: consensus_all_agents(po, w).mean)
    flat_fn = jax.jit(lambda fp, w: consensus_flat(fp, w).mean)
    seg_fn = jax.jit(
        lambda fp, d, s, v: consensus_flat_segments(fp, d, s, v).mean
    )

    rec = {
        "n_agents": n_agents,
        "p": p,
        "n_leaves": n_leaves,
        "topology": topology,
        "max_degree": g.max_in_degree,
        "backend": jax.default_backend(),
        "us": {
            "leaf_loop": _time(leaf_fn, (posts, W), iters),
            "flat_fused": _time(flat_fn, (flat, W), iters),
            "flat_segments": _time(seg_fn, (flat, *edges), iters),
        },
        "roofline": consensus_roofline(
            n_agents, p, n_leaves, max_degree=g.max_in_degree
        ),
    }
    rec["speedup_flat_vs_leaf_loop"] = rec["us"]["leaf_loop"] / rec["us"]["flat_fused"]
    # a sparse topology may run the edge-list form — best-of both
    rec["speedup_best_flat_vs_leaf_loop"] = rec["us"]["leaf_loop"] / min(
        rec["us"]["flat_fused"], rec["us"]["flat_segments"]
    )
    if check_interpret:
        # correctness probe only: the Pallas interpreter is not timed
        ref = consensus_flat(flat, W, mode="xla")
        kern = consensus_flat(flat, W, mode="interpret", block=256)
        sref = consensus_flat_segments(flat, *edges, mode="xla")
        skern = consensus_flat_segments(
            flat, *edges, slots=g.max_in_degree, mode="interpret", block=256
        )
        rec["interpret_max_err"] = {
            "dense_mean": float(jnp.max(jnp.abs(ref.mean - kern.mean))),
            "dense_rho": float(jnp.max(jnp.abs(ref.rho - kern.rho))),
            "segments_mean": float(jnp.max(jnp.abs(sref.mean - skern.mean))),
            "segments_rho": float(jnp.max(jnp.abs(sref.rho - skern.rho))),
        }
    return rec


def wire_sweep(
    n_agents: int = 8,
    p: int = 1 << 15,
    iters: int = 5,
    seed: int = 2,
) -> list[dict]:
    """Fused network consensus per wire dtype: wall-clock, modeled
    collective bytes, and max |err| vs the fp32 reference (which must be
    EXACTLY 0.0 for the f32 wire — the structural no-op contract)."""
    from repro.core.numerics import wire_error_bound

    posts = _posts_for(jax.random.key(seed), n_agents, p, 16)
    flat = flat_posterior_from_pytree(posts, leading_axes=1)
    W = jnp.asarray(_topology("ring", n_agents), jnp.float32)
    ref = consensus_flat(flat, W)
    out = []
    for wire in ("f32", "bf16"):
        fn = jax.jit(
            lambda fp, w, wd=wire: consensus_flat(fp, w, wire_dtype=wd).mean
        )
        got = consensus_flat(flat, W, wire_dtype=wire)
        max_err = max(
            float(jnp.max(jnp.abs(got.mean - ref.mean))),
            float(jnp.max(jnp.abs(got.rho - ref.rho))),
        )
        if wire == "f32":
            assert max_err == 0.0, f"f32 wire is not a structural no-op: {max_err}"
        rec = {
            "wire_dtype": wire,
            "us_flat_fused": _time(fn, (flat, W), iters),
            "max_err_vs_f32": max_err,
            "error_bound_u": wire_error_bound(wire),
            "roofline_wire": consensus_roofline(
                n_agents, flat.layout.n_params, 16, wire_dtype=wire
            )["wire"],
        }
        out.append(rec)
    assert (
        out[1]["roofline_wire"]["collective_bytes"]
        == 0.5 * out[0]["roofline_wire"]["collective_bytes"]
    )
    return out


def assert_no_dense_square(closed_jaxpr, n: int) -> None:
    """Assert the jaxpr allocates NO [n, n] intermediate anywhere — the
    O(E)-memory contract of the sparse path, checked on the actual traced
    computation rather than trusted.  Recurses into sub-jaxprs (scan / cond
    / pjit bodies)."""

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            for v in list(eqn.invars) + list(eqn.outvars):
                shape = getattr(getattr(v, "aval", None), "shape", ())
                if tuple(shape).count(n) >= 2:
                    raise AssertionError(
                        f"sparse path allocated a dense {tuple(shape)} "
                        f"intermediate (n={n}) in {eqn.primitive}"
                    )
            for param in eqn.params.values():
                for sub in param if isinstance(param, (list, tuple)) else [param]:
                    inner = getattr(sub, "jaxpr", None)
                    if inner is not None:
                        walk(inner)

    walk(closed_jaxpr.jaxpr)


def _flat_posts(seed: int, n: int, p: int) -> FlatPosterior:
    """Plain [N, P] posterior buffers (no ragged pytree: the population-scale
    sweep times the consensus, not the flatten)."""
    rng = np.random.default_rng(seed)
    layout = FlatLayout.for_pytree({"w": jnp.zeros((p,))})
    return FlatPosterior(
        mean=jnp.asarray(rng.normal(size=(n, p)), jnp.float32),
        rho=jnp.asarray(rng.normal(size=(n, p)) * 0.3 - 1.0, jnp.float32),
        layout=layout,
    )


def _segments_equivalence(n: int = 24, p: int = 96, seed: int = 5) -> dict:
    """Pin ``consensus_flat_segments`` against the dense reference on a
    small Watts-Strogatz graph, per wire dtype.  The wire-rounded exchange
    values are bitwise the reference's (same ``wire_roundtrip`` chain); the
    scatter accumulates in edge order vs the matmul's column order, so the
    comparison is elementwise at fp32 reduction-order tolerance."""
    g = watts_strogatz_sparse(n, k=4, beta=0.3, seed=seed)
    posts = _flat_posts(seed, n, p)
    W = jnp.asarray(g.to_dense(), jnp.float32)
    dst, src, w = (jnp.asarray(a) for a in g.edge_arrays())
    out = {}
    for wire in ("f32", "bf16", "f16"):
        ref_m, ref_r = consensus_flat_reference(
            posts.mean, posts.rho, W, wire_dtype=wire
        )
        got = consensus_flat_segments(posts, dst, src, w, wire_dtype=wire)
        err = max(
            float(jnp.max(jnp.abs(got.mean - ref_m))),
            float(jnp.max(jnp.abs(got.rho - ref_r))),
        )
        tol = 1e-4  # fp32 reduction-order tolerance (per-element)
        assert err <= tol, f"segments vs dense reference ({wire}): {err} > {tol}"
        out[wire] = err
    return out


# Population-scale sparse sweep: (n_agents, p, k, beta) on Watts-Strogatz.
# Only O(E) representations exist on this path — asserted on the jaxpr.
SEGMENTS_QUICK_SWEEP = [(10_000, 32, 6, 0.1)]
SEGMENTS_FULL_SWEEP = [
    (10_000, 64, 6, 0.1),
    (30_000, 64, 6, 0.1),
    (100_000, 32, 6, 0.1),  # N = 10^5: ~7e5 directed edges, still O(E)
]


def segments_sweep(quick: bool = False, iters: int = 5, seed: int = 0) -> dict:
    """The N = 10^4..10^5 edge-native sweep: time
    ``consensus_flat_segments`` on sparse small-world graphs no dense path
    could even allocate, against the E-parameterized roofline."""
    sweep = SEGMENTS_QUICK_SWEEP if quick else SEGMENTS_FULL_SWEEP
    entries = []
    for n, p, k, beta in sweep:
        t0 = time.perf_counter()
        g = watts_strogatz_sparse(n, k=k, beta=beta, seed=seed)
        build_s = time.perf_counter() - t0
        # host-side O(E) contract: every graph array is E- or N-sized
        for arr in (g.indptr, g.indices, g.weights):
            assert arr.size <= max(g.n_edges, n + 1)
        dst, src, w = (jnp.asarray(a) for a in g.edge_arrays())
        posts = _flat_posts(seed, n, p)
        fn = jax.jit(
            lambda fp, d, s, ww: consensus_flat_segments(fp, d, s, ww).mean
        )
        # device-side O(E) contract: no [N, N] aval anywhere in the trace
        assert_no_dense_square(jax.make_jaxpr(fn)(posts, dst, src, w), n)
        us = _time(fn, (posts, dst, src, w), iters)
        roof = consensus_roofline(
            n, p, 1, max_degree=g.max_in_degree, n_edges=g.n_edges
        )
        entries.append({
            "n_agents": n,
            "p": p,
            "k": k,
            "beta": beta,
            "n_edges": g.n_edges,
            "max_in_degree": g.max_in_degree,
            "graph_build_seconds": build_s,
            "us_flat_segments": us,
            "roofline": roof,
            "no_dense_alloc_asserted": True,
        })
        print(
            f"bench_consensus_segments[{n}x{p}:ws{k}],"
            f"{us:.1f},"
            f"E={g.n_edges};model_bytes={roof['hbm_bytes']['flat_segments']:.0f}"
        )
    # measured-vs-modeled scaling between consecutive sweep points: the
    # E-parameterized model should track the measured growth far better
    # than any N^2 law (recorded, not asserted — CI wall-clock is noisy)
    scaling = []
    for a, b in zip(entries, entries[1:]):
        scaling.append({
            "from": f"{a['n_agents']}x{a['p']}",
            "to": f"{b['n_agents']}x{b['p']}",
            "measured_ratio": b["us_flat_segments"] / a["us_flat_segments"],
            "modeled_ratio": (
                b["roofline"]["hbm_bytes"]["flat_segments"]
                / a["roofline"]["hbm_bytes"]["flat_segments"]
            ),
            "n2_ratio": (b["n_agents"] / a["n_agents"]) ** 2,
        })
    return {
        "equivalence_max_err": _segments_equivalence(),
        "sweep": entries,
        "scaling": scaling,
    }


# (n_agents, p, topology, n_leaves) — n_leaves is a first-class axis: the
# leaf-loop baseline pays per-leaf dispatch, so shallow pytrees (few big
# leaves) are its best case and deep-model pytrees (hundreds of leaves, the
# realistic regime — e.g. whisper-tiny has ~700) its worst.
QUICK_SWEEP = [(4, 4096, "ring", 8)]
FULL_SWEEP = [
    (4, 1 << 16, "complete", 32),
    (4, 1 << 16, "ring", 32),
    (9, 1 << 16, "star", 64),
    (9, 1 << 18, "ring", 32),
    (16, 1 << 18, "complete", 64),
    (16, 1 << 18, "ring", 128),
    (26, 1 << 16, "star", 420),
    (26, 1 << 18, "star", 420),  # largest CPU-feasible config
]


def run(quick: bool = False, json_out: str | None = DEFAULT_JSON) -> dict:
    """Execute the sweep; returns (and optionally writes) the JSON document.

    Also prints the harness's usual ``name,us_per_call,derived`` CSV rows so
    ``benchmarks/run.py`` aggregation keeps working.
    """
    sweep = QUICK_SWEEP if quick else FULL_SWEEP
    results = []
    for i, (n, p, topo, n_leaves) in enumerate(sweep):
        rec = bench_one(
            n, p, topo,
            n_leaves=n_leaves,
            iters=3 if quick else 10,
            check_interpret=(i == 0),  # one interpreter correctness probe
        )
        results.append(rec)
        print(
            f"bench_consensus[{n}x{rec['p']}:{topo}],"
            f"{rec['us']['flat_fused']:.1f},"
            f"speedup={rec['speedup_flat_vs_leaf_loop']:.2f}x"
        )
    segments = segments_sweep(quick=quick, iters=3 if quick else 5)
    wire = wire_sweep(iters=3 if quick else 5)
    for rec in wire:
        print(
            f"bench_consensus_wire[{rec['wire_dtype']}],"
            f"{rec['us_flat_fused']:.1f},"
            f"collective_bytes={rec['roofline_wire']['collective_bytes']:.0f};"
            f"max_err={rec['max_err_vs_f32']:.2e}"
        )
    doc = {
        "benchmark": "consensus_eq6",
        "backend": jax.default_backend(),
        "quick": quick,
        "results": results,
        "segments": segments,
        "wire": wire,
        "summary": {
            "max_speedup_flat_vs_leaf_loop": max(
                r["speedup_flat_vs_leaf_loop"] for r in results
            ),
            "largest_config_speedup_best_flat_vs_leaf_loop": results[-1][
                "speedup_best_flat_vs_leaf_loop"
            ],
            "model_speedup_fused_vs_leaf_loop": results[-1]["roofline"][
                "model_speedup_fused_vs_leaf_loop"
            ],
        },
    }
    if json_out:
        with open(json_out, "w") as f:
            json.dump(doc, f, indent=2)
        print(f"# wrote {json_out}")
    return doc


if __name__ == "__main__":
    run()
