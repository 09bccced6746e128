"""Benchmark harness: one function per paper table/figure.
Prints ``name,us_per_call,derived`` CSV rows.

  fig1   decentralized Bayesian linear regression (central/isolated/coop)
  fig2   star topology: accuracy vs center centrality a
  fig3   ID/OOD confidence vs a
  fig4   grid: informative-agent placement (center vs corner)
  fig5   data-partition ambiguity (Assumption 2 violation)
  table3 asynchronous time-varying star networks
  thm1   predicted rate K(Theta) vs empirical decay slope
  calib  (beyond-paper) ECE calibration of the Bayesian MC predictive
  roofline  dry-run roofline terms per (arch x shape x mesh) + kernel bench
  consensus leaf-loop einsum vs flat-fused network consensus kernel
            (writes BENCH_consensus.json; see ROADMAP.md "Performance")

Subcommands:
  run.py [figures] [--only ...] [--json-out F]   paper figures (default)
  run.py bench [--full] [--json-out F]           quick consensus sweep — the
            CI smoke test of the benchmark harness itself (interpret-mode
            kernel probe + tiny shapes; --full for the real sweep)
  run.py api-smoke                               headless exercise of the
            declarative repro.api surface: builds a tiny ExperimentSpec,
            runs BOTH engines (simulated + launch), asserts their posteriors
            agree, round-trips a self-describing session checkpoint
  run.py gossip-smoke [--json-out F]             event-driven gossip runtime
            smoke: all-edges-active window must equal the synchronous fused
            consensus bit-identically, tiny Poisson+link-failure run with
            staleness telemetry (compile_us split from the warm wall time),
            window-consensus / delivery-latency / shard-count sweeps (the
            shard sweep asserts consensus_ppermute_window bit-identity per
            shard count — run under
            XLA_FLAGS=--xla_force_host_platform_device_count=8 to cover
            S>1), plus the edge-native sparse tier: a N=1e4
            Watts-Strogatz Poisson session end to end on
            consensus_impl="segments" (round/evaluate/save/load, jaxpr
            walked for the no-[N,N] contract, window-build host time
            asserted O(fired) — not O(N^2) — across N=1e4 vs 3e4);
            emits BENCH_gossip.json
  run.py chaos-smoke [--json-out F]              fault-tolerance chaos
            harness: combined crash/recover churn + link drops + delivery
            latency + NaN/Inf/huge payload corruption under
            fault_policy="quarantine" (healthy posteriors asserted), the
            strict counter-demo (corruption poisons), the zero-fault
            quarantine==strict bitwise ladder, an lr=0 consensus
            contraction probe under churn, and a degradation-vs-crash-rate
            sweep; emits BENCH_chaos.json
  run.py serve-smoke [--json-out F]              posterior serving tier
            smoke: bf16 snapshot halving asserted live + in the roofline
            model, padding-bucket trace-count pinning with a zero-retrace
            replay, served point estimate vs Session.predictive, then
            p50/p99 latency + QPS sweeps vs MC ensemble size L and bucket
            policy; emits BENCH_serve.json
  run.py obs-smoke [--json-out F]                observability layer smoke:
            disabled-span overhead asserted free, obs-enabled vs unset
            bitwise ladder on the gossip engine, theory-vs-measured
            convergence rate_attainment on a static ring, Prometheus
            exporter golden check; emits BENCH_obs.json + a sample JSONL
            trace (BENCH_obs_trace.jsonl)
  run.py bench-diff OLD.json NEW.json            compare two BENCH_*.json
            documents and flag timing regressions (advisory; --strict to
            gate)
"""
from __future__ import annotations

import argparse
import json
import sys
import traceback

from benchmarks import (
    bench_chaos,
    bench_consensus,
    bench_diff,
    bench_gossip,
    bench_obs,
    bench_serve,
    calibration,
    fig1_linreg,
    fig2_star_centrality,
    fig3_confidence,
    fig4_grid_placement,
    fig5_partition,
    roofline,
    table3_timevarying,
    thm1_rate,
)

ALL = {
    "fig1": fig1_linreg.run,
    "fig2": fig2_star_centrality.run,
    "fig3": fig3_confidence.run,
    "fig4": fig4_grid_placement.run,
    "fig5": fig5_partition.run,
    "table3": table3_timevarying.run,
    "thm1": thm1_rate.run,
    "calib": calibration.run,
    "roofline": roofline.run,
    # quick sweep, no JSON side-effect: the figures path must not silently
    # overwrite the tracked BENCH_consensus.json (use the `bench` subcommand
    # for that)
    "consensus": lambda: bench_consensus.run(quick=True, json_out=None),
}


def api_smoke() -> None:
    """Exercise the repro.api spec/session surface end-to-end on a tiny
    experiment: eager validation, both engines, engine agreement, evaluate,
    and the self-describing checkpoint round trip."""
    import dataclasses
    import os
    import tempfile

    import numpy as np

    from repro.api import (
        DataSpec, ExperimentSpec, InferenceSpec, RunSpec, Session,
        TopologySpec, build_session,
    )

    spec = ExperimentSpec(
        topology=TopologySpec.star(n_edge=2, a=0.5),
        data=DataSpec(
            dataset_params=dict(n_classes=3, dim=8, n_train_per_class=30),
            partition="star",
            partition_params=dict(center_labels=[1, 2], edge_labels=[0], n_edge=2),
            batch_size=4, local_updates=2,
        ),
        inference=InferenceSpec(hidden=8, depth=1, lr=1e-2),
        run=RunSpec(n_rounds=3, seed=0),
    )
    sessions = {}
    for engine in ("simulated", "launch"):
        s = build_session(
            dataclasses.replace(spec, run=dataclasses.replace(spec.run, engine=engine))
        )
        s.run()
        sessions[engine] = s
        print(f"api-smoke,{engine},avg_acc={s.evaluate()['avg_acc']:.4f}")
    p_sim = sessions["simulated"].posterior()
    p_launch = sessions["launch"].posterior()
    np.testing.assert_allclose(
        np.asarray(p_sim.mean), np.asarray(p_launch.mean), atol=1e-5, rtol=1e-5
    )
    with tempfile.TemporaryDirectory() as td:
        path = os.path.join(td, "session.ckpt")
        sessions["simulated"].save(path)
        resumed = Session.load(path)
        np.testing.assert_array_equal(
            np.asarray(resumed.posterior().mean), np.asarray(p_sim.mean)
        )
        assert resumed.round_idx == 3
    print("api-smoke,ok,engines_agree=1;ckpt_roundtrip=1")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "cmd", nargs="?",
        choices=["figures", "bench", "api-smoke", "gossip-smoke",
                 "chaos-smoke", "serve-smoke", "obs-smoke", "bench-diff"],
        default="figures",
        help="figures (default): paper figures; bench: consensus perf "
        "sweep; api-smoke: declarative-API smoke; gossip-smoke: async "
        "gossip runtime smoke (all-active equivalence + Poisson run + "
        "edge-native N=1e4 segments session); "
        "chaos-smoke: fault-tolerance chaos harness (churn + corruption "
        "under quarantine); serve-smoke: posterior serving tier (snapshot "
        "halving + trace pinning + latency/QPS sweeps); obs-smoke: "
        "observability layer (span overhead + bitwise ladder + "
        "rate_attainment + exporter golden); bench-diff: compare two "
        "BENCH_*.json for timing regressions",
    )
    ap.add_argument(
        "paths", nargs="*",
        help="bench-diff only: the OLD.json NEW.json pair to compare",
    )
    ap.add_argument("--only", nargs="*", choices=list(ALL), default=None)
    ap.add_argument(
        "--json-out", default=None,
        help="write a JSON result document (bench: the BENCH_consensus.json "
        "path; figures: {name: ok|failed} status map)",
    )
    ap.add_argument(
        "--full", action="store_true",
        help="bench / gossip-smoke: run the full sweep (segment-sum and "
        "sparse-scale points up to N=1e5) instead of the quick CI smoke",
    )
    ap.add_argument(
        "--strict", action="store_true",
        help="bench-diff only: exit 1 when a timing regression is flagged",
    )
    args = ap.parse_args(argv)

    if args.cmd == "api-smoke":
        api_smoke()
        return
    if args.cmd == "gossip-smoke":
        bench_gossip.run(json_out=args.json_out or bench_gossip.DEFAULT_JSON,
                         full=args.full)
        return
    if args.cmd == "chaos-smoke":
        bench_chaos.run(json_out=args.json_out or bench_chaos.DEFAULT_JSON)
        return
    if args.cmd == "serve-smoke":
        bench_serve.run(json_out=args.json_out or bench_serve.DEFAULT_JSON)
        return
    if args.cmd == "obs-smoke":
        bench_obs.run(json_out=args.json_out or bench_obs.DEFAULT_JSON)
        return
    if args.cmd == "bench-diff":
        if len(args.paths) != 2:
            ap.error("bench-diff needs exactly two paths: OLD.json NEW.json")
        bench_diff.run(args.paths[0], args.paths[1], strict=args.strict)
        return
    if args.cmd == "bench":
        bench_consensus.run(
            quick=not args.full,
            json_out=args.json_out or bench_consensus.DEFAULT_JSON,
        )
        return

    names = args.only or list(ALL)
    print("name,us_per_call,derived")
    failed = []
    for name in names:
        try:
            ALL[name]()
        except Exception:  # noqa: BLE001
            failed.append(name)
            traceback.print_exc(file=sys.stderr)
            print(f"{name},0.0,FAILED")
    if args.json_out:
        with open(args.json_out, "w") as f:
            json.dump(
                {n: ("failed" if n in failed else "ok") for n in names}, f, indent=2
            )
    if failed:
        print(f"# FAILED: {failed}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
