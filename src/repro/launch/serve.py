"""Serving driver: train a small decentralized network, publish a posterior
snapshot, and serve batched MC-predictive traffic against it (the paper's
Sec 4.2 predictive distribution behind the ``repro.serve`` tier).

This replaces the dormant LM prefill/decode seed driver: the repo's end
product is each agent's *classification* predictive served from its
consensus posterior, so the driver now runs the supported path end to end —
``build_session`` -> ``Session.run`` -> ``Session.snapshot`` (the shared
wire-dtype snapshot machinery, not an ad-hoc per-leaf bf16 cast) ->
``PredictiveServer`` request stream — and reports serving latency
percentiles, QPS, and the staleness/SLO telemetry block.

Example (CPU, seconds):
  PYTHONPATH=src python -m repro.launch.serve --rounds 6 --requests 32 \
      --mc-samples 8 --snapshot-dtype bf16 --max-staleness 4
"""
from __future__ import annotations

import argparse
import json

import jax
import numpy as np

from repro.api import (
    DataSpec,
    ExperimentSpec,
    InferenceSpec,
    RunSpec,
    ServeSpec,
    TopologySpec,
    build_session,
)


def serving_spec(
    n_agents: int = 4,
    rounds: int = 6,
    seed: int = 0,
    *,
    serve: ServeSpec = ServeSpec(),
) -> ExperimentSpec:
    """A small gossip network whose snapshots carry real staleness
    telemetry — the serving tier's natural substrate."""
    return ExperimentSpec(
        topology=TopologySpec.gossip("ring", {"n": n_agents}),
        data=DataSpec(
            dataset_params=dict(n_classes=4, dim=16, n_train_per_class=60),
            partition_params=dict(n_agents=n_agents),
            batch_size=8,
            local_updates=2,
        ),
        inference=InferenceSpec(hidden=16, depth=1, lr=5e-3),
        run=RunSpec(n_rounds=rounds, seed=seed),
        serve=serve,
    )


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--agents", type=int, default=4)
    ap.add_argument("--rounds", type=int, default=6)
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--mc-samples", type=int, default=8,
                    help="posterior ensemble size L (0 = point estimate)")
    ap.add_argument("--snapshot-dtype", default="f32",
                    choices=["f32", "bf16", "f16"])
    ap.add_argument("--max-staleness", type=int, default=None,
                    help="SLO bound in training windows (default: off)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    spec = serving_spec(
        args.agents, args.rounds, args.seed,
        serve=ServeSpec(
            snapshot_dtype=args.snapshot_dtype,
            mc_samples=args.mc_samples,
            max_staleness=args.max_staleness,
            staleness_policy="flag",
        ),
    )
    sess = build_session(spec)
    hist = sess.run(eval_every=args.rounds)  # history: final round only
    print(f"trained {args.rounds} windows x {args.agents} agents "
          f"(final loss {hist[-1]['loss'] if hist else None})")

    snap = sess.snapshot()
    print(f"published snapshot: window={snap.window} dtype={snap.dtype} "
          f"resident={snap.nbytes()}B telemetry={snap.telemetry}")

    server = sess.attach_server()
    rng = np.random.default_rng(args.seed)
    x_test = np.asarray(sess.data.x_test)
    # a ragged request stream round-robined over the agents
    sizes = rng.integers(1, 9, size=args.requests)
    for i, n in enumerate(sizes):
        rows = x_test[rng.integers(0, x_test.shape[0], size=int(n))]
        probs, meta = server.query(rows, agent=i % args.agents)
        jax.block_until_ready(probs)

    tel = server.telemetry()
    lat = tel.get("latency", {})
    warm = server._lat_us[len(server.bucket_sizes):]  # skip compile batches
    qps = (1e6 * len(warm) / sum(warm)) if warm else 0.0
    print(f"served {tel['requests']} requests ({tel['rows']} rows, "
          f"{tel['batches']} bucket slabs, {tel['padded_rows']} pad rows, "
          f"{tel['traces']} traces)")
    print(f"latency p50={lat.get('p50_us', 0):.0f}us "
          f"p99={lat.get('p99_us', 0):.0f}us  warm-qps~{qps:.1f}")
    print("telemetry:", json.dumps(tel, default=float))


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
