"""Quickstart: decentralized Bayesian learning in ONE declarative spec.

Four agents, a star network, non-IID label partition of a synthetic
classification task.  Each round every agent runs a few Bayes-by-Backprop
steps on its LOCAL data, then precision-averages posteriors with its
neighbors (eq. 6).  Watch the edge agents learn labels they have NEVER
seen.

The whole experiment is the ~15-line ``ExperimentSpec`` below —
``build_session`` validates it eagerly (connectivity, row-stochasticity,
agent counts) and returns an engine-backed ``Session``; change
``TopologySpec`` to move the same run onto any other graph, or make it a
gossip topology to run it on the event-driven ``GossipEngine``.

To watch a run instead of just reading its result, attach the
observability layer — ``ExperimentSpec(obs=ObsSpec(enabled=True))`` gives
``session.obs`` (metrics registry, wall-clock spans, live convergence
tracking vs theory) and ``session.dashboard()``; the pure-observer
contract keeps the trajectory bitwise identical.  The
``convergence_demo`` below overlays a measured disagreement decay against
the ring's spectral prediction in ~15 lines.

Next steps: ``examples/async_gossip.py`` (event-driven asynchronous
runtime) and ``examples/serve_batched.py`` (the serving quickstart —
publish a posterior snapshot and serve batched MC-predictive traffic
under a staleness SLO).

    PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np

from repro.api import (
    DataSpec,
    ExperimentSpec,
    InferenceSpec,
    ObsSpec,
    RunSpec,
    TopologySpec,
    build_session,
)
from repro.core.theory import stationary_distribution

SPEC = ExperimentSpec(
    # star: agent 0 (center) holds labels {1,2,3}; 3 edge agents share label 0
    topology=TopologySpec.star(n_edge=3, a=0.5),
    data=DataSpec(
        dataset_params=dict(n_classes=4, dim=32, n_train_per_class=150),
        partition="star",
        partition_params=dict(center_labels=[1, 2, 3], edge_labels=[0], n_edge=3),
        batch_size=16,
        local_updates=4,
    ),
    inference=InferenceSpec(hidden=32, depth=1, lr=5e-3, kl_scale=1e-3),
    run=RunSpec(n_rounds=20, seed=0, eval_every=5),
)


def convergence_demo():
    """Theory-vs-measured in ~15 lines: on a static ring with lr=0 and
    per-agent inits, consensus is a plain W-average, so disagreement must
    decay at the spectral rate -log lambda_max(W) — watch it happen."""
    spec = ExperimentSpec(
        topology=TopologySpec(kind="bidirectional_ring", params={"n": 4}),
        data=DataSpec(dataset_params=dict(n_classes=3, dim=8, n_train_per_class=30),
                      partition="iid", partition_params=dict(n_agents=4),
                      batch_size=4, local_updates=1),
        inference=InferenceSpec(hidden=8, depth=1, lr=0.0, shared_init=False),
        run=RunSpec(n_rounds=10, seed=0),
        obs=ObsSpec(enabled=True),
    )
    session = build_session(spec)
    session.run()
    report = session.obs.convergence.report()
    for row in report["overlay"]:
        print(f"  round {row['round']:2d}  measured {row['measured']:.3e}  "
              f"predicted {row['predicted']:.3e}")
    print(f"  measured rate {report['measured_rate']:.4f} vs theory "
          f"{report['theory_rate']:.4f} -> attainment "
          f"{report['rate_attainment']:.2f}")


def main():
    session = build_session(SPEC)
    W = SPEC.topology.w_schedule()(0)
    print("eigenvector centrality:", np.round(stationary_distribution(W), 3))

    hist = session.run(eval_fn=lambda s: s.evaluate())
    for rec in hist:
        accs = ", ".join(f"{a:.2f}" for a in rec["acc"])
        print(f"round {rec['round']:3d}  loss {rec['loss']:7.3f}  per-agent acc [{accs}]")
    final = hist[-1]["avg_acc"]
    print(f"\nfinal average accuracy {final:.3f} — edge agents classify labels "
          "1-3 they never observed locally (the paper's central claim).")

    print("\nconvergence overlay (lr=0 ring: measured decay vs Theorem-1 "
          "spectral rate):")
    convergence_demo()


if __name__ == "__main__":
    main()
