"""``repro.api`` — the declarative front door (the supported entry point).

One ``ExperimentSpec`` describes a full decentralized-Bayesian-learning
experiment (topology x data x inference x run); ``build_session`` validates
it eagerly and returns an engine-backed ``Session``:

    from repro.api import (
        DataSpec, ExperimentSpec, InferenceSpec, RunSpec, TopologySpec,
        build_session,
    )

    spec = ExperimentSpec(
        topology=TopologySpec.star(n_edge=3, a=0.5),
        data=DataSpec(
            dataset_params=dict(n_classes=4, dim=32, n_train_per_class=150),
            partition="star",
            partition_params=dict(center_labels=[1, 2, 3], edge_labels=[0],
                                  n_edge=3),
        ),
        inference=InferenceSpec(hidden=32, depth=1, lr=5e-3),
        run=RunSpec(n_rounds=20, seed=0),
    )
    session = build_session(spec)
    session.run()
    print(session.evaluate())

Engines: ``RunSpec.engine="simulated"`` (flat vmap runtime, default); the
conjugate linear-regression family of paper Example 1 is selected by
``InferenceSpec(method="conjugate_linreg")``; a
``TopologySpec(kind="gossip", clock=...)`` selects the event-driven
asynchronous ``GossipEngine`` (``repro.gossip``) — one Poisson/trace event
window per round, active-edge masked consensus, staleness telemetry in
``Session.evaluate``.

Serving (``repro.serve``): ``session.snapshot()`` publishes the consensus
posterior into an immutable double-buffered serving copy (``ServeSpec``
picks residency/defaults) and ``session.attach_server()`` returns a
``PredictiveServer`` — batched MC-predictive inference under a
bounded-staleness SLO (see ``examples/serve_batched.py``).
"""
from repro.api.data import DataBundle, build_data
from repro.api.engines import (
    ConjugateLinregEngine,
    Engine,
    SimulatedEngine,
)
from repro.api.models import MODELS, ModelFns, build_model, mlp_init, mlp_logits, mlp_nll
from repro.api.session import Session, build_session
from repro.gossip.engine import GossipEngine
from repro.api.spec import (
    DataSpec,
    ExperimentSpec,
    InferenceSpec,
    ObsSpec,
    RunSpec,
    ServeSpec,
    TopologySpec,
)

__all__ = [
    "ConjugateLinregEngine",
    "DataBundle",
    "DataSpec",
    "Engine",
    "ExperimentSpec",
    "GossipEngine",
    "InferenceSpec",
    "MODELS",
    "ModelFns",
    "ObsSpec",
    "RunSpec",
    "ServeSpec",
    "Session",
    "SimulatedEngine",
    "TopologySpec",
    "build_data",
    "build_model",
    "build_session",
    "mlp_init",
    "mlp_logits",
    "mlp_nll",
]
