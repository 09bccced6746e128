"""The declarative API (repro.api): spec validation, engine equivalence,
session checkpoint round-trip with the embedded ExperimentSpec, first-class
topology schedules, and the flat-default satellite flips."""
import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import (
    DataSpec,
    ExperimentSpec,
    InferenceSpec,
    RunSpec,
    Session,
    TopologySpec,
    build_session,
)
from repro.core.flat import FlatPosterior


def _tiny_spec(engine="simulated", n_rounds=3, seed=0):
    """3-agent star, 8-dim 3-class synthetic task, 2 local steps of batch 4 —
    small enough that an engine-equivalence round trip runs in seconds."""
    return ExperimentSpec(
        topology=TopologySpec.star(n_edge=2, a=0.5),
        data=DataSpec(
            dataset_params=dict(n_classes=3, dim=8, n_train_per_class=30),
            partition="star",
            partition_params=dict(center_labels=[1, 2], edge_labels=[0], n_edge=2),
            batch_size=4,
            local_updates=2,
        ),
        inference=InferenceSpec(hidden=8, depth=1, lr=1e-2),
        run=RunSpec(n_rounds=n_rounds, seed=seed, engine=engine),
    )


# ---------------------------------------------------------------------------
# engine equivalence: the acceptance gate for the launch-path rewiring
# ---------------------------------------------------------------------------


def test_simulated_and_launch_engines_agree():
    """SimulatedEngine (core.simulated flat runtime) and LaunchEngine
    (launch.steps make_local_step/make_consensus_step on FlatPosterior)
    produce allclose posteriors over 3 rounds on a tiny star network — the
    production hot loop runs the same math as the reference runtime, flat
    end-to-end."""
    from repro.launch.steps import BayesTrainState

    s_sim = build_session(_tiny_spec(engine="simulated"))
    s_launch = build_session(_tiny_spec(engine="launch"))
    h_sim = s_sim.run()
    h_launch = s_launch.run()
    del h_sim, h_launch

    assert isinstance(s_launch.state, BayesTrainState)
    p_sim, p_launch = s_sim.posterior(), s_launch.posterior()
    # no pytree posterior in the launch hot loop
    assert isinstance(p_launch, FlatPosterior)
    assert isinstance(p_sim, FlatPosterior)
    np.testing.assert_allclose(
        np.asarray(p_sim.mean), np.asarray(p_launch.mean), atol=1e-5, rtol=1e-5
    )
    np.testing.assert_allclose(
        np.asarray(p_sim.rho), np.asarray(p_launch.rho), atol=1e-5, rtol=1e-5
    )
    # and the training actually moved the posterior
    fresh = build_session(_tiny_spec(engine="simulated")).posterior()
    assert float(jnp.max(jnp.abs(p_sim.mean - fresh.mean))) > 1e-4


def test_launch_engine_evaluate_matches_simulated():
    s_sim = build_session(_tiny_spec(engine="simulated"))
    s_launch = build_session(_tiny_spec(engine="launch"))
    s_sim.run()
    s_launch.run()
    ev_sim = s_sim.evaluate()
    ev_launch = s_launch.evaluate()
    np.testing.assert_allclose(ev_sim["acc"], ev_launch["acc"], atol=1e-6)


# ---------------------------------------------------------------------------
# eager spec validation
# ---------------------------------------------------------------------------


def _iid_spec(topology, n_agents):
    return ExperimentSpec(
        topology=topology,
        data=DataSpec(
            dataset_params=dict(n_classes=2, dim=4, n_train_per_class=10),
            partition="iid",
            partition_params=dict(n_agents=n_agents),
        ),
    )


def test_disconnected_w_rejected():
    bad = np.eye(2)  # two isolated agents: no strongly connected support
    with pytest.raises(ValueError, match="strongly connected"):
        build_session(_iid_spec(TopologySpec.explicit(bad), 2))


def test_non_row_stochastic_w_rejected():
    bad = np.array([[0.5, 0.6], [0.5, 0.5]])
    with pytest.raises(ValueError, match="row-stochastic"):
        build_session(_iid_spec(TopologySpec.explicit(bad), 2))


def test_agent_count_mismatch_rejected():
    with pytest.raises(ValueError, match="3 agents"):
        build_session(_iid_spec(TopologySpec.complete(3), 4))


def test_schedule_union_connectivity_enforced():
    # two slots whose union still leaves agent 2 isolated
    w_a = np.array([[0.5, 0.5, 0.0], [0.5, 0.5, 0.0], [0.0, 0.0, 1.0]])
    with pytest.raises(ValueError, match="union"):
        TopologySpec.from_schedule([w_a, w_a]).validate()


def test_unknown_enum_fields_rejected():
    with pytest.raises(ValueError, match="engine"):
        _tiny_spec().run.__class__(engine="warp").validate()
    with pytest.raises(ValueError, match="consensus"):
        InferenceSpec(consensus="median").validate()
    with pytest.raises(ValueError, match="dataset"):
        DataSpec(dataset="imagenet").validate()


def test_callable_topology_not_checkpoint_embeddable():
    spec = dataclasses.replace(
        _tiny_spec(),
        topology=TopologySpec.from_callable(lambda r: np.eye(3), n_agents=3),
    )
    with pytest.raises(ValueError, match="callable"):
        spec.to_doc()


# ---------------------------------------------------------------------------
# first-class topology schedules (Callable[[int], W])
# ---------------------------------------------------------------------------


def test_run_rounds_accepts_callable_schedule():
    from repro.core.simulated import as_w_schedule

    mats = [np.eye(2), np.full((2, 2), 0.5)]
    fn = as_w_schedule(lambda r: mats[r % 2])
    np.testing.assert_array_equal(fn(0), mats[0])
    np.testing.assert_array_equal(fn(3), mats[1])
    # list and static forms normalize through the same helper
    np.testing.assert_array_equal(as_w_schedule(mats)(1), mats[1])
    np.testing.assert_array_equal(as_w_schedule(mats[0])(7), mats[0])


def test_session_run_callable_schedule_matches_list_schedule():
    """Session.run(w_schedule=callable) == the same schedule as a list —
    the table3 time-varying port relies on this."""
    from repro.core.graphs import time_varying_star_schedule

    mats = time_varying_star_schedule(2, 1, a=0.5)

    def build(n_agents=3):
        return build_session(ExperimentSpec(
            topology=TopologySpec.time_varying_star(2, 1, a=0.5),
            data=DataSpec(
                dataset_params=dict(n_classes=2, dim=4, n_train_per_class=12),
                partition="iid",
                partition_params=dict(n_agents=3),
                batch_size=4,
                local_updates=1,
            ),
            inference=InferenceSpec(hidden=4, depth=1),
            run=RunSpec(n_rounds=4, seed=1),
        ))

    s_list = build()
    s_callable = build()
    s_list.run(w_schedule=[np.asarray(m) for m in mats])
    s_callable.run(w_schedule=lambda r: mats[r % len(mats)])
    np.testing.assert_array_equal(
        np.asarray(s_list.posterior().mean), np.asarray(s_callable.posterior().mean)
    )


# ---------------------------------------------------------------------------
# self-describing session checkpoints (embedded ExperimentSpec)
# ---------------------------------------------------------------------------


def test_session_checkpoint_roundtrip_and_resume(tmp_path):
    """save -> load rebuilds the session FROM THE EMBEDDED SPEC (no `like`
    tree) and resuming both sessions stays bit-identical."""
    s = build_session(_tiny_spec(n_rounds=5))
    s.run(2)
    path = os.path.join(tmp_path, "sess.ckpt")
    s.save(path)

    s2 = Session.load(path)
    assert s2.round_idx == 2
    assert s2.spec == s.spec  # the embedded spec round-trips exactly
    np.testing.assert_array_equal(
        np.asarray(s2.posterior().mean), np.asarray(s.posterior().mean)
    )
    s.run(2)
    s2.run(2)
    np.testing.assert_array_equal(
        np.asarray(s2.posterior().mean), np.asarray(s.posterior().mean)
    )
    np.testing.assert_array_equal(
        np.asarray(s2.posterior().rho), np.asarray(s.posterior().rho)
    )


def test_session_checkpoint_is_zstd_framed(tmp_path):
    """The session document is one zstd frame (magic 28 b5 2f fd) and
    loads back to the same posterior and spec."""
    s = build_session(_tiny_spec(n_rounds=2))
    s.run()
    path = os.path.join(tmp_path, "sess_zstd.ckpt")
    s.save(path)
    with open(path, "rb") as f:
        assert f.read(4) == b"\x28\xb5\x2f\xfd"
    s2 = Session.load(path)
    np.testing.assert_array_equal(
        np.asarray(s2.posterior().mean), np.asarray(s.posterior().mean)
    )
    assert s2.spec == s.spec


def test_spec_doc_roundtrip_explicit_w():
    W = np.array([[0.5, 0.5], [0.25, 0.75]])
    spec = dataclasses.replace(
        _tiny_spec(),
        topology=TopologySpec.explicit(W),
        data=DataSpec(
            dataset_params=dict(n_classes=2, dim=4, n_train_per_class=10),
            partition="iid",
            partition_params=dict(n_agents=2),
        ),
    )
    doc = spec.to_doc()
    back = ExperimentSpec.from_doc(doc)
    np.testing.assert_array_equal(np.asarray(back.topology.w), W)
    assert back.inference == spec.inference
    assert back.run == spec.run


# ---------------------------------------------------------------------------
# conjugate linreg engine (paper Example 1 through the same front door)
# ---------------------------------------------------------------------------


def test_conjugate_linreg_session_reaches_noise_floor():
    spec = ExperimentSpec(
        topology=TopologySpec.complete(4),
        data=DataSpec(dataset="linreg", batch_size=10),
        inference=InferenceSpec(method="conjugate_linreg"),
        run=RunSpec(n_rounds=60, seed=0),
    )
    s = build_session(spec)
    s.run()
    ev = s.evaluate()
    noise_floor = float(s.data.dataset.noise_std) ** 2
    assert ev["avg_mse"] < noise_floor * 1.2, ev


def test_linreg_requires_conjugate_method():
    with pytest.raises(ValueError, match="conjugate_linreg"):
        ExperimentSpec(data=DataSpec(dataset="linreg")).validate()


# ---------------------------------------------------------------------------
# satellite: flat-by-default flips
# ---------------------------------------------------------------------------


def test_init_network_flat_default_and_deprecation():
    from repro.core.simulated import init_network
    from repro.optim import adam

    def init_params(key):
        return {"w": jax.random.normal(key, (4, 2))}

    opt = adam()
    state = init_network(jax.random.key(0), 3, init_params, opt)
    assert isinstance(state.posterior, FlatPosterior)  # flat IS the default
    with pytest.warns(DeprecationWarning, match="flat"):
        legacy = init_network(jax.random.key(0), 3, init_params, opt, flat=False)
    assert not isinstance(legacy.posterior, FlatPosterior)
    # both hold the same values
    np.testing.assert_allclose(
        np.asarray(state.posterior.mean),
        np.asarray(legacy.posterior.mean["w"].reshape(3, -1)),
        atol=1e-6,
    )


def test_launch_init_train_state_flat_default():
    from repro.configs import get_config
    from repro.launch.steps import init_train_state, serve_params
    from repro.optim import adam

    cfg = get_config("repro-100m").reduced()
    state = init_train_state(jax.random.key(0), cfg, 2, adam())
    assert isinstance(state.posterior, FlatPosterior)
    assert state.posterior.mean.ndim == 2  # [A, P]
    sp = serve_params(state.posterior)
    assert all(l.dtype == jnp.bfloat16 for l in jax.tree.leaves(sp))


def test_quickstart_runs_on_the_spec_api():
    """Acceptance: the quickstart has no direct simulated-runtime wiring."""
    src = open(os.path.join(os.path.dirname(__file__), "..",
                            "examples", "quickstart.py")).read()
    assert "init_network" not in src
    assert "make_round_fn" not in src
    assert "ExperimentSpec(" in src and "build_session" in src
