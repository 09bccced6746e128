"""Fault-tolerant gossip: churn, corruption, and the quarantine guard.

The ladder this file pins (ROADMAP "Robustness"):

* the fault model is DETERMINISTIC — every crash/corruption decision is a
  pure function of (fault seed, round), so resumed sessions regenerate the
  identical schedule and a crashed-and-resumed run is bit-identical to an
  uninterrupted one;
* a crashed agent freezes: no local training, no fired edges, W-tilde row
  exactly e_i (the conserve rule keeps every row row-stochastic);
* under ``fault_policy="quarantine"`` injected NaN/Inf/huge payloads NEVER
  reach a healthy resident posterior, on every consensus execution;
* with ZERO faults the quarantined path is BITWISE identical to strict on
  every execution (dense masked, edge-list, delayed; the sharded
  ppermute rung runs under the ``multidevice`` marker);
* the Pallas validity kernel agrees with the XLA reference exactly.
"""
import dataclasses
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.flat import (
    FlatLayout,
    FlatPosterior,
    consensus_flat_masked,
    consensus_flat_masked_quarantined,
    consensus_flat_segments,
    consensus_flat_segments_quarantined,
    payload_validity,
    quarantine_w,
)
from repro.core.graphs import SparseGraph, bidirectional_ring_w
from repro.core.posterior import softplus
from repro.gossip.clocks import PoissonClock, build_clock
from repro.gossip.faults import FaultModel, FaultSpec


def _posts(n, p, seed=0):
    rng = np.random.default_rng(seed)
    layout = FlatLayout.for_pytree({"w": jnp.zeros((p,))})
    return FlatPosterior(
        mean=jnp.asarray(rng.normal(size=(n, p)), jnp.float32),
        rho=jnp.asarray(rng.normal(size=(n, p)) * 0.4 - 1.0, jnp.float32),
        layout=layout,
    )


def _mkspec(policy="strict", faults=None, clock=None, n=5, n_rounds=4,
            **inf_kw):
    from repro.api import (
        DataSpec, ExperimentSpec, InferenceSpec, RunSpec, TopologySpec,
    )

    clock = dict(clock or {"kind": "poisson", "rate": 0.8, "seed": 3})
    if faults is not None:
        clock["faults"] = dict(faults)
    return ExperimentSpec(
        topology=TopologySpec.gossip("bidirectional_ring", {"n": n},
                                     clock=clock),
        data=DataSpec(
            dataset_params=dict(n_classes=3, dim=8, n_train_per_class=30),
            partition="iid", partition_params=dict(n_agents=n),
            batch_size=4, local_updates=2,
        ),
        inference=InferenceSpec(hidden=8, depth=1, lr=1e-2,
                                fault_policy=policy, **inf_kw),
        run=RunSpec(n_rounds=n_rounds, seed=0),
    )


_FAULTS = {"crash_rate": 0.25, "recover_rate": 0.5, "corrupt_rate": 0.3,
           "corrupt_kind": "mix", "seed": 7}


# ---------------------------------------------------------------------------
# fault model: determinism, Markov semantics, spec validation
# ---------------------------------------------------------------------------


def test_fault_stream_is_pure_function_of_seed_and_round():
    """Two independently built models replay the identical schedule, and
    the access ORDER (sequential vs random, fresh vs warm memo) is
    irrelevant — the resume contract."""
    spec = FaultSpec(crash_rate=0.3, recover_rate=0.4, corrupt_rate=0.5,
                     seed=11)
    a = FaultModel(spec, 6)
    b = FaultModel(spec, 6)
    rounds = [9, 0, 4, 9, 2, 7]  # deliberately out of order on b
    for r in sorted(set(rounds)):
        _ = a.up(r)
    for r in rounds:
        np.testing.assert_array_equal(a.up(r), b.up(r))
        np.testing.assert_array_equal(a.corrupted(r), b.corrupted(r))
        fm_a, fr_a = a.fills(r)
        fm_b, fr_b = b.fills(r)
        np.testing.assert_array_equal(fm_a, fm_b)
        np.testing.assert_array_equal(fr_a, fr_b)


def test_fault_model_markov_semantics():
    """All agents start UP; crash_rate=0 never crashes; crash_rate>0 with
    recover_rate=1 means every down spell lasts exactly one window."""
    n = 8
    none = FaultModel(FaultSpec(), n)
    assert all(none.up(r).all() for r in range(5))
    assert not none.corrupted(3).any()
    flappy = FaultModel(
        FaultSpec(crash_rate=0.5, recover_rate=1.0, seed=3), n
    )
    assert flappy.up(0).all()
    for r in range(1, 12):
        down_prev = ~flappy.up(r - 1)
        # recover_rate=1: every agent down at r-1 is up at r
        assert flappy.up(r)[down_prev].all()
    # corruption only hits UP agents
    noisy = FaultModel(
        FaultSpec(crash_rate=0.4, recover_rate=0.3, corrupt_rate=0.9,
                  seed=5), n
    )
    for r in range(8):
        assert not (noisy.corrupted(r) & noisy.crashed(r)).any()


def test_fault_spec_validation_and_doc_roundtrip():
    with pytest.raises(ValueError, match="crash_rate"):
        FaultSpec(crash_rate=1.0).validate()
    with pytest.raises(ValueError, match="recover_rate"):
        FaultSpec(crash_rate=0.2, recover_rate=0.0).validate()
    with pytest.raises(ValueError, match="corrupt_kind"):
        FaultSpec(corrupt_kind="zeros").validate()
    with pytest.raises(ValueError, match="unknown FaultSpec keys"):
        FaultSpec.from_doc({"crash_rate": 0.1, "typo": 1})
    spec = FaultSpec(crash_rate=0.2, recover_rate=0.7, corrupt_rate=0.1,
                     corrupt_kind="nan", seed=9)
    assert FaultSpec.from_doc(spec.to_doc()) == spec


def test_faults_rejected_on_inner_clock_doc():
    """The fault model must sit on the OUTERMOST clock (wrappers bypass the
    inner clock's window construction) — loud error, not silent no-op."""
    W = bidirectional_ring_w(4)
    with pytest.raises(ValueError, match="OUTERMOST"):
        build_clock(
            {"kind": "failure_injected", "drop_rate": 0.1,
             "inner": {"kind": "poisson", "rate": 1.0,
                       "faults": {"crash_rate": 0.1}}},
            W,
        )
    # on the outermost doc it attaches fine, wrapper or not
    clock = build_clock(
        {"kind": "failure_injected", "drop_rate": 0.1,
         "inner": {"kind": "poisson", "rate": 1.0},
         "faults": {"crash_rate": 0.1, "seed": 2}},
        W,
    )
    assert clock.faults is not None


def test_crashed_agent_rows_are_identity_and_row_stochastic():
    """Clock-level churn: a crashed agent fires nothing and receives
    nothing — its W-tilde row is EXACTLY e_i — and every row of every
    window stays row-stochastic."""
    W = bidirectional_ring_w(6)
    clock = PoissonClock(W, rate=1.5, seed=1)
    clock.attach_faults(FaultModel(
        FaultSpec(crash_rate=0.4, recover_rate=0.5, seed=13), 6
    ))
    saw_crash = False
    for r in range(12):
        win = clock.window(r)
        crashed = clock.crashed(r)
        saw_crash |= bool(crashed.any())
        np.testing.assert_allclose(win.w_eff.sum(axis=1), 1.0, atol=1e-12)
        np.testing.assert_array_equal(
            win.w_eff[crashed], np.eye(6)[crashed]
        )
        assert not win.active[crashed].any()
    assert saw_crash  # the regime actually exercised a crash


# ---------------------------------------------------------------------------
# quarantine guard: validity, hand-computed window, kernel parity
# ---------------------------------------------------------------------------


def test_payload_validity_flags():
    p = 6
    layout = FlatLayout.for_pytree({"w": jnp.zeros((p,))})
    mean = np.zeros((5, p), np.float32)
    rho = np.zeros((5, p), np.float32)
    mean[1, 2] = np.nan          # non-finite prec*mu
    mean[2, 0] = np.inf          # non-finite prec*mu
    mean[3, 4] = 1.0e30          # finite but beyond the magnitude bound
    rho[4, 1] = np.nan           # non-finite prec
    ok = np.asarray(payload_validity(jnp.asarray(mean), jnp.asarray(rho)))
    np.testing.assert_array_equal(ok, [True, False, False, False, False])
    del layout


def test_payload_validity_fused_matches_xla_reference():
    """The Pallas single-pass validity kernel (interpret mode on CPU) is
    bit-equal to the XLA reference, including on garbage inputs."""
    rng = np.random.default_rng(5)
    n, p = 6, 512
    mean = rng.normal(size=(n, p)).astype(np.float32)
    rho = (rng.normal(size=(n, p)) * 0.4).astype(np.float32)
    mean[1, 100] = np.nan
    mean[2, 0] = np.inf
    mean[3, 511] = 5.0e29  # large but within bound * prec scale
    rho[4, 7] = np.inf     # prec -> 0: positivity violation
    ref = payload_validity(jnp.asarray(mean), jnp.asarray(rho), mode="xla")
    got = payload_validity(
        jnp.asarray(mean), jnp.asarray(rho), mode="interpret", block=128
    )
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_quarantine_w_reassigns_dropped_mass_to_self():
    rng = np.random.default_rng(3)
    n = 5
    W = rng.random((n, n)) + 0.1
    W = (W / W.sum(1, keepdims=True)).astype(np.float32)
    valid = np.array([True, False, True, True, False])
    Wq = np.asarray(quarantine_w(jnp.asarray(W), jnp.asarray(valid)))
    np.testing.assert_allclose(Wq.sum(axis=1), 1.0, atol=1e-6)
    # invalid columns zeroed everywhere except the self-loop
    for j in np.nonzero(~valid)[0]:
        off = [i for i in range(n) if i != j]
        assert (Wq[off, j] == 0.0).all()
        assert Wq[j, j] > 0.0  # an agent never quarantines itself
    # all-valid is the identity
    np.testing.assert_array_equal(
        np.asarray(quarantine_w(jnp.asarray(W),
                                jnp.ones(n, bool))), W
    )


def test_quarantined_window_hand_computed_three_agents():
    """A 3-agent window with agent 2's WIRE payload poisoned: receivers 0
    and 1 must reproduce the hand-derived eq.-(6) merge with agent 2's
    weight moved to their self-loops; agent 2's own resident state (still
    healthy — only its transmission was garbage) merges from its TRUE
    stats and the healthy neighbors."""
    n, p = 3, 4
    posts = _posts(n, p, seed=42)
    W = jnp.asarray(
        [[0.6, 0.2, 0.2], [0.3, 0.5, 0.2], [0.25, 0.25, 0.5]], jnp.float32
    )
    active = jnp.ones((n,), bool)
    mean_src = posts.mean.at[2].set(jnp.nan)  # poisoned transmission
    rho_src = posts.rho
    out, valid = consensus_flat_masked_quarantined(
        posts, W, active, mean_src=mean_src, rho_src=rho_src
    )
    np.testing.assert_array_equal(np.asarray(valid), [True, True, False])

    mean = np.asarray(posts.mean, np.float64)
    sig = np.asarray(softplus(posts.rho), np.float64)
    prec = 1.0 / sig**2
    Wq = np.asarray(W, np.float64).copy()
    for i in range(n):
        if i != 2:
            Wq[i, i] += Wq[i, 2]
            Wq[i, 2] = 0.0
    # agent 2's own row: its self-contribution falls back to its TRUE
    # resident stats (it is healthy; only the wire copy was poisoned)
    exp_prec = Wq @ prec
    exp_mean = (Wq @ (prec * mean)) / exp_prec
    got_sig = np.asarray(softplus(out.rho), np.float64)
    np.testing.assert_allclose(np.asarray(out.mean), exp_mean,
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(1.0 / got_sig**2, exp_prec,
                               rtol=1e-4, atol=1e-5)
    assert np.isfinite(np.asarray(out.mean)).all()


def _fired_edges(w_eff):
    """A window's W-tilde as the edge-native window carries it: the fired
    non-self edges (dst, src, w), the per-agent self weights, and the row
    bound (max in-degree, self included)."""
    g = SparseGraph.from_dense(np.asarray(w_eff))
    dst, src, w = g.edge_arrays()
    fired = dst != src
    self_w = np.diagonal(np.asarray(w_eff)).astype(np.float32)
    return (jnp.asarray(dst[fired]), jnp.asarray(src[fired]),
            jnp.asarray(w[fired]), jnp.asarray(self_w), g.max_in_degree)


def test_zero_fault_quarantine_bitwise_dense_and_sparse_kernels():
    """Kernel rung of the ladder: with every payload valid the quarantined
    wrappers are BITWISE the plain kernels (dense masked and edge-list,
    xla and interpreted-Pallas modes)."""
    n, p = 6, 256
    posts = _posts(n, p, seed=9)
    win = PoissonClock(bidirectional_ring_w(n), rate=0.7, seed=2).window(0)
    W = jnp.asarray(win.w_eff, jnp.float32)
    active = jnp.asarray(win.active)
    for mode in ("xla", "interpret"):
        ref = consensus_flat_masked(posts, W, active, mode=mode, block=128)
        got, valid = consensus_flat_masked_quarantined(
            posts, W, active, mode=mode, block=128
        )
        assert bool(jnp.all(valid))
        np.testing.assert_array_equal(np.asarray(got.mean),
                                      np.asarray(ref.mean))
        np.testing.assert_array_equal(np.asarray(got.rho),
                                      np.asarray(ref.rho))
    dst, src, w, self_w, slots = _fired_edges(win.w_eff)
    ar = jnp.arange(n, dtype=dst.dtype)
    for mode in ("xla", "interpret"):
        ref = consensus_flat_segments(
            posts, jnp.concatenate([dst, ar]), jnp.concatenate([src, ar]),
            jnp.concatenate([w, self_w]), active=active, slots=slots,
            mode=mode, block=128,
        )
        got, valid = consensus_flat_segments_quarantined(
            posts, dst, src, w, self_w, active=active, slots=slots,
            mode=mode, block=128,
        )
        assert bool(jnp.all(valid))
        np.testing.assert_array_equal(np.asarray(got.mean),
                                      np.asarray(ref.mean))
        np.testing.assert_array_equal(np.asarray(got.rho),
                                      np.asarray(ref.rho))


def test_sparse_quarantine_drops_invalid_neighbor_mass_to_self():
    n, p = 5, 32
    posts = _posts(n, p, seed=21)
    win = PoissonClock(bidirectional_ring_w(n), rate=2.0, seed=4).window(0)
    W = jnp.asarray(win.w_eff, jnp.float32)
    active = jnp.asarray(win.active)
    mean_src = posts.mean.at[1].set(jnp.inf)
    dst, src, w, self_w, _ = _fired_edges(win.w_eff)
    got_s, valid_s = consensus_flat_segments_quarantined(
        posts, dst, src, w, self_w, active=active,
        mean_src=mean_src, rho_src=posts.rho,
    )
    got_d, valid_d = consensus_flat_masked_quarantined(
        posts, W, active, mean_src=mean_src, rho_src=posts.rho,
    )
    # the edge-list guard flags each fired edge by its source's payload
    np.testing.assert_array_equal(np.asarray(valid_s),
                                  np.asarray(valid_d)[np.asarray(src)])
    assert not bool(jnp.all(valid_s))  # agent 1's fired edges are dropped
    np.testing.assert_allclose(np.asarray(got_s.mean),
                               np.asarray(got_d.mean), rtol=1e-6, atol=1e-6)
    assert np.isfinite(np.asarray(got_s.mean)).all()


# ---------------------------------------------------------------------------
# engine / session: poison containment, bitwise ladder, resume, telemetry
# ---------------------------------------------------------------------------


def test_quarantine_contains_injection_strict_propagates():
    """Acceptance: under quarantine the injected NaN/Inf NEVER reaches a
    resident posterior; the identical chaos under strict poisons agents —
    the guard, not luck, is doing the work."""
    from repro.api import build_session

    n, rounds = 5, 5
    s_q = build_session(_mkspec("quarantine", _FAULTS, n=n))
    s_s = build_session(_mkspec("strict", _FAULTS, n=n))
    for _ in range(rounds):
        rec = s_q.round()
        if rec["loss"] is not None:
            assert np.isfinite(rec["loss"])
        s_s.round()
    hq, hs = s_q.health(), s_s.health()
    assert hq["all_ok"], f"quarantine leaked garbage: {hq}"
    assert np.isfinite(np.asarray(s_q.posterior().mean)).all()
    assert np.isfinite(np.asarray(s_q.posterior().rho)).all()
    assert hs["n_healthy"] < n, "strict survived: injection too weak"
    # telemetry: the guard counted its drops
    tel = s_q.evaluate(n_mc=1)["engine"]
    assert tel["faults"]["policy"] == "quarantine"
    assert tel["faults"]["quarantined"]["total"] > 0
    assert len(tel["faults"]["uptime"]["per_agent"]) == n


def test_zero_fault_quarantine_bitwise_engine_instant_and_delayed():
    """Engine rung: no fault model => quarantine sessions are BITWISE the
    strict sessions, on the instant-masked AND the delayed-gather paths."""
    from repro.api import build_session

    instant = {"kind": "poisson", "rate": 0.8, "seed": 3}
    delayed = {"kind": "delayed", "max_delay": 2, "seed": 5,
               "inner": instant}
    for clock in (instant, delayed):
        posts = {}
        for policy in ("strict", "quarantine"):
            s = build_session(_mkspec(policy, None, clock=clock))
            for _ in range(4):
                s.round()
            posts[policy] = s.posterior()
        np.testing.assert_array_equal(
            np.asarray(posts["strict"].mean),
            np.asarray(posts["quarantine"].mean),
        )
        np.testing.assert_array_equal(
            np.asarray(posts["strict"].rho),
            np.asarray(posts["quarantine"].rho),
        )


def test_delayed_chaos_quarantine_stays_finite():
    """Delivery latency + churn + corruption + quarantine: the gathered
    stale payloads are validated per EVENT; posteriors stay finite."""
    from repro.api import build_session

    clock = {"kind": "delayed", "max_delay": 2, "seed": 5,
             "inner": {"kind": "poisson", "rate": 0.9, "seed": 3}}
    s = build_session(_mkspec("quarantine", _FAULTS, clock=clock,
                              n_rounds=6))
    for _ in range(6):
        s.round()
    assert s.health()["all_ok"]
    assert int(np.asarray(s.state.n_quarantined).sum()) > 0


def test_crashed_and_resumed_run_is_bit_identical(tmp_path):
    """Acceptance: save mid-run under active churn+corruption, reload, run
    to the end — posterior, quarantine counters and fault schedule all
    BIT-identical to the uninterrupted run (the fault stream is a pure
    function of (seed, round), not of process history)."""
    from repro.api import Session, build_session

    mk = lambda: build_session(_mkspec("quarantine", _FAULTS, n_rounds=6))
    s_ref = mk()
    for _ in range(6):
        s_ref.round()

    s_a = mk()
    for _ in range(3):
        s_a.round()
    path = str(tmp_path / "chaos.ckpt")
    s_a.save(path)
    s_b = Session.load(path)
    crashes = []
    for _ in range(3):
        rec = s_b.round()
        crashes.append(rec["n_crashed"])
    # the resumed process replays the identical crash schedule
    s_c = mk()
    for i in range(6):
        rec = s_c.round()
        if i >= 3:
            assert rec["n_crashed"] == crashes[i - 3]
    np.testing.assert_array_equal(np.asarray(s_b.posterior().mean),
                                  np.asarray(s_ref.posterior().mean))
    np.testing.assert_array_equal(np.asarray(s_b.posterior().rho),
                                  np.asarray(s_ref.posterior().rho))
    np.testing.assert_array_equal(np.asarray(s_b.state.n_quarantined),
                                  np.asarray(s_ref.state.n_quarantined))


def test_session_round_reports_n_crashed_and_nan_safe_loss():
    """Satellite: ``Session.round`` reports n_crashed under a fault model
    and the loss mean excludes crashed agents (their NaN sentinel)."""
    from repro.api import build_session

    faults = {"crash_rate": 0.4, "recover_rate": 0.5, "seed": 13}
    s = build_session(_mkspec("strict", faults))
    saw = False
    for _ in range(5):
        rec = s.round()
        assert rec["n_crashed"] + rec["n_trained"] == 5
        if rec["n_crashed"]:
            saw = True
            assert rec["loss"] is None or np.isfinite(rec["loss"])
    assert saw, "churn regime never crashed an agent in 5 windows"
    # no fault model => the key is absent (dict contract unchanged)
    s0 = build_session(_mkspec("strict", None))
    assert "n_crashed" not in s0.round()


def test_session_health_probe():
    from repro.api import build_session

    s = build_session(_mkspec("strict", None))
    s.round()
    h = s.health()
    assert h == {"ok": [True] * 5, "n_healthy": 5, "all_ok": True}
    # poison one resident posterior by hand: the probe localizes it
    bad = s.state.posterior.mean.at[2, 0].set(jnp.nan)
    s.state = dataclasses.replace(
        s.state, posterior=dataclasses.replace(s.state.posterior, mean=bad)
    )
    h = s.health()
    assert h["ok"] == [True, True, False, True, True]
    assert h["n_healthy"] == 4 and not h["all_ok"]


def test_fault_policy_spec_validation():
    from repro.api import InferenceSpec

    with pytest.raises(ValueError, match="fault_policy"):
        InferenceSpec(fault_policy="lenient").validate()
    with pytest.raises(ValueError, match="quarantine"):
        InferenceSpec(fault_policy="quarantine",
                      consensus="mean_only").validate()
    spec = _mkspec("quarantine", None)
    spec.validate()
    # quarantine without a gossip topology is rejected eagerly
    from repro.api import TopologySpec

    with pytest.raises(ValueError, match="gossip"):
        dataclasses.replace(
            spec, topology=TopologySpec.complete(5)
        ).validate()
    # corruption without a gaussian exchange is rejected at engine build
    from repro.api import build_session

    bad = _mkspec("strict",
                  {"corrupt_rate": 0.5, "seed": 1}, consensus="none")
    with pytest.raises(ValueError, match="corruption"):
        build_session(bad)


def test_strict_no_fault_state_structure_unchanged():
    """Structural gate: a strict no-fault gossip state has NO extra leaves
    (n_quarantined is an empty subtree), so pre-fault checkpoints keep
    loading positionally."""
    from repro.api import build_session

    s = build_session(_mkspec("strict", None))
    assert s.state.n_quarantined is None
    assert not s.engine._guarded
    sq = build_session(_mkspec("quarantine", None))
    assert sq.engine._guarded
    leaves_strict = len(jax.tree.leaves(s.state))
    leaves_q = len(jax.tree.leaves(sq.state))
    assert leaves_q == leaves_strict + 1


# ---------------------------------------------------------------------------
# sharded rung: ppermute quarantine under 8 virtual devices
# ---------------------------------------------------------------------------

_SHARD_PRELUDE = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
"""


@pytest.mark.slow
@pytest.mark.multidevice
def test_ppermute_quarantine_zero_fault_bitwise_and_containment():
    """Sharded rung of the ladder: on an 8-virtual-device agent mesh the
    quarantined ppermute window is (a) BITWISE the strict ppermute window
    with every payload valid, and (b) finite + equal to the dense
    quarantined merge when one agent's payload is poisoned."""
    from conftest import run_multidevice_subprocess

    run_multidevice_subprocess(_SHARD_PRELUDE + textwrap.dedent("""
    from repro.core.flat import (FlatLayout, FlatPosterior,
                                 consensus_flat_masked,
                                 consensus_flat_masked_quarantined)
    from repro.core.graphs import bidirectional_ring_w
    from repro.gossip.clocks import PoissonClock

    n, p = 8, 192
    ks = jax.random.split(jax.random.key(0), 2)
    layout = FlatLayout.for_pytree({"w": jnp.zeros((p,))})
    posts = FlatPosterior(
        mean=jax.random.normal(ks[0], (n, p)),
        rho=jax.random.normal(ks[1], (n, p)) * 0.4 - 1.0,
        layout=layout,
    )
    clock = PoissonClock(bidirectional_ring_w(n), rate=0.7, seed=2)
    for S in (2, 4, 8):
        mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:S]), ("agents",))
        for r in range(3):
            win = clock.window(r)
            W = jnp.asarray(win.w_eff, jnp.float32)
            act = jnp.asarray(win.active)
            ref = consensus_flat_masked(
                posts, W, act, mode="ppermute", mesh=mesh, axis="agents",
                window=win)
            got, valid = consensus_flat_masked_quarantined(
                posts, W, act, mode="ppermute", mesh=mesh, axis="agents",
                window=win)
            assert bool(jnp.all(valid)), (S, r)
            assert bool(jnp.all(got.mean == ref.mean)), (S, r)
            assert bool(jnp.all(got.rho == ref.rho)), (S, r)
            # poison one agent's wire payload: sharded quarantine must
            # agree with the dense quarantined merge and stay finite
            mean_src = posts.mean.at[3].set(jnp.nan)
            gq, vq = consensus_flat_masked_quarantined(
                posts, W, act, mean_src=mean_src, rho_src=posts.rho,
                mode="ppermute", mesh=mesh, axis="agents", window=win)
            dq, vd = consensus_flat_masked_quarantined(
                posts, W, act, mean_src=mean_src, rho_src=posts.rho)
            assert bool(jnp.all(vq == vd)), (S, r)
            assert bool(jnp.all(jnp.isfinite(gq.mean))), (S, r)
            assert bool(jnp.all(gq.mean == dq.mean)), (S, r)
            assert bool(jnp.all(gq.rho == dq.rho)), (S, r)
    print("OK")
    """))


@pytest.mark.slow
@pytest.mark.multidevice
def test_gossip_engine_ppermute_quarantine_session():
    """Engine level, sharded: a quarantined chaos session on
    consensus_impl='ppermute' stays finite, and its zero-fault twin is
    BITWISE the strict ppermute session."""
    from conftest import run_multidevice_subprocess

    run_multidevice_subprocess(_SHARD_PRELUDE + textwrap.dedent("""
    from repro.api import (DataSpec, ExperimentSpec, InferenceSpec, RunSpec,
                           TopologySpec, build_session)

    n = 8
    def spec(policy, faults):
        clock = {"kind": "poisson", "rate": 0.7, "seed": 3}
        if faults:
            clock["faults"] = dict(faults)
        return ExperimentSpec(
            topology=TopologySpec.gossip("bidirectional_ring", {"n": n},
                                         clock=clock),
            data=DataSpec(
                dataset_params=dict(n_classes=3, dim=8, n_train_per_class=30),
                partition="iid", partition_params=dict(n_agents=n),
                batch_size=4, local_updates=2),
            inference=InferenceSpec(hidden=8, depth=1, lr=1e-2,
                                    consensus_impl="ppermute",
                                    fault_policy=policy),
            run=RunSpec(n_rounds=3, seed=0),
        )

    posts = {}
    for policy in ("strict", "quarantine"):
        s = build_session(spec(policy, None))
        for _ in range(3):
            s.round()
        posts[policy] = s.posterior()
    assert bool(jnp.all(posts["strict"].mean == posts["quarantine"].mean))
    assert bool(jnp.all(posts["strict"].rho == posts["quarantine"].rho))

    faults = {"crash_rate": 0.25, "recover_rate": 0.5, "corrupt_rate": 0.3,
              "seed": 7}
    s = build_session(spec("quarantine", faults))
    for _ in range(4):
        s.round()
    assert s.health()["all_ok"], s.health()
    tel = s.evaluate(n_mc=1)["engine"]
    assert tel["faults"]["quarantined"]["total"] >= 0
    print("OK")
    """))


def test_edge_keep_mask_matches_per_event_loop():
    """The vectorized edge-list crash filter must agree event-by-event with
    the obvious per-event loop — instant delivery AND lagged fire times."""
    from repro.gossip.faults import edge_keep_mask

    model = FaultModel(
        FaultSpec(crash_rate=0.4, recover_rate=0.5, seed=11), 10)
    rng = np.random.default_rng(5)
    for r in range(3, 8):
        e = 40
        dst = rng.integers(0, 10, e)
        src = rng.integers(0, 10, e)
        lags = rng.integers(0, 3, e)
        got_instant = edge_keep_mask(model, r, dst, src)
        got_lagged = edge_keep_mask(model, r, dst, src, lags=lags)
        up = {k: model.up(k) for k in range(r - 2, r + 1)}
        for i in range(e):
            assert got_instant[i] == (up[r][dst[i]] and up[r][src[i]])
            assert got_lagged[i] == (
                up[r][dst[i]] and up[r - int(lags[i])][src[i]]
            )
        # with crash_rate 0.4 over 40 edges some must drop, some survive
        assert got_lagged.any() and not got_lagged.all()
