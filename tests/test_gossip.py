"""Event-driven gossip runtime (repro.gossip): clock determinism and
Assumption-1 validation, the masked active-edge consensus kernels
(bit-identical all-active equivalence + bit-stable passthrough), the
GossipEngine on the Engine protocol (one jitted call per window, resume,
staleness telemetry), the time_varying_star re-expression, the
delivery-latency runtime (DelayedClock + [K, N, P] history ring), the
sharded window consensus (consensus_ppermute_window equivalence ladder,
8-virtual-device subprocess), and the gossip-window roofline satellite."""
import os
import textwrap

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
from repro.core.flat import (
    consensus_flat,
    consensus_flat_masked,
    consensus_flat_segments,
)
from repro.core.graphs import (
    SparseGraph,
    bidirectional_ring_w,
    complete_w,
    time_varying_star_schedule,
)
from repro.core.numerics import softplus, softplus_inv
from repro.gossip.clocks import (
    DelayedClock,
    FailureInjectedClock,
    PoissonClock,
    RoundRobinClock,
    TraceClock,
    all_edges_trace,
    build_clock,
    trace_from_schedule,
    window_from_events,
    _directed_edges,
)
from repro.kernels.consensus import (
    consensus_fused_masked,
    consensus_fused_network,
)
from repro.launch.costmodel import consensus_roofline, gossip_window_roofline


def _rand_posts(n, p, seed=0):
    ks = jax.random.split(jax.random.key(seed), 2)
    mean = jax.random.normal(ks[0], (n, p))
    rho = jax.random.normal(ks[1], (n, p)) * 0.4 - 1.0
    return mean, rho


def _gossip_data(n_agents, local_updates=2):
    return DataSpec(
        dataset_params=dict(n_classes=3, dim=8, n_train_per_class=30),
        partition="iid",
        partition_params=dict(n_agents=n_agents),
        batch_size=4,
        local_updates=local_updates,
    )


def _gossip_spec(topology, n_agents, n_rounds=3, seed=0, **inf_kw):
    return ExperimentSpec(
        topology=topology,
        data=_gossip_data(n_agents),
        inference=InferenceSpec(hidden=8, depth=1, lr=1e-2, **inf_kw),
        run=RunSpec(n_rounds=n_rounds, seed=seed),
    )


# ---------------------------------------------------------------------------
# clocks: determinism, windows, validation
# ---------------------------------------------------------------------------


def test_poisson_clock_deterministic_and_row_stochastic():
    W = bidirectional_ring_w(6)
    c = PoissonClock(W, rate=0.8, seed=3)
    for r in range(6):
        a, b = c.window(r), c.window(r)
        np.testing.assert_array_equal(a.edges, b.edges)
        np.testing.assert_array_equal(a.w_eff, b.w_eff)
        np.testing.assert_allclose(a.w_eff.sum(axis=1), 1.0, atol=1e-12)
        # inactive rows are EXACTLY e_i (the engine's mask contract)
        inactive = ~a.active
        np.testing.assert_array_equal(
            a.w_eff[inactive], np.eye(6)[inactive]
        )
        assert a.edges.shape == (c.e_max, 2)  # static shapes across windows


def test_round_robin_cycles_all_edges():
    W = bidirectional_ring_w(4)
    c = RoundRobinClock(W, edges_per_window=2)
    fired = set()
    for r in range(len(_directed_edges(W)) // 2):
        w = c.window(r)
        fired.update(map(tuple, w.edges[: w.n_events].tolist()))
    assert fired == set(_directed_edges(W))  # one cycle covers the graph


def test_failure_injection_drops_but_preserves_union():
    W = complete_w(5)
    inner = PoissonClock(W, rate=5.0, seed=1)
    c = FailureInjectedClock(inner, drop_rate=0.5, seed=2)
    dropped = sum(
        inner.window(r).n_events - c.window(r).n_events for r in range(8)
    )
    assert dropped > 0
    np.testing.assert_array_equal(c.union_support(), inner.union_support())
    c.validate()  # union still satisfies Assumption 1


def test_window_feasibility_and_event_checks():
    W = bidirectional_ring_w(4)
    with pytest.raises(ValueError, match="self-event"):
        window_from_events(W, [(1, 1)], e_max=4)
    with pytest.raises(ValueError, match="not an edge"):
        window_from_events(W, [(0, 2)], e_max=4)  # ring: 0-2 not adjacent
    # weight-table row over-commitment is rejected
    table = np.array([[1.0, 0.6, 0.6], [0.5, 1.0, 0.0], [0.5, 0.0, 1.0]])
    with pytest.raises(ValueError, match="row-feasible"):
        window_from_events(table, [(0, 1), (0, 2)], e_max=4, rule="table")


def test_trace_clock_conserve_requires_row_stochastic_base():
    """Review regression: a non-row-stochastic base under rule="conserve"
    would silently produce non-row-stochastic windows."""
    W_bad = bidirectional_ring_w(4) * 1.5
    with pytest.raises(ValueError, match="row-stochastic"):
        TraceClock(W_bad, [[(0, 1)]], rule="conserve")


def test_gossip_convenience_rejects_w_with_named_base():
    """Review regression: gossip(w=...) with a named base would silently
    drop the user's matrix."""
    with pytest.raises(ValueError, match="explicit"):
        TopologySpec.gossip("bidirectional_ring", {"n": 4},
                            w=bidirectional_ring_w(4))


def test_failure_drop_stream_independent_of_inner_stream():
    """Review regression: with equal (default) seeds the drop uniforms must
    NOT come from the same generator state as the inner firing draws."""
    W = complete_w(5)
    inner = PoissonClock(W, rate=5.0, seed=0)
    c = FailureInjectedClock(inner, drop_rate=0.5, seed=0)
    outer_stream = np.random.default_rng([0, 0])
    inner_stream = np.random.default_rng([0, 0])
    assert outer_stream.bit_generator.state == inner_stream.bit_generator.state
    # the clock still drops ~half the edges deterministically per (seed, r)
    kept = [c.window(r).n_events for r in range(6)]
    fired = [inner.window(r).n_events for r in range(6)]
    assert kept == [c.window(r).n_events for r in range(6)]
    assert sum(kept) < sum(fired)
    # drop decisions replayed from the salted stream match the clock output
    ev0 = inner.window(0)
    drops = np.random.default_rng([0, 0xFA11ED, 0]).random(ev0.n_events) < 0.5
    assert c.window(0).n_events == int((~drops).sum())


def test_gossip_topology_validates_union_connectivity():
    # two disconnected ring components: union can never be strongly connected
    blocks = np.zeros((6, 6))
    blocks[:3, :3] = bidirectional_ring_w(3)
    blocks[3:, 3:] = bidirectional_ring_w(3)
    topo = TopologySpec.gossip("explicit", w=blocks,
                               clock={"kind": "poisson", "rate": 1.0})
    with pytest.raises(ValueError, match="strongly connected"):
        _gossip_spec(topo, 6).validate()


def test_gossip_engine_field_cross_validation():
    topo = TopologySpec.gossip("bidirectional_ring", {"n": 4})
    # the "simulated" default on a gossip topology runs the GossipEngine
    _gossip_spec(topo, 4).validate()
    # engine="gossip" without a gossip topology is contradictory
    with pytest.raises(ValueError, match="kind='gossip'"):
        ExperimentSpec(
            topology=TopologySpec.complete(4),
            data=_gossip_data(4),
            run=RunSpec(engine="gossip"),
        ).validate()


def test_clock_doc_registry_roundtrip():
    W = bidirectional_ring_w(4)
    doc = {
        "kind": "failure_injected",
        "inner": {"kind": "poisson", "rate": 0.5, "seed": 7},
        "drop_rate": 0.25,
        "seed": 9,
    }
    c = build_clock(doc, W)
    assert isinstance(c, FailureInjectedClock)
    np.testing.assert_array_equal(
        c.window(2).edges, build_clock(doc, W).window(2).edges
    )
    with pytest.raises(ValueError, match="unknown clock kind"):
        build_clock({"kind": "quartz"}, W)


# ---------------------------------------------------------------------------
# masked consensus kernels: all-active bit-identity + bit-stable passthrough
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mode", ["xla", "interpret"])
def test_all_active_window_equals_network_kernel_bitwise(mode):
    """Acceptance: the all-edges-active window == consensus_fused_network /
    consensus_flat OUTPUT BIT-IDENTICALLY (assert_array_equal, no atol)."""
    from repro.core.flat import FlatPosterior, FlatLayout

    n, p = 5, 300
    mean, rho = _rand_posts(n, p)
    W = jnp.asarray(bidirectional_ring_w(n), jnp.float32)
    layout = FlatLayout.for_pytree({"w": jnp.zeros((p,))})
    posts = FlatPosterior(mean=mean, rho=rho, layout=layout)
    allmask = jnp.ones((n,), bool)
    out = consensus_flat_masked(posts, W, allmask, mode=mode, block=128)
    ref = consensus_flat(posts, W, mode=mode, block=128)
    np.testing.assert_array_equal(np.asarray(out.mean), np.asarray(ref.mean))
    np.testing.assert_array_equal(np.asarray(out.rho), np.asarray(ref.rho))
    if mode == "interpret":
        mn, rn = consensus_fused_network(W, mean, rho, block=128, interpret=True)
        mm, rm = consensus_fused_masked(W, allmask, mean, rho, block=128,
                                        interpret=True)
        np.testing.assert_array_equal(np.asarray(mm), np.asarray(mn))
        np.testing.assert_array_equal(np.asarray(rm), np.asarray(rn))


@pytest.mark.parametrize("mode", ["xla", "interpret"])
def test_partial_window_passthrough_and_active_rows(mode):
    """Inactive agents pass through BITWISE (no softplus round trip); active
    rows match the dense reference on the window's W-tilde.  Dense-masked
    and edge-list paths agree."""
    from repro.core.flat import FlatPosterior, FlatLayout

    n, p = 6, 260
    mean, rho = _rand_posts(n, p, seed=4)
    layout = FlatLayout.for_pytree({"w": jnp.zeros((p,))})
    posts = FlatPosterior(mean=mean, rho=rho, layout=layout)
    win = PoissonClock(bidirectional_ring_w(n), rate=0.4, seed=7).window(0)
    assert 0 < win.active.sum() < n  # genuinely partial
    W = jnp.asarray(win.w_eff, jnp.float32)
    act = jnp.asarray(win.active)

    out = consensus_flat_masked(posts, W, act, mode=mode, block=128)
    inactive = ~win.active
    np.testing.assert_array_equal(
        np.asarray(out.mean)[inactive], np.asarray(mean)[inactive]
    )
    np.testing.assert_array_equal(
        np.asarray(out.rho)[inactive], np.asarray(rho)[inactive]
    )
    ref = consensus_flat(posts, W, mode="xla", block=128)
    active = win.active
    np.testing.assert_allclose(
        np.asarray(out.mean)[active], np.asarray(ref.mean)[active],
        atol=1e-6, rtol=1e-5,
    )

    g = SparseGraph.from_dense(win.w_eff)
    dst, src, w = (jnp.asarray(a) for a in g.edge_arrays())
    sp = consensus_flat_segments(
        posts, dst, src, w, active=act, slots=g.max_in_degree, mode=mode,
        block=128,
    )
    np.testing.assert_allclose(
        np.asarray(sp.mean), np.asarray(out.mean), atol=1e-6, rtol=1e-5
    )
    np.testing.assert_array_equal(
        np.asarray(sp.mean)[inactive], np.asarray(mean)[inactive]
    )
    np.testing.assert_array_equal(
        np.asarray(sp.rho)[inactive], np.asarray(rho)[inactive]
    )


# ---------------------------------------------------------------------------
# GossipEngine: protocol, equivalence, compile count, resume, staleness
# ---------------------------------------------------------------------------


def _all_edges_topo(n):
    edges = [[int(i), int(j)] for i, j in _directed_edges(bidirectional_ring_w(n))]
    return TopologySpec(
        kind="gossip",
        params={"base": "bidirectional_ring", "base_params": {"n": n}},
        clock={"kind": "trace", "trace": [edges]},
    )


def test_all_edges_gossip_reproduces_synchronous_bitwise():
    """Property (acceptance): a gossip trace with ALL edges active every
    window is bit-identical to the synchronous SimulatedEngine run — the
    synchronous runtime is the all-edges special case of the gossip one."""
    n = 4
    s_g = build_session(_gossip_spec(_all_edges_topo(n), n))
    s_s = build_session(
        ExperimentSpec(
            topology=TopologySpec(kind="bidirectional_ring", params={"n": n}),
            data=_gossip_data(n),
            inference=InferenceSpec(hidden=8, depth=1, lr=1e-2),
            run=RunSpec(n_rounds=3, seed=0),
        )
    )
    s_g.run()
    s_s.run()
    np.testing.assert_array_equal(
        np.asarray(s_g.posterior().mean), np.asarray(s_s.posterior().mean)
    )
    np.testing.assert_array_equal(
        np.asarray(s_g.posterior().rho), np.asarray(s_s.posterior().rho)
    )
    tel = s_g.evaluate()["engine"]
    assert tel["staleness"]["max"] == 0  # every agent merged every window
    assert tel["merges"]["min"] == 3


def test_gossip_window_is_one_jitted_call():
    """Acceptance: a full event window executes as ONE jitted call — the
    per-window transition traces exactly once across the whole run (static
    window shapes; no per-event Python dispatch)."""
    n = 4
    topo = TopologySpec.gossip(
        "bidirectional_ring", {"n": n}, clock={"kind": "poisson", "rate": 0.7}
    )
    s = build_session(_gossip_spec(topo, n, n_rounds=5))
    s.run()
    assert s.engine.n_traces == 1
    assert int(s.state.round) == 5


def test_gossip_session_save_load_resume_bitwise(tmp_path):
    """Acceptance: Engine protocol end-to-end — build_session -> run ->
    save/load resumes bit-identically (the clock regenerates the identical
    event stream from the embedded spec + round index)."""
    n = 5
    topo = TopologySpec.gossip(
        "bidirectional_ring", {"n": n},
        clock={"kind": "poisson", "rate": 0.6, "seed": 11},
    )
    s = build_session(_gossip_spec(topo, n, n_rounds=6, seed=2))
    s.run(3)
    path = os.path.join(tmp_path, "gossip.ckpt")
    s.save(path)
    s2 = Session.load(path)
    assert s2.round_idx == 3
    assert s2.spec == s.spec
    s.run(3)
    s2.run(3)
    np.testing.assert_array_equal(
        np.asarray(s.posterior().mean), np.asarray(s2.posterior().mean)
    )
    np.testing.assert_array_equal(
        np.asarray(s.posterior().rho), np.asarray(s2.posterior().rho)
    )
    np.testing.assert_array_equal(
        np.asarray(s.state.last_merge), np.asarray(s2.state.last_merge)
    )
    np.testing.assert_array_equal(
        np.asarray(s.state.n_merges), np.asarray(s2.state.n_merges)
    )


def test_time_varying_star_as_gossip_trace_matches_table3_path():
    """Property (satellite): the paper's time-varying star schedule
    re-expressed as a gossip trace matches the existing table3 execution
    (SimulatedEngine cycling the slot W's)."""
    mats = time_varying_star_schedule(4, 2, a=0.5)
    n = 5
    # per-window w_eff reproduces each slot W exactly
    table, trace = trace_from_schedule(mats)
    tc = TraceClock(table, trace, rule="table")
    for k, m in enumerate(mats):
        np.testing.assert_allclose(tc.window(k).w_eff, m, atol=1e-12)

    data = _gossip_data(n, local_updates=1)
    inf = InferenceSpec(hidden=6, depth=1, lr=1e-2)
    s_g = build_session(ExperimentSpec(
        topology=TopologySpec.gossip_from_schedule(mats),
        data=data, inference=inf, run=RunSpec(n_rounds=4, seed=1),
    ))
    s_s = build_session(ExperimentSpec(
        topology=TopologySpec.time_varying_star(4, 2, a=0.5),
        data=data, inference=inf, run=RunSpec(n_rounds=4, seed=1),
    ))
    s_g.run()
    s_s.run()
    # identical up to the passthrough: the scheduled path round-trips idle
    # agents through softplus(softplus^-1(.)), the gossip path does not
    np.testing.assert_allclose(
        np.asarray(s_g.posterior().mean), np.asarray(s_s.posterior().mean),
        atol=1e-4, rtol=1e-4,
    )
    np.testing.assert_allclose(
        np.asarray(s_g.posterior().rho), np.asarray(s_s.posterior().rho),
        atol=1e-4, rtol=1e-4,
    )


def test_staleness_telemetry_counts_unmerged_windows():
    """An agent whose edges never fire stays bit-frozen in consensus and its
    staleness equals the whole run length."""
    n = 4
    W = bidirectional_ring_w(n)
    # only the 0<->1 edges ever fire; agents 2 and 3 never merge
    trace = [[[0, 1], [1, 0]]]
    topo = TopologySpec(
        kind="gossip",
        params={"base": "bidirectional_ring", "base_params": {"n": n}},
        clock={"kind": "trace", "trace": trace},
    )
    spec = _gossip_spec(topo, n, n_rounds=4)
    with pytest.raises(ValueError, match="strongly connected"):
        spec.validate()  # such a trace violates Assumption 1 eagerly ...
    # ... so bypass the spec layer and drive the clock directly
    s = build_session(_gossip_spec(_all_edges_topo(n), n, n_rounds=4))
    clock = TraceClock(W, [[(0, 1), (1, 0)]])
    s.run(w_schedule=lambda r: clock.window(r).w_eff)
    age = s.engine.staleness(s.state)
    assert age[2] == 4 and age[3] == 4  # never merged: age == run length
    assert age[0] == 0 and age[1] == 0
    merges = np.asarray(s.state.n_merges)
    np.testing.assert_array_equal(merges, [4, 4, 0, 0])
    tel = s.evaluate()["engine"]
    assert tel["staleness"]["max"] == 4 and tel["windows"] == 4


def test_wake_on_event_policy_freezes_sleeping_agents():
    """local_policy="active": agents with no incoming event skip their local
    steps too — posterior, optimizer state and step counter all pass through
    bitwise."""
    n = 4
    W = bidirectional_ring_w(n)
    topo = TopologySpec(
        kind="gossip",
        params={"base": "bidirectional_ring", "base_params": {"n": n}},
        clock={"kind": "poisson", "rate": 0.4, "seed": 3,
               "local_policy": "active"},
    )
    s = build_session(_gossip_spec(topo, n, n_rounds=1))
    post0 = s.posterior()
    clock = s.spec.topology.gossip_clock()
    win = clock.window(0)
    s.round()
    sleeping = ~win.active
    assert sleeping.any()
    np.testing.assert_array_equal(
        np.asarray(s.posterior().mean)[sleeping],
        np.asarray(post0.mean)[sleeping],
    )
    np.testing.assert_array_equal(
        np.asarray(s.state.step)[sleeping], np.zeros(int(sleeping.sum()))
    )
    awake = win.active
    assert np.all(np.asarray(s.state.step)[awake] == 2)  # u local steps ran
    assert float(
        np.abs(np.asarray(s.posterior().mean)[awake]
               - np.asarray(post0.mean)[awake]).max()
    ) > 0
    # phantom losses of sleeping agents are NaN-masked (review regression:
    # they must not pollute the loss telemetry); Session aggregates nanmean
    _, losses = s.engine.run_round(
        s.state, s.data.sampler(jax.random.key(5), 1),
        jnp.asarray(win.w_eff), jax.random.key(6),
    )
    assert np.isnan(np.asarray(losses)[sleeping]).all()
    assert np.isfinite(np.asarray(losses)[awake]).all()


# ---------------------------------------------------------------------------
# satellites: roofline monotonicity + ppermute flat routing
# ---------------------------------------------------------------------------


def test_gossip_window_roofline_monotone_vs_dense():
    """Satellite: window HBM bytes are monotone in the active-edge fraction
    and meet the dense ``consensus_roofline`` flat_fused bytes exactly at
    full participation."""
    n, p = 16, 1 << 14
    dense = consensus_roofline(n, p, n_leaves=8)["hbm_bytes"]["flat_fused"]
    prev = -1.0
    for k in range(n + 1):
        rec = gossip_window_roofline(n, p, n_participating=k)
        b = rec["hbm_bytes"]["window_masked"]
        assert b >= prev  # monotone in active fraction
        assert b <= dense
        prev = b
    full = gossip_window_roofline(n, p, n_participating=n)
    assert full["hbm_bytes"]["window_masked"] == dense
    assert full["hbm_passes"]["window_masked"] == 1.0
    # fewer merging agents than participants can only reduce traffic
    half = gossip_window_roofline(n, p, n_participating=n, n_merging=n // 2)
    assert half["hbm_bytes"]["window_masked"] < dense
    with pytest.raises(ValueError, match="n_merging"):
        gossip_window_roofline(n, p, n_participating=2, n_merging=3)


def test_gossip_window_roofline_latency_and_interconnect_terms():
    """Satellite: the sharded/delayed extensions — ICI bytes are monotone in
    the fired-offset count, ppermute never exceeds the dense all-gather,
    and the history term appears exactly when delay_depth > 0 (its resident
    footprint scaling with the ring depth)."""
    n, p = 16, 1 << 14
    base = gossip_window_roofline(n, p, n_participating=8)
    assert "ici_bytes" not in base and "history" not in base["hbm_bytes"]

    s = 8
    allgather = gossip_window_roofline(
        n, p, n_participating=8, n_shards=s, n_cross_offsets=s - 1
    )["ici_bytes"]["dense_allgather"]
    prev = -1.0
    for k in range(s):
        rec = gossip_window_roofline(
            n, p, n_participating=8, n_shards=s, n_cross_offsets=k
        )
        ici = rec["ici_bytes"]["window_ppermute"]
        assert ici >= prev  # monotone in the fired-offset schedule
        assert ici <= allgather  # never worse than the dense layout
        # HBM terms are untouched by the interconnect extension
        assert rec["hbm_bytes"] == base["hbm_bytes"]
        prev = ici
    idle = gossip_window_roofline(
        n, p, n_participating=0, n_shards=s, n_cross_offsets=0
    )
    assert idle["ici_bytes"]["window_ppermute"] == 0.0  # idle windows: no wire

    d1 = gossip_window_roofline(
        n, p, n_participating=8, delay_depth=1, n_stale_events=4
    )
    d3 = gossip_window_roofline(
        n, p, n_participating=8, delay_depth=3, n_stale_events=4
    )
    assert d1["hbm_bytes"]["history"] == d3["hbm_bytes"]["history"] > 0
    assert d3["hist_resident_bytes"] == 2.0 * d1["hist_resident_bytes"]
    assert d1["hbm_bytes"]["window_masked"] == base["hbm_bytes"]["window_masked"]

    with pytest.raises(ValueError, match="n_cross_offsets"):
        gossip_window_roofline(n, p, n_participating=2, n_shards=4,
                               n_cross_offsets=4)
    with pytest.raises(ValueError, match=">= 0"):
        gossip_window_roofline(n, p, n_participating=2, delay_depth=-1)


# ---------------------------------------------------------------------------
# delivery latency: DelayedClock + history-ring engine
# ---------------------------------------------------------------------------


def _delayed_clock_doc(delay, inner=None):
    return {
        "kind": "delayed",
        "inner": inner or {"kind": "poisson", "rate": 0.8, "seed": 1},
        "latency": {"kind": "constant", "delay": delay},
    }


def test_delayed_clock_latency_zero_matches_inner_windows():
    """{"kind": "constant", "delay": 0} delivers every firing instantly:
    every window's (w_eff, active, event set) equals the inner clock's and
    all lags are 0."""
    W = bidirectional_ring_w(6)
    inner = PoissonClock(W, rate=0.8, seed=3)
    c0 = DelayedClock(inner, {"kind": "constant", "delay": 0})
    for r in range(6):
        a, b = c0.window(r), inner.window(r)
        np.testing.assert_array_equal(a.w_eff, b.w_eff)
        np.testing.assert_array_equal(a.active, b.active)
        assert a.max_lag == 0
        assert (
            set(map(tuple, a.edges[: a.n_events].tolist()))
            == set(map(tuple, b.edges[: b.n_events].tolist()))
        )


def test_delayed_clock_constant_k_shifts_delivery():
    """Constant latency k: window r delivers exactly the firings of window
    r - k (each at lag k); the first k windows deliver nothing."""
    W = bidirectional_ring_w(5)
    inner = PoissonClock(W, rate=0.9, seed=7)
    k = 2
    c = DelayedClock(inner, {"kind": "constant", "delay": k})
    assert c.max_delay == k
    for r in range(k):
        assert c.window(r).n_events == 0
    for r in range(k, 7):
        win, fired = c.window(r), inner.window(r - k)
        assert (
            set(map(tuple, win.edges[: win.n_events].tolist()))
            == set(map(tuple, fired.edges[: fired.n_events].tolist()))
        )
        assert (win.delays[: win.n_events] == k).all()


def test_delayed_clock_geometric_and_per_edge_models():
    W = bidirectional_ring_w(6)
    inner = PoissonClock(W, rate=1.2, seed=1)
    cg = build_clock(
        {"kind": "delayed", "inner": {"kind": "poisson", "rate": 1.2, "seed": 1},
         "latency": {"kind": "geometric", "p": 0.4, "max": 3}, "seed": 5},
        W,
    )
    lags = [cg.window(r).max_lag for r in range(12)]
    assert max(lags) <= 3  # truncation bound
    assert lags == [cg.window(r).max_lag for r in range(12)]  # deterministic
    cg.validate()  # union delegates to the inner clock

    mat = np.zeros((6, 6), int)
    mat[0, 1] = 2
    cp = DelayedClock(
        PoissonClock(W, rate=50.0, seed=2),  # all edges fire ~every window
        {"kind": "per_edge", "delays": mat.tolist()},
    )
    assert cp.max_delay == 2
    win = cp.window(4)
    ev = {tuple(e): int(d) for e, d in
          zip(win.edges[: win.n_events].tolist(), win.delays[: win.n_events])}
    assert ev[(0, 1)] == 2
    assert all(d == 0 for e, d in ev.items() if e != (0, 1))

    with pytest.raises(ValueError, match="latency"):
        DelayedClock(inner, {"kind": "tachyonic"})
    with pytest.raises(ValueError, match="shape"):
        DelayedClock(inner, {"kind": "per_edge", "delays": [[0]]})
    with pytest.raises(ValueError, match=">= 0"):
        DelayedClock(inner, {"kind": "constant", "delay": -1})


def _delayed_spec(clock, n=6, n_rounds=4, seed=0, **inf_kw):
    return _gossip_spec(
        TopologySpec.gossip("bidirectional_ring", {"n": n}, clock=clock),
        n, n_rounds=n_rounds, seed=seed, **inf_kw,
    )


def test_delayed_latency_zero_reproduces_engine_bitwise():
    """Acceptance: DelayedClock with latency 0 reproduces today's
    GossipEngine run BITWISE from the same seed (the k=0 reduction)."""
    inner = {"kind": "poisson", "rate": 0.8, "seed": 1}
    s_plain = build_session(_delayed_spec(inner))
    s_d0 = build_session(_delayed_spec(_delayed_clock_doc(0, inner)))
    s_plain.run()
    s_d0.run()
    assert s_d0.engine.hist_slots == 0  # no ring buffer at depth 0
    assert s_d0.state.hist_mean is None  # ... and no extra state leaves
    np.testing.assert_array_equal(
        np.asarray(s_plain.posterior().mean), np.asarray(s_d0.posterior().mean)
    )
    np.testing.assert_array_equal(
        np.asarray(s_plain.posterior().rho), np.asarray(s_d0.posterior().rho)
    )
    np.testing.assert_array_equal(
        np.asarray(s_plain.state.n_merges), np.asarray(s_d0.state.n_merges)
    )
    assert s_d0.engine.n_traces == 1


def test_delayed_engine_merges_posterior_as_of_fire_time():
    """The delivered merge uses the SRC posterior as of FIRE time, not as of
    delivery: with lr=0 (locals are no-ops) and constant latency 1, agent
    1's merge of the edge fired at window 1 must mix agent 0's INITIAL
    posterior, even though agent 0 itself merged at window 1."""
    n = 3
    W = complete_w(n)
    trace = [[[0, 2]], [[1, 0]], [[2, 1]]]  # union = 3-cycle: connected
    clock = {"kind": "delayed",
             "inner": {"kind": "trace", "trace": trace},
             "latency": {"kind": "constant", "delay": 1}}
    topo = TopologySpec.gossip("complete", {"n": n}, clock=clock)
    spec = ExperimentSpec(
        topology=topo,
        data=_gossip_data(n),
        # lr=0: local steps are bitwise no-ops, so posteriors change ONLY
        # through merges; distinct inits make the stale merge observable
        inference=InferenceSpec(hidden=8, depth=1, lr=0.0, shared_init=False),
        run=RunSpec(n_rounds=3, seed=0),
    )
    s = build_session(spec)
    post0 = s.posterior()
    mean0 = np.asarray(post0.mean)
    prec0 = np.asarray(1.0 / jnp.square(softplus(post0.rho)))
    s.run()  # w0: no delivery; w1: (0,2)@lag1; w2: (1,0)@lag1
    out = s.posterior()

    # conserve-rule weights of a single fired in-edge (dst, src) on W
    def merge(dst, src, mean_dst, prec_dst, mean_src, prec_src):
        w_self = 1.0 - W[dst, src]
        p = np.float32(w_self) * prec_dst + np.float32(W[dst, src]) * prec_src
        m = (np.float32(w_self) * prec_dst * mean_dst
             + np.float32(W[dst, src]) * prec_src * mean_src) / p
        return m, p

    # window 2: agent 1 merges agent 0 AS OF window 1 = initial (lr == 0,
    # history holds the PRE-merge post-local value)
    m1, p1 = merge(1, 0, mean0[1], prec0[1], mean0[0], prec0[0])
    np.testing.assert_allclose(
        np.asarray(out.mean)[1], m1, atol=1e-6, rtol=1e-6
    )
    rho1 = np.asarray(softplus_inv(jax.lax.rsqrt(jnp.asarray(p1))))
    np.testing.assert_allclose(
        np.asarray(out.rho)[1], rho1, atol=1e-6, rtol=1e-6
    )
    # counterfactual: merging agent 0 AS OF DELIVERY (its window-1-merged
    # value) gives a DIFFERENT posterior — the staleness is real
    m0w1, p0w1 = merge(0, 2, mean0[0], prec0[0], mean0[2], prec0[2])
    np.testing.assert_allclose(np.asarray(out.mean)[0], m0w1, atol=1e-6,
                               rtol=1e-6)
    m1_fresh, _ = merge(1, 0, mean0[1], prec0[1], m0w1, p0w1)
    assert float(np.abs(m1_fresh - m1).max()) > 1e-6


def test_delayed_session_save_load_resume_bitwise(tmp_path):
    """The history ring buffer rides in the checkpoint: a resumed delayed
    session continues bit-identically (stale merges included)."""
    clock = {"kind": "delayed",
             "inner": {"kind": "poisson", "rate": 0.9, "seed": 2},
             "latency": {"kind": "geometric", "p": 0.5, "max": 3}}
    s = build_session(_delayed_spec(clock, n_rounds=6, seed=2))
    assert s.engine.hist_slots == 4  # max_delay + 1 ring slots
    s.run(3)
    path = os.path.join(tmp_path, "delayed.ckpt")
    s.save(path)
    s2 = Session.load(path)
    s.run(3)
    s2.run(3)
    np.testing.assert_array_equal(
        np.asarray(s.posterior().mean), np.asarray(s2.posterior().mean)
    )
    np.testing.assert_array_equal(
        np.asarray(s.state.hist_mean), np.asarray(s2.state.hist_mean)
    )
    np.testing.assert_array_equal(
        np.asarray(s.state.last_merge), np.asarray(s2.state.last_merge)
    )
    assert s.engine.n_traces == s2.engine.n_traces == 1


def test_instant_gossip_state_keeps_pre_latency_leaf_structure():
    """Review regression (checkpoint back-compat): instant-delivery gossip
    states carry ``None`` history leaves — an EMPTY pytree subtree — so
    they flatten to exactly the pre-latency structure and gossip
    checkpoints saved before the latency feature keep loading."""
    s = build_session(_delayed_spec({"kind": "poisson", "rate": 0.8}))
    st = s.state
    assert st.hist_mean is None and st.hist_rho is None
    n_core = (
        len(jax.tree.leaves(st.posterior))
        + len(jax.tree.leaves(st.opt_state))
        + 4  # step, round, last_merge, n_merges
    )
    assert len(jax.tree.leaves(st)) == n_core  # no latency leaves
    # a delayed engine's state DOES carry the two ring leaves
    s_d = build_session(_delayed_spec(_delayed_clock_doc(1)))
    assert len(jax.tree.leaves(s_d.state)) == n_core + 2


def test_delayed_table_rule_lag_mixing_checked_eagerly():
    """Review regression: a lag-MIXING latency over a weight-table trace can
    co-deliver fire windows whose combined in-weights reach >= 1 — rejected
    at DelayedClock construction instead of crashing mid-run; constant
    latency (never mixes: each window is one shifted inner window) and
    feasible tables stay accepted."""
    table = np.array([
        [1.0, 0.6, 0.6],
        [0.5, 1.0, 0.0],
        [0.5, 0.0, 1.0],
    ])
    trace = [[(0, 1)], [(0, 2)], [(1, 0)], [(2, 0)]]  # each window feasible
    inner = TraceClock(table, trace, rule="table")
    lags = np.zeros((3, 3), int)
    lags[0, 1] = 1  # (0,1)@lag1 can land on (0,2)@lag0: 0.6 + 0.6 >= 1
    with pytest.raises(ValueError, match="co-deliver"):
        DelayedClock(inner, {"kind": "per_edge", "delays": lags.tolist()})
    with pytest.raises(ValueError, match="co-deliver"):
        DelayedClock(inner, {"kind": "geometric", "p": 0.5, "max": 2})
    # constant latency only shifts inner windows — accepted, and runs
    c = DelayedClock(inner, {"kind": "constant", "delay": 2})
    for r in range(6):
        c.window(r)
    # the hazard is PER ROW: the heavy row's own in-edges sharing one lag
    # can never co-deliver two fire windows, whatever the rest of the graph
    # carries — accepted, and every window stays feasible
    uniform_row = np.zeros((3, 3), int)
    uniform_row[0, 1] = uniform_row[0, 2] = 1  # row 0 uniform; others lag 0
    c_row = DelayedClock(inner, {"kind": "per_edge",
                                 "delays": uniform_row.tolist()})
    for r in range(8):
        c_row.window(r)
    # a feasible table (worst-case combined rows < 1) accepts mixing lags
    feasible = TraceClock(
        np.array([[1.0, 0.4, 0.4], [0.5, 1.0, 0.0], [0.5, 0.0, 1.0]]),
        trace, rule="table",
    )
    DelayedClock(feasible, {"kind": "geometric", "p": 0.5, "max": 2})


def test_delayed_clock_must_be_outermost_wrapper():
    """Review regression: burying a DelayedClock inside another wrapper
    would silently strip its lags (wrappers see only ``_events``) and run
    the instant engine on time-shifted events — rejected eagerly, both
    directly and via the doc registry."""
    W = bidirectional_ring_w(4)
    delayed = DelayedClock(
        PoissonClock(W, rate=1.0), {"kind": "constant", "delay": 2}
    )
    with pytest.raises(ValueError, match="OUTERMOST"):
        FailureInjectedClock(delayed, drop_rate=0.1)
    with pytest.raises(ValueError, match="OUTERMOST"):
        DelayedClock(delayed, {"kind": "constant", "delay": 1})
    with pytest.raises(ValueError, match="OUTERMOST"):
        build_clock(
            {"kind": "failure_injected", "drop_rate": 0.1,
             "inner": {"kind": "delayed",
                       "inner": {"kind": "poisson", "rate": 1.0},
                       "latency": {"kind": "constant", "delay": 2}}},
            W,
        )
    # the supported order (delays outermost) still composes
    ok = build_clock(
        {"kind": "delayed",
         "inner": {"kind": "failure_injected", "drop_rate": 0.1,
                   "inner": {"kind": "poisson", "rate": 1.0}},
         "latency": {"kind": "constant", "delay": 2}},
        W,
    )
    assert ok.max_delay == 2
    # delay 0 is not a delayed clock for composition purposes
    zero = DelayedClock(PoissonClock(W, rate=1.0),
                        {"kind": "constant", "delay": 0})
    FailureInjectedClock(zero, drop_rate=0.1)


def test_clock_window_memo_returns_identical_windows():
    """Review regression: window(r) is memoized one round deep (Session and
    engine both ask for the same window each round) and repeated calls stay
    deterministic across the memo boundary."""
    W = bidirectional_ring_w(5)
    c = DelayedClock(PoissonClock(W, rate=0.8, seed=3),
                     {"kind": "constant", "delay": 1})
    w_a = c.window(4)
    assert c.window(4) is w_a  # memo hit: no second construction
    w_b = c.window(5)  # memo moves on ...
    assert c.window(4) is not w_a  # ... old slot evicted
    np.testing.assert_array_equal(c.window(4).edges, w_a.edges)
    np.testing.assert_array_equal(c.window(4).w_eff, w_a.w_eff)
    np.testing.assert_array_equal(c.window(5).edges, w_b.edges)


def test_delayed_engine_rejects_w_override():
    """Delayed windows carry static event structure the W matrix alone
    cannot express — per-round W overrides are rejected loudly instead of
    silently merging the wrong stream."""
    s = build_session(_delayed_spec(_delayed_clock_doc(1)))
    with pytest.raises(ValueError, match="spec clock"):
        s.run(w_schedule=lambda r: complete_w(6))


# ---------------------------------------------------------------------------
# async edge cases: zero-event windows, drop-stream independence, table rule
# ---------------------------------------------------------------------------


def test_zero_event_window_is_bitwise_passthrough():
    """A zero-event window under local_policy="active" leaves posterior,
    optimizer state and step counters bit-untouched (trace count still 1),
    and Session.round reports the all-idle window honestly instead of
    writing NaN into the history (n_trained=0, loss=None)."""
    n = 4
    all_edges = [[int(i), int(j)]
                 for i, j in _directed_edges(bidirectional_ring_w(n))]
    topo = TopologySpec(
        kind="gossip",
        params={"base": "bidirectional_ring", "base_params": {"n": n}},
        # window 0 fires everything (union: connected), window 1 is EMPTY
        clock={"kind": "trace", "trace": [all_edges, []],
               "local_policy": "active"},
    )
    s = build_session(_gossip_spec(topo, n, n_rounds=2))
    rec0 = s.round()
    assert rec0["n_trained"] == n and np.isfinite(rec0["loss"])
    post1 = s.posterior()
    opt1 = s.state.opt_state
    step1 = np.asarray(s.state.step)
    rec1 = s.round()  # the all-idle window
    assert rec1["n_trained"] == 0
    assert rec1["loss"] is None  # NOT a silent NaN
    np.testing.assert_array_equal(
        np.asarray(s.posterior().mean), np.asarray(post1.mean)
    )
    np.testing.assert_array_equal(
        np.asarray(s.posterior().rho), np.asarray(post1.rho)
    )
    for a, b in zip(jax.tree.leaves(s.state.opt_state), jax.tree.leaves(opt1)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(s.state.step), step1)
    assert int(s.state.round) == 2  # the window still counted
    assert s.engine.n_traces == 1  # no retrace for the empty window
    # history aggregation over the mixed run stays NaN-free
    losses = [r["loss"] for r in (rec0, rec1) if r["n_trained"]]
    assert np.isfinite(np.mean(losses))


def test_failure_drop_stream_independent_of_inner_firings():
    """Satellite regression for the 0xFA11ED salt: the drop decisions for
    window r are a pure function of (outer seed, r) — swapping the INNER
    clock (different seed and rate) leaves the kept/dropped prefix pattern
    unchanged."""
    W = complete_w(5)
    drop = 0.5

    def keep_mask(inner, r, n_events):
        rng = np.random.default_rng([0, 0xFA11ED, r])
        return rng.random(n_events) >= drop

    inner_a = PoissonClock(W, rate=5.0, seed=1)
    inner_b = PoissonClock(W, rate=2.0, seed=9)
    c_a = FailureInjectedClock(inner_a, drop_rate=drop, seed=0)
    c_b = FailureInjectedClock(inner_b, drop_rate=drop, seed=0)
    for r in range(6):
        ev_a, ev_b = inner_a.window(r), inner_b.window(r)
        mask_a = keep_mask(inner_a, r, ev_a.n_events)
        mask_b = keep_mask(inner_b, r, ev_b.n_events)
        # the salted stream is shared: same prefix regardless of the inner
        m = min(ev_a.n_events, ev_b.n_events)
        np.testing.assert_array_equal(mask_a[:m], mask_b[:m])
        # and each clock's output is exactly its inner events + that mask
        kept_a = [tuple(e) for e, k in
                  zip(ev_a.edges[: ev_a.n_events].tolist(), mask_a) if k]
        win_a = c_a.window(r)
        assert kept_a == [tuple(e) for e in
                          win_a.edges[: win_a.n_events].tolist()]
        kept_b = [tuple(e) for e, k in
                  zip(ev_b.edges[: ev_b.n_events].tolist(), mask_b) if k]
        win_b = c_b.window(r)
        assert kept_b == [tuple(e) for e in
                          win_b.edges[: win_b.n_events].tolist()]


def test_trace_clock_table_rule_row_infeasibility_errors_eagerly():
    """A weight-table trace whose fired in-weights sum to >= 1 on some row
    is rejected at TraceClock CONSTRUCTION (eager per-window feasibility),
    not midway through a run."""
    table = np.array([
        [1.0, 0.6, 0.6],
        [0.5, 1.0, 0.0],
        [0.5, 0.0, 1.0],
    ])
    # single fired in-edge per window: feasible
    TraceClock(table, [[(0, 1)], [(0, 2)]], rule="table")
    # both of row 0's in-edges in ONE window: 0.6 + 0.6 >= 1
    with pytest.raises(ValueError, match="row-feasible"):
        TraceClock(table, [[(0, 1), (0, 2)]], rule="table")
    # the report names the offending window row
    with pytest.raises(ValueError, match="window row 0"):
        TraceClock(table, [[(0, 1)], [(0, 1), (0, 2)]], rule="table")


# ---------------------------------------------------------------------------
# sharded window consensus: the equivalence ladder under 8 virtual devices
# ---------------------------------------------------------------------------

_SHARD_PRELUDE = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
"""


def _run_sharded(body: str) -> None:
    from conftest import run_multidevice_subprocess

    run_multidevice_subprocess(_SHARD_PRELUDE + textwrap.dedent(body))


@pytest.mark.slow
@pytest.mark.multidevice
def test_ppermute_window_bitwise_all_clocks_and_topologies():
    """Acceptance: consensus_ppermute_window == consensus_flat_masked
    BIT-identically for EVERY window of poisson / round_robin / trace
    clocks on ring, torus and time-varying-star topologies, on an
    8-virtual-device host mesh (several shard counts per topology)."""
    _run_sharded("""
    from repro.core.flat import FlatLayout, FlatPosterior, consensus_flat_masked
    from repro.core.graphs import (bidirectional_ring_w, torus_w,
                                   time_varying_star_schedule)
    from repro.gossip.clocks import (PoissonClock, RoundRobinClock,
                                     TraceClock, all_edges_trace,
                                     trace_from_schedule)
    from repro.launch.consensus_opt import consensus_ppermute_window

    def clocks_for(W, row_stochastic):
        if row_stochastic:
            return [PoissonClock(W, rate=0.6, seed=1),
                    RoundRobinClock(W, edges_per_window=3),
                    all_edges_trace(W)]
        table, trace = W
        return [TraceClock(table, trace, rule="table")]

    ring = bidirectional_ring_w(8)
    torus = torus_w(2, 4)
    tvs = trace_from_schedule(time_varying_star_schedule(4, 2, a=0.5))
    cases = [("ring", ring, True, (2, 4, 8)),
             ("torus", torus, True, (2, 8)),
             ("time_varying_star", tvs, False, (5,))]  # 5 agents

    p = 200
    for name, W, rs, shard_counts in cases:
        n = (W if rs else W[0]).shape[0]
        ks = jax.random.split(jax.random.key(n), 2)
        layout = FlatLayout.for_pytree({"w": jnp.zeros((p,))})
        posts = FlatPosterior(
            mean=jax.random.normal(ks[0], (n, p)),
            rho=jax.random.normal(ks[1], (n, p)) * 0.4 - 1.0,
            layout=layout,
        )
        for clock in clocks_for(W, rs):
            for S in shard_counts:
                mesh = jax.sharding.Mesh(
                    np.asarray(jax.devices()[:S]), ("agents",))
                for r in range(4):
                    win = clock.window(r)
                    ref = consensus_flat_masked(
                        posts, jnp.asarray(win.w_eff, jnp.float32),
                        jnp.asarray(win.active), mode="xla")
                    out = consensus_ppermute_window(posts, win, mesh, "agents")
                    assert bool(jnp.all(out.mean == ref.mean)), (name, S, r)
                    assert bool(jnp.all(out.rho == ref.rho)), (name, S, r)
        print(name, "ok")
    print("OK")
    """)


@pytest.mark.slow
@pytest.mark.multidevice
def test_gossip_engine_ppermute_impl_bitwise_vs_masked():
    """Acceptance (engine level): a gossip session on
    InferenceSpec(consensus_impl="ppermute") over the 8-device agent mesh
    produces the BIT-identical posterior trajectory to the default dense
    masked execution — instant gossip and sharded gossip are the same
    point on the equivalence ladder."""
    _run_sharded("""
    import dataclasses
    from repro.api import (DataSpec, ExperimentSpec, InferenceSpec, RunSpec,
                           TopologySpec, build_session)

    n = 8
    def spec(impl):
        return ExperimentSpec(
            topology=TopologySpec.gossip(
                "bidirectional_ring", {"n": n},
                clock={"kind": "poisson", "rate": 0.7, "seed": 3}),
            data=DataSpec(
                dataset_params=dict(n_classes=3, dim=8, n_train_per_class=30),
                partition="iid", partition_params=dict(n_agents=n),
                batch_size=4, local_updates=2),
            inference=InferenceSpec(hidden=8, depth=1, lr=1e-2,
                                    consensus_impl=impl),
            run=RunSpec(n_rounds=3, seed=0),
        )

    s_m = build_session(spec("masked"))
    s_p = build_session(spec("ppermute"))
    s_m.run(); s_p.run()
    assert s_p.engine.n_shards == 8
    assert s_p.engine.n_traces == 1  # local phase still traces once
    np.testing.assert_array_equal(np.asarray(s_m.posterior().mean),
                                  np.asarray(s_p.posterior().mean))
    np.testing.assert_array_equal(np.asarray(s_m.posterior().rho),
                                  np.asarray(s_p.posterior().rho))
    assert s_p.evaluate()["engine"]["consensus_shards"] == 8
    print("OK")
    """)


def test_consensus_impl_spec_validation():
    """consensus_impl is a gossip-window execution choice: eager errors for
    non-gossip topologies and for non-gaussian ppermute."""
    topo = TopologySpec.gossip("bidirectional_ring", {"n": 4})
    _gossip_spec(topo, 4, consensus_impl="ppermute").validate()
    with pytest.raises(ValueError, match="gossip"):
        ExperimentSpec(
            topology=TopologySpec.complete(4),
            data=_gossip_data(4),
            inference=InferenceSpec(consensus_impl="ppermute"),
        ).validate()
    with pytest.raises(ValueError, match="ppermute"):
        _gossip_spec(
            topo, 4, consensus_impl="ppermute", consensus="mean_only"
        ).validate()
    with pytest.raises(ValueError, match="unknown consensus_impl"):
        InferenceSpec(consensus_impl="carrier_pigeon").validate()
    # consensus_shards without the ppermute impl would be silently ignored
    with pytest.raises(ValueError, match="consensus_shards"):
        InferenceSpec(consensus_shards=4).validate()
    InferenceSpec(consensus_impl="ppermute", consensus_shards=4).validate()
    # a delayed clock cannot take the instant-delivery sharded path
    delayed = TopologySpec.gossip(
        "bidirectional_ring", {"n": 4},
        clock={"kind": "delayed", "inner": {"kind": "poisson", "rate": 1.0},
               "latency": {"kind": "constant", "delay": 1}},
    )
    with pytest.raises(ValueError, match="instant delivery"):
        build_session(_gossip_spec(delayed, 4, consensus_impl="ppermute"))


def test_window_shard_offsets_schedule():
    """The static permutation schedule: only offsets crossed by fired edges
    appear; intra-shard edges contribute nothing; an idle window's schedule
    is empty."""
    from repro.launch.consensus_opt import window_shard_offsets

    W = bidirectional_ring_w(8)
    # ring edges cross adjacent shards only: offsets {1, S-1}
    win = all_edges_trace(W).window(0)
    assert window_shard_offsets(win, 4) == (1, 3)
    assert window_shard_offsets(win, 8) == (1, 7)
    assert window_shard_offsets(win, 1) == ()  # one shard: all local
    # single intra-shard edge (agents 0 and 1 share shard 0 at S=4)
    single = window_from_events(W, [(0, 1)], e_max=2)
    assert window_shard_offsets(single, 4) == ()
    empty = window_from_events(W, [], e_max=2)
    assert window_shard_offsets(empty, 4) == ()


def test_ppermute_flat_routes_through_single_shard_map(monkeypatch):
    """Satellite (ROADMAP open item): make_train_round_step(consensus_impl=
    "ppermute") on a FLAT posterior routes through
    consensus_ppermute_ring_flat (one shard_map over the [A, P] buffers),
    not the leaf-wise pod ppermute."""
    import repro.launch.consensus_opt as co
    from repro.configs import get_config
    from repro.launch.steps import init_train_state, make_train_round_step
    from repro.optim import adam

    calls = {}

    def fake_ring_flat(posts, mesh, axis, self_weight=1.0 / 3.0,
                      wire_dtype=jnp.float32, W=None):
        calls["axis"] = axis
        calls["W"] = W
        calls["flat"] = hasattr(posts, "layout")
        return posts  # identity consensus: enough to prove the routing

    def fail_pod(*a, **k):  # the leaf-wise path must NOT run for flat states
        raise AssertionError("leaf-wise consensus_ppermute_pod was called")

    monkeypatch.setattr(co, "consensus_ppermute_ring_flat", fake_ring_flat)
    monkeypatch.setattr(co, "consensus_ppermute_pod", fail_pod)

    cfg = get_config("repro-100m").reduced()
    a = 2
    opt = adam()
    state = init_train_state(jax.random.key(0), cfg, a, opt)
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    W = jnp.asarray(complete_w(a))
    step = make_train_round_step(
        cfg, W, opt=opt, remat=False, consensus_impl="ppermute",
        mesh=mesh, posterior_shardings=None,
    )
    from repro.data.pipeline import make_lm_batch_sampler

    batch = make_lm_batch_sampler(cfg.vocab_size, 2, 16, n_agents=a)(
        jax.random.key(1), 0
    )
    step(state, batch, jax.random.key(2))
    assert calls["flat"] and calls["axis"] == "pod"
    assert calls["W"] is W


# ---------------------------------------------------------------------------
# activity-mask exactness, f64 schedule identity, window contracts
# ---------------------------------------------------------------------------


def test_active_mask_survives_subresolution_weight():
    """Headline mask regression: a fired in-edge with weight 1e-8 is below
    f32 resolution at the diagonal (1 - 1e-8 rounds back to exactly 1.0 in
    float32), so deriving activity as diag(W_f32) < 1 silently drops the
    merge — and under local_policy="active" the agent does not even train.
    The engine must thread the clock's host-exact mask instead."""
    eps = 1e-8
    W = np.array([[1.0 - eps, eps], [0.4, 0.6]])
    # the bug's exact mechanism, pinned: the f32 diagonal is indistinguishable
    # from an idle row, only the host-side f64 mask can see the fired edge
    assert np.float32(W[0, 0]) == np.float32(1.0)
    spec = _gossip_spec(
        TopologySpec.gossip(
            "explicit", w=W,
            clock={"kind": "trace", "trace": [[[0, 1]], [[1, 0]]],
                   "local_policy": "active"},
        ),
        n_agents=2, n_rounds=1,
    )
    s = build_session(spec)
    rec = s.round()
    # agent 0 (the sub-resolution merge target) trained AND merged; agent 1
    # (no incoming event) slept
    assert rec["n_trained"] == 1
    np.testing.assert_array_equal(np.asarray(s.state.n_merges), [1, 0])
    np.testing.assert_array_equal(np.asarray(s.state.last_merge), [0, -1])
    u = spec.data.local_updates
    np.testing.assert_array_equal(np.asarray(s.state.step), [u, 0])


def test_window_for_rejects_f32_colliding_schedule():
    """_window_for must compare the Session's W against the clock stream in
    float64: a foreign schedule differing by less than one f32 ulp collides
    with the stream at float32 and was previously false-accepted — then
    silently merged with the STREAM's event structure instead of the
    caller's matrix."""
    s = build_session(_delayed_spec(_delayed_clock_doc(1)))
    w0 = np.asarray(s.spec.topology.w_schedule()(0), np.float64)
    w2 = w0.copy()
    w2[0, 0] -= 1e-9  # ~2^-30: far below the f32 ulp at 1.0 (2^-24)
    assert not np.array_equal(w2, w0)
    # the collision this test exists for: bitwise equal after the f32 cast
    assert np.array_equal(w2.astype(np.float32), w0.astype(np.float32))
    batches = s.data.sampler(jax.random.key(1), 0)
    with pytest.raises(ValueError, match="spec clock"):
        s.engine.run_round(s.state, batches, w2, jax.random.key(2))


def test_window_from_events_duplicate_collapse_first_wins():
    """Duplicate (dst, src) events within a window collapse to ONE merge,
    and the FIRST occurrence wins — including its delivery delay."""
    W = bidirectional_ring_w(4)
    win = window_from_events(
        W, [(0, 1), (0, 3), (0, 1)], e_max=4, delays=[2, 0, 5]
    )
    assert win.n_events == 2
    assert win.edges[:2].tolist() == [[0, 1], [0, 3]]
    # the duplicate's lag-5 redelivery is dropped with it
    assert win.delays[:2].tolist() == [2, 0]
    # the collapsed edge carries the base weight ONCE
    assert win.w_eff[0, 1] == W[0, 1]
    np.testing.assert_allclose(win.w_eff.sum(axis=1), 1.0, atol=1e-12)
    # pad slots beyond the collapsed count stay zero
    assert (win.weights[2:] == 0.0).all()


def test_thinned_poisson_e_max_boundary():
    """fired == e_max fits the static window shape; e_max + 1 must raise
    (never silently truncate the realization)."""
    from repro.gossip.clocks import thinned_poisson_indices

    class _StubRng:
        """Deterministic stand-in: k distinct uniform picks."""

        def __init__(self, k):
            self._k = k

        def poisson(self, mu):
            return self._k

        def integers(self, lo, hi, size):
            return np.arange(size, dtype=np.int64) % (hi - lo)

    fired = thinned_poisson_indices(_StubRng(5), 100, 0.05, e_max=5)
    assert fired.size == 5  # exactly at the cap: passes
    with pytest.raises(ValueError, match="e_max=5"):
        thinned_poisson_indices(_StubRng(6), 100, 0.05, e_max=5)
