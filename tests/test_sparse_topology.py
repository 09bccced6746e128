"""Sparse-first topology layer: CSR graphs, generators, iterative
strong-connectivity, segment-sum consensus, thinned-Poisson clocks, and
the ``TopologySpec(kind="sparse")`` surface.

The dense [N, N] path stays the reference everywhere: sparse builders are
pinned BITWISE to their dense counterparts, the segment-sum consensus to
the dense flat reference (fp32 reduction-order tolerance), and the
iterative Kosaraju check to ``networkx.is_strongly_connected``.
"""
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import (
    DataSpec,
    ExperimentSpec,
    InferenceSpec,
    RunSpec,
    build_session,
)
from repro.api.spec import SPARSE_DENSE_GUARD, TopologySpec
from repro.core.flat import (
    FlatLayout,
    FlatPosterior,
    consensus_flat_reference,
    consensus_flat_segments,
    gather_tables,
)
from repro.core.graphs import (
    SPARSE_GENERATORS,
    SparseGraph,
    barabasi_albert_sparse,
    bidirectional_ring_sparse,
    bidirectional_ring_w,
    build_sparse,
    complete_w,
    erdos_w,
    grid_sparse,
    grid_w,
    max_in_degree,
    neighbor_lists,
    ring_sparse,
    ring_w,
    star_sparse,
    star_w,
    strongly_connected_csr,
    torus_sparse,
    torus_w,
    watts_strogatz_sparse,
)
from repro.gossip.clocks import (
    PoissonClock,
    SparseAllEdgesClock,
    SparseFailureInjectedClock,
    SparsePoissonClock,
    SparseWindow,
    build_sparse_clock,
    thinned_poisson_indices,
)


def _posts(n: int, p: int, seed: int = 0) -> FlatPosterior:
    ks = jax.random.split(jax.random.key(seed), 2)
    layout = FlatLayout.for_pytree({"w": jnp.zeros((p,))})
    return FlatPosterior(
        mean=jax.random.normal(ks[0], (n, p)),
        rho=jax.random.normal(ks[1], (n, p)) * 0.4 - 1.0,
        layout=layout,
    )

# every named dense builder the API exposes, with small-but-nontrivial
# parameters — the neighbor-extraction consistency sweep runs over ALL of
# them (satellite: one CSR construction behind every extraction helper)
NAMED_DENSE = {
    "star": star_w(5, 0.3),
    "grid": grid_w(3, 4),
    "ring": ring_w(7),
    "bidirectional_ring": bidirectional_ring_w(8),
    "torus": torus_w(3, 4),
    "complete": complete_w(6),
    "erdos": erdos_w(12, 0.5, seed=3),
    "watts_strogatz": watts_strogatz_sparse(20, k=4, beta=0.2, seed=1).to_dense(),
    "barabasi_albert": barabasi_albert_sparse(20, m=2, seed=1).to_dense(),
}


# -- sparse builders vs dense counterparts (bitwise) -------------------------


@pytest.mark.parametrize("sparse_g,dense_w", [
    (ring_sparse(7), ring_w(7)),
    (bidirectional_ring_sparse(8), bidirectional_ring_w(8)),
    (grid_sparse(3, 4), grid_w(3, 4)),
    (torus_sparse(3, 4), torus_w(3, 4)),
    (star_sparse(5, 0.3), star_w(5, 0.3)),
], ids=["ring", "bidirectional_ring", "grid", "torus", "star"])
def test_sparse_builder_matches_dense_bitwise(sparse_g, dense_w):
    # the sparse builders never allocate [N, N]; their densification must
    # still reproduce the seed dense builders EXACTLY (same weight arithmetic)
    assert np.array_equal(sparse_g.to_dense(), dense_w)
    sparse_g.validate()


def test_from_dense_round_trip():
    W = erdos_w(15, 0.4, seed=7)
    g = SparseGraph.from_dense(W)
    assert np.array_equal(g.to_dense(), W)
    assert g.n_edges == int(np.count_nonzero(W))
    g.validate()


def test_generator_registry_and_build_sparse():
    for name in ("ring", "bidirectional_ring", "grid", "torus", "star",
                 "watts_strogatz", "barabasi_albert"):
        assert name in SPARSE_GENERATORS
    g = build_sparse("watts_strogatz", n=40, k=4, beta=0.1, seed=2)
    assert g.n_agents == 40
    g.validate()
    with pytest.raises(ValueError, match="unknown sparse generator"):
        build_sparse("moebius", n=4)


def test_small_world_generators_are_valid_and_deterministic():
    for mk in (lambda s: watts_strogatz_sparse(60, k=6, beta=0.3, seed=s),
               lambda s: barabasi_albert_sparse(60, m=3, seed=s)):
        a, b = mk(4), mk(4)
        assert np.array_equal(a.indices, b.indices)
        assert np.array_equal(a.weights, b.weights)
        a.validate()  # row-stochastic + self-loops + strongly connected
        assert not np.array_equal(a.indices, mk(5).indices) or \
            not np.array_equal(a.weights, mk(5).weights)


# -- iterative strong connectivity vs networkx -------------------------------


def _random_support(rng, n, p):
    A = rng.random((n, n)) < p
    np.fill_diagonal(A, True)
    indptr = np.zeros(n + 1, np.int64)
    indptr[1:] = np.cumsum(A.sum(1))
    indices = np.concatenate([np.nonzero(A[i])[0] for i in range(n)])
    return A, indptr, indices.astype(np.int32)


def test_strong_connectivity_matches_networkx_seeded():
    nx = pytest.importorskip("networkx")
    rng = np.random.default_rng(0)
    agree_true = agree_false = 0
    for _ in range(60):
        n = int(rng.integers(2, 25))
        p = float(rng.uniform(0.02, 0.4))
        A, indptr, indices = _random_support(rng, n, p)
        got = strongly_connected_csr(indptr, indices, n)
        ref = nx.is_strongly_connected(nx.from_numpy_array(
            A.astype(float), create_using=nx.DiGraph))
        assert got == ref
        agree_true += ref
        agree_false += not ref
    # the sweep must exercise BOTH verdicts, else it proves nothing
    assert agree_true > 0 and agree_false > 0


def test_strong_connectivity_property_hypothesis():
    hyp = pytest.importorskip("hypothesis")
    nx = pytest.importorskip("networkx")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=60, deadline=None)
    @given(st.integers(0, 2**32 - 1), st.integers(2, 20),
           st.floats(0.02, 0.5))
    def prop(seed, n, p):
        A, indptr, indices = _random_support(
            np.random.default_rng(seed), n, p)
        assert strongly_connected_csr(indptr, indices, n) == \
            nx.is_strongly_connected(nx.from_numpy_array(
                A.astype(float), create_using=nx.DiGraph))

    prop()


def test_strong_connectivity_edge_cases():
    assert strongly_connected_csr(np.array([0, 1]), np.array([0]), 1)
    # two nodes, no cross edges: disconnected
    indptr = np.array([0, 1, 2])
    indices = np.array([0, 1], np.int32)
    assert not strongly_connected_csr(indptr, indices, 2)
    # directed ring IS strongly connected; drop one edge and it is not
    g = ring_sparse(30)
    assert g.strongly_connected()


# -- one CSR construction behind every neighbor extraction -------------------


@pytest.mark.parametrize("name", sorted(NAMED_DENSE))
def test_neighbor_extraction_consistency(name):
    """neighbor_lists / max_in_degree must agree with the single
    SparseGraph.from_dense construction on every named topology (no
    per-helper nonzero scans left), and its rows are W's nonzero entries."""
    W = NAMED_DENSE[name]
    g = SparseGraph.from_dense(W)
    lists = neighbor_lists(W)
    assert lists == [list(g.row(i)[0]) for i in range(g.n_agents)]
    assert max_in_degree(W) == g.max_in_degree
    for i in range(g.n_agents):
        row_idx, row_w = g.row(i)
        assert np.array_equal(row_idx, np.nonzero(W[i])[0])
        np.testing.assert_allclose(row_w, W[i][row_idx], rtol=0, atol=1e-7)


# -- segment-sum consensus vs the dense flat reference -----------------------


@pytest.mark.parametrize("wire", ["f32", "bf16", "f16"])
def test_segments_matches_dense_reference_per_wire(wire):
    n, p = 18, 96
    g = watts_strogatz_sparse(n, k=4, beta=0.3, seed=9)
    posts = _posts(n, p, seed=2)
    dst, src, w = g.edge_arrays()
    got = consensus_flat_segments(
        posts, jnp.asarray(dst), jnp.asarray(src), jnp.asarray(w),
        wire_dtype=wire)
    ref_mean, ref_rho = consensus_flat_reference(
        posts.mean, posts.rho, jnp.asarray(g.to_dense(), jnp.float32),
        wire_dtype=wire)
    # same op chain, different reduction order (edge-order scatter vs
    # column-order matmul): fp32 tolerance, not bitwise
    assert float(jnp.max(jnp.abs(got.mean - ref_mean))) <= 1e-4
    assert float(jnp.max(jnp.abs(got.rho - ref_rho))) <= 1e-4


def test_segments_active_mask_passthrough_bitwise():
    n, p = 12, 33
    g = bidirectional_ring_sparse(n)
    posts = _posts(n, p, seed=5)
    dst, src, w = g.edge_arrays()
    active = np.zeros(n, bool)
    active[[2, 3, 7]] = True
    out = consensus_flat_segments(
        posts, jnp.asarray(dst), jnp.asarray(src), jnp.asarray(w),
        active=jnp.asarray(active))
    # inactive rows pass through BITWISE — the gossip conserve rule
    # depends on exact passthrough, not approximate
    inact = ~active
    assert bool(jnp.all(out.mean[inact] == posts.mean[inact]))
    assert bool(jnp.all(out.rho[inact] == posts.rho[inact]))
    assert not bool(jnp.all(out.mean[active] == posts.mean[active]))


def test_segments_blocked_matches_single_call():
    n, p = 10, 96
    g = torus_sparse(2, 5)
    posts = _posts(n, p, seed=11)
    dst, src, w = g.edge_arrays()
    args = (posts, jnp.asarray(dst), jnp.asarray(src), jnp.asarray(w))
    whole = consensus_flat_segments(*args)
    blocked = consensus_flat_segments(*args, block=32)
    # the param-axis loop changes nothing about per-column arithmetic
    assert bool(jnp.all(whole.mean == blocked.mean))
    assert bool(jnp.all(whole.rho == blocked.rho))


# -- the row-gather execution of the segment consensus -----------------------


def _window_edges(n, *, pads, rate=0.6, seed=4, k=4):
    """A Poisson window's fired edges on WS(n, k), zero-weight pads (all
    aimed at row 0, as the clock pads) and the self-loops appended, the
    engine's layout; plus the clock's row bound D and the active mask."""
    clk = SparsePoissonClock(watts_strogatz_sparse(n, k=k, beta=0.3, seed=2),
                             rate=rate, seed=seed)
    win = clk.window(0)
    e = win.n_events
    ar = np.arange(n, dtype=np.int32)
    zeros_i = np.zeros(pads, np.int32)
    dst = np.concatenate([win.dst[:e], zeros_i, ar])
    src = np.concatenate([win.src[:e], zeros_i, ar])
    w = np.concatenate([win.weights[:e], np.zeros(pads, np.float32),
                        win.self_weight.astype(np.float32)])
    return dst, src, w, win.active.copy(), clk.max_in_degree + 1


def _full_row_edges(n):
    """Every edge of WS(n, 4) fired: the max-in-degree row is full."""
    g = watts_strogatz_sparse(n, k=4, beta=0.3, seed=2)
    dst, src, w = g.edge_arrays()
    ns = dst != src
    deg = np.bincount(dst[ns], minlength=n)
    assert deg.max() > deg.min()  # ragged rows, so one row is the longest
    ar = np.arange(n, dtype=np.int32)
    diag = np.zeros(n, np.float32)
    diag[dst[~ns]] = w[~ns]
    return (np.concatenate([dst[ns], ar]), np.concatenate([src[ns], ar]),
            np.concatenate([w[ns], diag]), np.ones(n, bool),
            int(deg.max()) + 1)


def _gather_case(case):
    n = 20
    if case == "max_in_degree_row":
        return n, 96, _full_row_edges(n)
    dst, src, w, active, slots = _window_edges(n, pads=37)
    if case == "all_inactive":
        active[:] = False
    elif case == "all_active":
        # rows with no fired in-edge still merge their self term alone
        active[:] = True
    p = 200 if case == "p_not_lane_multiple" else 128
    return n, p, (dst, src, w, active, slots)


@pytest.mark.parametrize("wire", ["f32", "bf16"])
@pytest.mark.parametrize("case", ["mixed_active_with_pads",
                                  "max_in_degree_row", "all_inactive",
                                  "all_active", "p_not_lane_multiple"])
def test_row_gather_matches_segment_sum(case, wire):
    """The row-gather kernel (Pallas interpreter) against the XLA segment
    sum: the same terms in the same edge order, fp32 accumulation;
    zero-weight pads add nothing and inactive rows pass through bitwise."""
    n, p, (dst, src, w, active, slots) = _gather_case(case)
    posts = _posts(n, p, seed=8)
    args = (posts, jnp.asarray(dst), jnp.asarray(src), jnp.asarray(w))
    kw = dict(active=jnp.asarray(active), wire_dtype=wire)
    ref = consensus_flat_segments(*args, mode="xla", **kw)
    got = consensus_flat_segments(*args, slots=slots, mode="interpret", **kw)
    assert got.mean.shape == (n, p)
    np.testing.assert_allclose(np.asarray(got.mean), np.asarray(ref.mean),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(got.rho), np.asarray(ref.rho),
                               rtol=1e-5, atol=1e-6)
    inact = ~active
    np.testing.assert_array_equal(np.asarray(got.mean)[inact],
                                  np.asarray(posts.mean)[inact])
    np.testing.assert_array_equal(np.asarray(got.rho)[inact],
                                  np.asarray(posts.rho)[inact])
    if active.any():
        assert not np.array_equal(np.asarray(got.mean)[active],
                                  np.asarray(posts.mean)[active])


def test_gather_tables_row_order_self_slot_and_dropped_pads():
    """Row i: its fired sources in edge order, then its own id with the
    self weight, then its own id at weight 0; zero-weight pads are gone,
    and an inactive row is all own id at weight 0."""
    n, slots = 5, 4
    #        fired edges       pads    self-loops
    dst = [3, 1, 3, 0, 1,     0, 0,   0, 1, 2, 3, 4]
    src = [2, 4, 0, 4, 3,     0, 0,   0, 1, 2, 3, 4]
    w = [.2, .1, .3, .4, .2,   0, 0,   .6, .7, 1, .5, 1]
    active = np.array([True, True, False, True, False])
    nbr, wts = gather_tables(jnp.asarray(dst), jnp.asarray(src),
                             jnp.asarray(w, jnp.float32), n, slots,
                             jnp.asarray(active))
    np.testing.assert_array_equal(np.asarray(nbr), [
        [4, 0, 0, 0],
        [4, 3, 1, 1],
        [2, 2, 2, 2],
        [2, 0, 3, 3],
        [4, 4, 4, 4],
    ])
    np.testing.assert_allclose(np.asarray(wts), np.float32([
        [.4, .6, 0, 0],
        [.1, .2, .7, 0],
        [0, 0, 0, 0],
        [.2, .3, .5, 0],
        [0, 0, 0, 0],
    ]))
    # without a mask every row keeps its nonzero entries
    nbr_all, wts_all = gather_tables(jnp.asarray(dst), jnp.asarray(src),
                                     jnp.asarray(w, jnp.float32), n, slots)
    assert list(np.asarray(nbr_all)[2]) == [2, 2, 2, 2]
    assert list(np.asarray(wts_all)[2]) == [1, 0, 0, 0]


# -- thinned-Poisson clocks --------------------------------------------------


def test_thinned_poisson_pure_function_of_seed_round():
    n_edges, mu = 5000, 0.03
    for r in range(4):
        a = thinned_poisson_indices(np.random.default_rng([7, r]), n_edges, mu)
        b = thinned_poisson_indices(np.random.default_rng([7, r]), n_edges, mu)
        assert np.array_equal(a, b), "same (seed, round) must be bitwise"
        assert a.size == np.unique(a).size and np.all(np.diff(a) > 0)
        assert a.size == 0 or (a.min() >= 0 and a.max() < n_edges)
    r0 = thinned_poisson_indices(np.random.default_rng([7, 0]), n_edges, mu)
    r1 = thinned_poisson_indices(np.random.default_rng([7, 1]), n_edges, mu)
    assert not np.array_equal(r0, r1), "distinct rounds must differ"


def test_thinned_poisson_marginal_rate():
    # per-edge firing probability under thinning is 1 - exp(-mu); check
    # the empirical mean over many windows (law of large numbers, wide tol)
    n_edges, mu, windows = 400, 0.5, 400
    hits = 0
    for r in range(windows):
        hits += thinned_poisson_indices(
            np.random.default_rng([13, r]), n_edges, mu).size
    p_emp = hits / (n_edges * windows)
    assert abs(p_emp - (1.0 - np.exp(-mu))) < 0.02


def test_poisson_clock_e_max_cap():
    W = bidirectional_ring_w(6)
    # a declared cap shrinks the static [E_max] window buffers the engine
    # jits over (default would be all 18 directed edges)
    c = PoissonClock(W, rate=0.5, seed=3, e_max=12)
    for r in range(5):
        win = c.window(r)
        assert win.edges.shape[0] == 12 and win.n_events <= 12
    # cap of 1 with a hot clock: some window must overflow and raise
    hot = PoissonClock(W, rate=50.0, seed=3, e_max=1)
    with pytest.raises(ValueError, match="e_max"):
        for r in range(20):
            hot.window(r)
    with pytest.raises(ValueError):
        PoissonClock(W, rate=0.5, seed=0, e_max=0)


# -- erdos_w rich failure ----------------------------------------------------


def test_erdos_w_unsatisfiable_raises_rich_error():
    with pytest.raises(RuntimeError) as ei:
        erdos_w(60, 0.001, seed=0, attempts=4)
    msg = str(ei.value)
    assert "n=60" in msg and "p=0.001" in msg and "4 attempts" in msg
    assert "log(n)/n" in msg  # the actionable threshold hint


def test_erdos_w_retries_until_connected():
    # p below a single-shot sure thing but workable within the budget:
    # the retry loop must land on a connected sample deterministically
    W = erdos_w(25, 0.25, seed=1, attempts=200)
    assert SparseGraph.from_dense(W).strongly_connected()


# -- TopologySpec(kind="sparse") ---------------------------------------------


def test_sparse_spec_validate_and_dense_bridge():
    spec = TopologySpec.sparse("watts_strogatz", n=50, k=4, beta=0.2, seed=1)
    spec.validate()
    assert spec.n_agents() == 50
    g = spec.sparse_graph()
    assert g is spec.sparse_graph()  # memoized: one construction
    W = spec.w_schedule()(0)
    assert np.array_equal(W, g.to_dense())


def test_sparse_spec_dense_guard():
    n = SPARSE_DENSE_GUARD + 1
    spec = TopologySpec.sparse("ring", n=n)
    assert spec.n_agents() == n  # metadata never materializes W
    with pytest.raises(ValueError, match="guard"):
        spec.w_schedule()


def test_sparse_spec_checkpoint_embeddable():
    spec = TopologySpec.sparse("barabasi_albert", n=30, m=2, seed=5)
    doc = json.loads(json.dumps(dataclasses.asdict(spec)))
    back = TopologySpec(**doc)
    back.validate()
    g0, g1 = spec.sparse_graph(), back.sparse_graph()
    assert np.array_equal(g0.indptr, g1.indptr)
    assert np.array_equal(g0.indices, g1.indices)
    assert np.array_equal(g0.weights, g1.weights)


def test_sparse_spec_unknown_generator():
    with pytest.raises(ValueError, match="generator"):
        TopologySpec.sparse("kleinberg", n=10).sparse_graph()


# -- edge-native sparse clocks (SparseWindow, no [N, N] anywhere) ------------


def _win_equal(a: SparseWindow, b: SparseWindow) -> bool:
    return (a.index == b.index and a.n_events == b.n_events
            and np.array_equal(a.dst, b.dst)
            and np.array_equal(a.src, b.src)
            and np.array_equal(a.weights, b.weights)
            and np.array_equal(a.self_weight, b.self_weight)
            and np.array_equal(a.active, b.active))


def test_sparse_clock_window_pure_function_of_seed_round():
    g = watts_strogatz_sparse(30, k=4, beta=0.3, seed=2)
    a = SparsePoissonClock(g, rate=0.7, seed=5)
    b = SparsePoissonClock(g, rate=0.7, seed=5)
    # out-of-order access defeats the one-slot memo: windows must still be
    # bitwise functions of (seed, round), never of call history
    for r in (0, 3, 1, 3, 0):
        assert _win_equal(a.window(r), b.window(r))
    assert not _win_equal(a.window(0), a.window(1))
    assert not _win_equal(
        a.window(2), SparsePoissonClock(g, rate=0.7, seed=6).window(2)
    )


def test_sparse_all_edges_window_self_weight_is_base_diagonal_bitwise():
    g = watts_strogatz_sparse(20, k=4, beta=0.2, seed=1)
    c = SparseAllEdgesClock(g)
    c.validate()
    win = c.window(0)
    W = g.to_dense()
    # the sparse ladder anchor: every non-self edge fires, so the conserve
    # self-weights equal the base diagonal EXACTLY and everyone is active
    assert win.n_events == c.n_edges
    assert np.array_equal(win.self_weight, np.diagonal(W))
    assert win.active.all() and win.max_lag == 0
    assert np.array_equal(win.w_eff != 0.0, W != 0.0)
    np.testing.assert_allclose(win.w_eff, W, rtol=0, atol=1e-7)
    # rows conserve: w_eff stays row-stochastic up to the f32 cast of the
    # off-diagonal wire weights (the f64 self-weights are exact)
    np.testing.assert_allclose(win.w_eff.sum(1), 1.0, rtol=0, atol=1e-6)


def test_sparse_failure_injected_drops_fired_edges():
    g = watts_strogatz_sparse(30, k=4, beta=0.2, seed=3)
    mk_inner = lambda: SparsePoissonClock(g, rate=2.0, seed=4)
    dropped = SparseFailureInjectedClock(mk_inner(), drop_rate=0.5, seed=9)
    again = SparseFailureInjectedClock(mk_inner(), drop_rate=0.5, seed=9)
    inner = mk_inner()
    strictly_fewer = False
    for r in range(6):
        wi, wd = inner.window(r), dropped.window(r)
        surv = set(zip(wd.dst[:wd.n_events], wd.src[:wd.n_events]))
        full = set(zip(wi.dst[:wi.n_events], wi.src[:wi.n_events]))
        assert surv <= full  # drops only remove events, never invent them
        strictly_fewer |= wd.n_events < wi.n_events
        assert _win_equal(wd, again.window(r))  # salted stream: bitwise
    assert strictly_fewer
    with pytest.raises(ValueError, match="drop_rate"):
        SparseFailureInjectedClock(mk_inner(), drop_rate=1.0)


def test_sparse_poisson_e_max_cap_and_overflow():
    g = bidirectional_ring_sparse(8)
    base = SparsePoissonClock(g, rate=1.0)
    assert base.e_max == base.n_edges  # default cap: every non-self edge
    small = SparsePoissonClock(g, rate=0.2, seed=3, e_max=4)
    for r in range(5):
        win = small.window(r)
        assert win.e_max == 4 and win.n_events <= 4
    hot = SparsePoissonClock(g, rate=60.0, seed=3, e_max=2)
    with pytest.raises(ValueError, match="e_max"):
        for r in range(20):
            hot.window(r)
    with pytest.raises(ValueError, match="e_max"):
        SparsePoissonClock(g, rate=1.0, e_max=0)
    with pytest.raises(ValueError, match="e_max"):
        SparsePoissonClock(g, rate=1.0, e_max=base.n_edges + 1)


def test_sparse_clock_faults_filter_crashed_agents():
    g = watts_strogatz_sparse(24, k=4, beta=0.2, seed=5)
    doc = {"kind": "poisson", "rate": 3.0, "seed": 2,
           "faults": {"crash_rate": 0.3, "recover_rate": 0.5, "seed": 7}}
    c = build_sparse_clock(doc, g)
    saw_crash = False
    for r in range(8):
        win = c.window(r)
        down = c.crashed(r)
        saw_crash |= bool(down.any())
        # a fired edge never touches a crashed endpoint, and the conserve
        # rule keeps crashed rows idle (active False, self-weight 1.0)
        assert not down[win.dst[:win.n_events]].any()
        assert not down[win.src[:win.n_events]].any()
        assert not win.active[down].any()
        np.testing.assert_array_equal(win.self_weight[down], 1.0)
    assert saw_crash
    with pytest.raises(ValueError, match="OUTERMOST"):
        build_sparse_clock(
            {"kind": "failure_injected", "drop_rate": 0.2,
             "inner": {"kind": "poisson", "faults": {"crash_rate": 0.1}}}, g)
    with pytest.raises(ValueError, match="unknown sparse clock"):
        build_sparse_clock({"kind": "metronome"}, g)


def test_sparse_window_w_eff_refuses_above_guard():
    n = SPARSE_DENSE_GUARD + 1
    win = SparseWindow(
        index=0, dst=np.zeros(1, np.int32), src=np.zeros(1, np.int32),
        weights=np.zeros(1, np.float32), self_weight=np.ones(n),
        active=np.zeros(n, bool), n_agents=n, n_events=0,
    )
    with pytest.raises(ValueError, match="segments"):
        win.w_eff


# -- segments engine vs the dense masked engine (below the guard) ------------


def _clocked_spec(n, impl, wire="f32", n_rounds=2, **clock_extra):
    topo = TopologySpec.sparse(
        "watts_strogatz", n=n, k=4, beta=0.2, seed=1,
        clock={"kind": "poisson", "rate": 1.0, "seed": 3, **clock_extra},
    )
    return ExperimentSpec(
        topology=topo,
        data=DataSpec(
            dataset_params=dict(n_classes=3, dim=8, n_train_per_class=30),
            partition="iid", partition_params=dict(n_agents=n),
            batch_size=4, local_updates=2,
        ),
        inference=InferenceSpec(
            hidden=8, depth=1, lr=1e-2,
            consensus_impl=impl, wire_dtype=wire,
        ),
        run=RunSpec(n_rounds=n_rounds, seed=0),
    )


@pytest.mark.parametrize("wire,gather", [
    pytest.param("f32", False, id="f32"),
    pytest.param("bf16", False, id="bf16"),
    pytest.param("f16", False, id="f16"),
    pytest.param("f32", True, id="f32-row_gather"),
    pytest.param("bf16", True, id="bf16-row_gather"),
])
def test_segments_engine_matches_masked_engine_per_wire(wire, gather,
                                                        monkeypatch):
    """Below SPARSE_DENSE_GUARD the same SparseWindow executes two ways:
    edge-native segments, or densified (w_eff) through the masked engine.
    Both cast payloads to the wire dtype BEFORE reduction, so they sum the
    same quantized values — only edge-order vs column-order differs, which
    is fp32 reduction tolerance, not wire tolerance.  The segments engine
    runs the XLA segment sum here, or (``gather``) the row-gather kernel
    its TPU dispatch picks, in the Pallas interpreter."""
    if gather:
        from repro.gossip import engine as engine_mod

        monkeypatch.setattr(engine_mod, "segments_mode",
                            lambda n, slots, wire=None: "interpret")
    s_seg = build_session(_clocked_spec(16, "segments", wire=wire))
    assert s_seg.engine.segments_mode == ("interpret" if gather else "xla")
    s_msk = build_session(_clocked_spec(16, "masked", wire=wire))
    s_seg.run()
    s_msk.run()
    d_mean = np.max(np.abs(np.asarray(s_seg.posterior().mean)
                           - np.asarray(s_msk.posterior().mean)))
    d_rho = np.max(np.abs(np.asarray(s_seg.posterior().rho)
                          - np.asarray(s_msk.posterior().rho)))
    assert d_mean <= 1e-4 and d_rho <= 1e-4
    assert np.array_equal(np.asarray(s_seg.state.n_merges),
                          np.asarray(s_msk.state.n_merges))


def test_sparse_clock_spec_auto_selects_segments():
    spec = _clocked_spec(12, "auto", n_rounds=1)
    spec.validate()
    s = build_session(spec)
    assert s.engine.consensus_impl == "segments"
    s.run()
    assert int(s.state.round) == 1


def test_sparse_spec_clock_validation_and_errors():
    spec = _clocked_spec(12, "segments")
    dataclasses.replace(spec, run=RunSpec(n_rounds=2, seed=0,
                                          engine="gossip")).validate()
    # segments needs edge-native windows: dense gossip clocks emit [N, N]
    dense = TopologySpec.gossip(
        "bidirectional_ring", base_params={"n": 8},
        clock={"kind": "poisson", "rate": 1.0})
    with pytest.raises(ValueError, match="edge-native"):
        dataclasses.replace(spec, topology=dense).validate()
    with pytest.raises(ValueError, match="mean_only"):
        dataclasses.replace(
            spec, inference=dataclasses.replace(
                spec.inference, consensus="mean_only", wire_dtype="f32"),
        ).validate()
    # a clockless sparse topology is synchronous: no window execution to pick
    clockless = TopologySpec.sparse("watts_strogatz", n=12, k=4, beta=0.2,
                                    seed=1)
    with pytest.raises(ValueError, match="consensus_impl"):
        dataclasses.replace(spec, topology=clockless).validate()
    with pytest.raises(ValueError, match="no clock"):
        clockless.gossip_clock()
    # ppermute shards dense EventWindows; sparse clocks have none
    with pytest.raises(ValueError, match="EventWindows"):
        build_session(_clocked_spec(12, "ppermute"))


def test_sparse_clock_w_schedule_emits_sparse_windows():
    spec = _clocked_spec(10, "segments")
    sched = spec.topology.w_schedule()
    for r in (0, 2):
        win = sched(r)
        assert isinstance(win, SparseWindow) and win.index == r
        assert win.n_agents == 10
    clock = spec.topology.gossip_clock()
    assert clock is spec.topology.gossip_clock()  # memoized: one build
    assert _win_equal(sched(1), clock.window(1))
