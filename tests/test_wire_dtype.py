"""Wire-dtype compressed consensus (bf16/f16 exchange, fp32 accumulate).

Pins the two halves of the ROADMAP "Wire precision" contract on EVERY
kernel path (dense, sparse, masked, masked_sparse, ppermute window,
delayed gather):

1. ``wire_dtype="f32"`` (the default) is a STRUCTURAL no-op — output
   bitwise identical to the pre-wire kernels (``assert_array_equal``,
   no tolerance), so the whole PR-3/PR-4 equivalence ladder is untouched.
2. A compressed wire dtype agrees with the fp32 reference within the
   DERIVED error bound: one cast at the exchange boundary perturbs each
   exchanged scalar by a relative error <= u = ``core.numerics
   .wire_error_bound(dtype)`` (round-to-nearest unit roundoff eps/2:
   2^-8 for bf16's 7 stored mantissa bits, 2^-11 for f16's 10).  Since
   eq. (6) accumulates convex combinations of POSITIVE rounded precisions,

       |d new_prec|  <=  u * sum_j W_ij prec_j          (relative u)
       |d mean_out|  <=  u * (W @ |prec*mu| + |mean_out| * W @ prec)
                          / new_prec
       |d rho_out|   <=  (u/2) * sigma_out / sigmoid(rho_out)

   (second-order and fp32-accumulation terms absorbed into the slack
   factor C).  The fixtures span EXTREME posterior scales (sigma 1e-4 ..
   1e4, the ``softplus_inv`` extreme-sigma regime) for bf16, whose
   exponent range matches fp32; f16 is validated at moderate scales (its
   range caps the representable precision at ~6e4).

Plus the cost-model halving assertions and the InferenceSpec plumbing
(engine-level f32 bitwise identity, bf16 session sanity, eager
validation), and the optional bf16-resident delivery-latency history ring.
"""
import dataclasses
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
    FlatLayout,
    FlatPosterior,
    consensus_flat,
    consensus_flat_delayed,
    consensus_flat_masked,
    consensus_flat_segments,
)
from repro.core.graphs import SparseGraph, bidirectional_ring_w, complete_w
from repro.core.numerics import (
    canonical_wire_dtype,
    softplus,
    softplus_inv,
    wire_dtype_name,
    wire_error_bound,
    wire_itemsize,
    wire_roundtrip,
)
from repro.gossip.clocks import PoissonClock, window_from_events
from repro.launch.consensus_opt import consensus_ppermute_window
from repro.launch.costmodel import consensus_roofline, gossip_window_roofline

# slack factor absorbing second-order roundoff, the output division, and
# the fp32 accumulation itself (measured headroom ~2x at C=4; see the
# derivation in the module docstring)
SLACK = 4.0


def _flat(mean, rho):
    layout = FlatLayout.for_pytree({"w": jnp.zeros((mean.shape[-1],))})
    return FlatPosterior(
        mean=jnp.asarray(mean), rho=jnp.asarray(rho), layout=layout
    )


def _extreme_posts(n, p, seed=0, scales=None):
    """[N, P] posterior whose per-agent sigma spans the softplus_inv
    extreme-sigma regime (1e-4 .. 1e4) — the fixtures the wire rounding
    must survive.  Means scale with sigma so prec*mu stays interesting."""
    rng = np.random.default_rng(seed)
    if scales is None:
        scales = [1e-4, 1e-2, 1.0, 1.0, 1e2, 1e4]
    assert len(scales) == n
    rho = np.zeros((n, p), np.float32)
    for i, s in enumerate(scales):
        sig = s * np.exp(rng.normal(size=p).astype(np.float32) * 0.3)
        rho[i] = np.asarray(softplus_inv(jnp.asarray(sig)))
    mean = (
        rng.normal(size=(n, p)) * np.maximum(np.asarray(scales)[:, None], 1.0)
    ).astype(np.float32)
    return _flat(mean, rho)


def _moderate_posts(n, p, seed=0):
    """Moderate-sigma fixture for f16 (prec = sigma^-2 must stay under
    f16's ~6.5e4 ceiling)."""
    return _extreme_posts(n, p, seed=seed, scales=[0.1, 0.3, 1.0, 1.0, 3.0, 10.0][:n])


def _assert_within_wire_bound(out, ref, W_eff, posts, wire, active=None):
    """The derived error bound (module docstring) per element, from the
    fp32 reference intermediates.  ``active=None`` checks every row;
    otherwise only active rows (inactive rows are asserted bitwise by the
    caller)."""
    u = wire_error_bound(wire)
    Wn = np.asarray(W_eff, np.float64)
    prec = np.asarray(1.0 / jnp.square(softplus(posts.rho)), np.float64)
    mean_in = np.asarray(posts.mean, np.float64)
    new_prec = Wn @ prec
    mean_ref = np.asarray(ref.mean, np.float64)
    rho_ref = np.asarray(ref.rho, np.float64)
    bound_mean = (
        SLACK * u * (Wn @ (prec * np.abs(mean_in))
                     + np.abs(mean_ref) * new_prec) / new_prec
    )
    sig_ref = np.asarray(softplus(ref.rho), np.float64)
    sigmoid = 1.0 / (1.0 + np.exp(-rho_ref))
    bound_rho = SLACK * 0.5 * u * sig_ref / sigmoid
    rows = slice(None) if active is None else np.asarray(active, bool)
    d_mean = np.abs(np.asarray(out.mean, np.float64) - mean_ref)
    d_rho = np.abs(np.asarray(out.rho, np.float64) - rho_ref)
    assert (d_mean[rows] <= bound_mean[rows]).all(), (
        f"mean error exceeds the derived bound: "
        f"max ratio {(d_mean[rows] / bound_mean[rows]).max():.3f}"
    )
    assert (d_rho[rows] <= bound_rho[rows]).all(), (
        f"rho error exceeds the derived bound: "
        f"max ratio {(d_rho[rows] / bound_rho[rows]).max():.3f}"
    )
    # the compressed output must actually differ (the cast is real)
    if u > 0:
        assert d_mean[rows].max() > 0


# ---------------------------------------------------------------------------
# per-path f32 bitwise identity + bf16/f16 error bounds
# ---------------------------------------------------------------------------


N, P = 6, 384


def _paths(posts, win):
    """Every kernel path as (name, fn(wire_dtype) -> FlatPosterior, W_eff
    of the rows it computes, active mask or None)."""
    W_ring = jnp.asarray(bidirectional_ring_w(N), jnp.float32)
    W_eff = jnp.asarray(win.w_eff, jnp.float32)
    act = jnp.asarray(win.active)
    ring = SparseGraph.from_dense(bidirectional_ring_w(N))
    edges = [jnp.asarray(a) for a in ring.edge_arrays()]
    edges_w = [jnp.asarray(a)
               for a in SparseGraph.from_dense(win.w_eff).edge_arrays()]
    mesh1 = jax.sharding.Mesh(np.asarray(jax.devices()[:1]), ("agents",))
    return [
        ("dense_xla",
         lambda wd: consensus_flat(posts, W_ring, mode="xla", wire_dtype=wd),
         W_ring, None),
        ("dense_interpret",
         lambda wd: consensus_flat(posts, W_ring, mode="interpret",
                                   block=128, wire_dtype=wd),
         W_ring, None),
        ("segments",
         lambda wd: consensus_flat_segments(posts, *edges, wire_dtype=wd),
         W_ring, None),
        ("segments_interpret",
         lambda wd: consensus_flat_segments(
             posts, *edges, slots=ring.max_in_degree, mode="interpret",
             block=128, wire_dtype=wd),
         W_ring, None),
        ("masked",
         lambda wd: consensus_flat_masked(posts, W_eff, act, wire_dtype=wd),
         W_eff, win.active),
        ("masked_interpret",
         lambda wd: consensus_flat_masked(posts, W_eff, act, mode="interpret",
                                          block=128, wire_dtype=wd),
         W_eff, win.active),
        ("masked_segments",
         lambda wd: consensus_flat_segments(
             posts, *edges_w, active=act, wire_dtype=wd),
         W_eff, win.active),
        ("ppermute_window",
         lambda wd: consensus_ppermute_window(
             posts, win, mesh1, "agents", wire_dtype=wd),
         W_eff, win.active),
    ]


def _partial_window():
    win = PoissonClock(bidirectional_ring_w(N), rate=0.5, seed=7).window(0)
    assert 0 < win.active.sum() < N  # genuinely partial
    return win


def test_wire_f32_is_bitwise_noop_on_every_path():
    """Acceptance: wire_dtype="f32" output is BIT-identical to calling the
    kernel without the argument, on every consensus path."""
    posts = _extreme_posts(N, P)
    win = _partial_window()
    for name, fn, _, _ in _paths(posts, win):
        base = fn(None)
        f32 = fn("f32")
        np.testing.assert_array_equal(
            np.asarray(base.mean), np.asarray(f32.mean), err_msg=name
        )
        np.testing.assert_array_equal(
            np.asarray(base.rho), np.asarray(f32.rho), err_msg=name
        )


@pytest.mark.parametrize("wire", ["bf16", "f16"])
def test_wire_error_bound_on_every_path(wire):
    """Acceptance: every kernel path's compressed output stays within the
    derived bound vs its own fp32 reference — bf16 at EXTREME posterior
    scales (sigma 1e-4 .. 1e4), f16 at moderate scales (range-limited)."""
    posts = _extreme_posts(N, P) if wire == "bf16" else _moderate_posts(N, P)
    win = _partial_window()
    for name, fn, W_eff, active in _paths(posts, win):
        ref = fn(None)
        out = fn(wire)
        _assert_within_wire_bound(out, ref, W_eff, posts, wire, active=active)
        if active is not None:
            # inactive rows never touch the wire: bitwise passthrough
            inactive = ~np.asarray(active, bool)
            np.testing.assert_array_equal(
                np.asarray(out.mean)[inactive],
                np.asarray(posts.mean)[inactive], err_msg=name,
            )
            np.testing.assert_array_equal(
                np.asarray(out.rho)[inactive],
                np.asarray(posts.rho)[inactive], err_msg=name,
            )


def test_wire_impl_agreement_bf16():
    """The same wire dtype gives the SAME bits across executions of the
    same math: interpret==xla on the dense path, and the (single-shard)
    ppermute window == the masked xla path — the equivalence ladder
    extends one rung per wire dtype."""
    posts = _extreme_posts(N, P)
    win = _partial_window()
    W_ring = jnp.asarray(bidirectional_ring_w(N), jnp.float32)
    a = consensus_flat(posts, W_ring, mode="xla", wire_dtype="bf16")
    b = consensus_flat(posts, W_ring, mode="interpret", block=128,
                       wire_dtype="bf16")
    np.testing.assert_array_equal(np.asarray(a.mean), np.asarray(b.mean))
    np.testing.assert_array_equal(np.asarray(a.rho), np.asarray(b.rho))
    W_eff = jnp.asarray(win.w_eff, jnp.float32)
    act = jnp.asarray(win.active)
    masked = consensus_flat_masked(posts, W_eff, act, wire_dtype="bf16")
    mesh1 = jax.sharding.Mesh(np.asarray(jax.devices()[:1]), ("agents",))
    shard = consensus_ppermute_window(posts, win, mesh1, "agents",
                                      wire_dtype="bf16")
    np.testing.assert_array_equal(np.asarray(masked.mean), np.asarray(shard.mean))
    np.testing.assert_array_equal(np.asarray(masked.rho), np.asarray(shard.rho))


# ---------------------------------------------------------------------------
# delayed event-gather path
# ---------------------------------------------------------------------------


def _delayed_fixture(wire_hist="f32", seed=3):
    """A hand-built delayed window: K=3 ring slots of stale posteriors,
    events with mixed lags."""
    n, p, k = 5, 256, 3
    rng = np.random.default_rng(seed)
    posts = _extreme_posts(n, p, seed=seed, scales=[1e-3, 0.5, 1.0, 10.0, 1e3])
    W_base = bidirectional_ring_w(n)
    events = [(0, 1), (2, 3), (4, 0)]
    lags = [0, 1, 2]
    win = window_from_events(W_base, events, e_max=4, rule="conserve",
                             delays=lags)
    hd = canonical_wire_dtype(wire_hist)
    hist_mean = jnp.asarray(
        rng.normal(size=(k, n, p)).astype(np.float32)).astype(hd)
    hist_rho = jnp.asarray(
        (rng.normal(size=(k, n, p)) * 0.3 - 1.0).astype(np.float32)).astype(hd)
    args = (
        jnp.asarray(win.w_eff, jnp.float32),
        jnp.asarray(win.active),
        jnp.asarray(win.edges),
        jnp.asarray(win.weights),
        jnp.asarray(win.delays),
        hist_mean,
        hist_rho,
        jnp.asarray(2, jnp.int32),  # round index
    )
    return posts, win, args


def test_delayed_gather_wire_f32_bitwise_and_bf16_bound():
    posts, win, args = _delayed_fixture()
    base = consensus_flat_delayed(posts, *args)
    f32 = consensus_flat_delayed(posts, *args, wire_dtype="f32")
    np.testing.assert_array_equal(np.asarray(base.mean), np.asarray(f32.mean))
    np.testing.assert_array_equal(np.asarray(base.rho), np.asarray(f32.rho))

    out = consensus_flat_delayed(posts, *args, wire_dtype="bf16")
    # derived bound via the gather accumulate itself, run on fp32 inputs
    u = wire_error_bound("bf16")
    W, active, edges, weights, lags, hist_mean, hist_rho, r = args
    k = hist_mean.shape[0]
    slot = np.mod(int(r) - np.asarray(lags), k)
    dst, src = np.asarray(edges)[:, 0], np.asarray(edges)[:, 1]
    h_mean = np.asarray(hist_mean, np.float64)[slot, src]
    h_prec = 1.0 / np.square(
        np.asarray(softplus(jnp.asarray(hist_rho, jnp.float32)), np.float64)[slot, src]
    )
    w_e = np.asarray(weights, np.float64)[:, None]
    prec_now = np.asarray(1.0 / jnp.square(softplus(posts.rho)), np.float64)
    diag = np.diagonal(np.asarray(W, np.float64))[:, None]
    acc_prec = diag * prec_now
    acc_abs_pm = diag * prec_now * np.abs(np.asarray(posts.mean, np.float64))
    np.add.at(acc_prec, dst, w_e * h_prec)
    np.add.at(acc_abs_pm, dst, w_e * h_prec * np.abs(h_mean))
    mean_ref = np.asarray(base.mean, np.float64)
    rho_ref = np.asarray(base.rho, np.float64)
    bound_mean = SLACK * u * (acc_abs_pm + np.abs(mean_ref) * acc_prec) / acc_prec
    sig_ref = np.asarray(softplus(base.rho), np.float64)
    bound_rho = SLACK * 0.5 * u * sig_ref * (1.0 + np.exp(-rho_ref))
    act = np.asarray(win.active, bool)
    d_mean = np.abs(np.asarray(out.mean, np.float64) - mean_ref)
    d_rho = np.abs(np.asarray(out.rho, np.float64) - rho_ref)
    assert (d_mean[act] <= bound_mean[act]).all()
    assert (d_rho[act] <= bound_rho[act]).all()
    assert d_mean[act].max() > 0
    # inactive rows: bitwise passthrough
    np.testing.assert_array_equal(
        np.asarray(out.mean)[~act], np.asarray(posts.mean)[~act]
    )
    np.testing.assert_array_equal(
        np.asarray(out.rho)[~act], np.asarray(posts.rho)[~act]
    )


def test_delayed_gather_bf16_resident_history_decodes():
    """A bf16-RESIDENT history ring (history_dtype) is decoded to fp32
    before the gather math; the result tracks the f32-resident reference
    to bf16 storage precision (rho rounding is u-relative in rho, so the
    tolerance scales with |rho| — looser than the wire bound)."""
    posts, win, args32 = _delayed_fixture(wire_hist="f32")
    _, _, args16 = _delayed_fixture(wire_hist="bf16")
    ref = consensus_flat_delayed(posts, *args32)
    out = consensus_flat_delayed(posts, *args16)
    assert args16[5].dtype == jnp.bfloat16
    act = np.asarray(win.active, bool)
    np.testing.assert_allclose(
        np.asarray(out.mean)[act], np.asarray(ref.mean)[act],
        rtol=3e-2, atol=3e-2,
    )
    # untouched rows identical regardless of residency
    np.testing.assert_array_equal(
        np.asarray(out.mean)[~act], np.asarray(ref.mean)[~act]
    )


# ---------------------------------------------------------------------------
# cost model: bf16 halves the modeled collective / ICI bytes
# ---------------------------------------------------------------------------


def test_consensus_roofline_wire_bytes_halve_at_bf16():
    n, p = 16, 1 << 14
    f32 = consensus_roofline(n, p, n_leaves=8)["wire"]
    bf16 = consensus_roofline(n, p, n_leaves=8, wire_dtype="bf16")["wire"]
    f16 = consensus_roofline(n, p, n_leaves=8, wire_dtype="f16")["wire"]
    assert f32["dtype"] == "f32" and f32["model_saving_vs_f32"] == 1.0
    assert bf16["collective_bytes"] == 0.5 * f32["collective_bytes"]
    assert f16["collective_bytes"] == 0.5 * f32["collective_bytes"]
    assert bf16["model_saving_vs_f32"] == 2.0
    with pytest.raises(ValueError, match="wire_dtype"):
        consensus_roofline(n, p, n_leaves=8, wire_dtype="f64")


def test_gossip_window_roofline_ici_bytes_halve_at_bf16():
    n, p, s = 16, 1 << 14, 8
    kw = dict(n_participating=8, n_shards=s, n_cross_offsets=3)
    f32 = gossip_window_roofline(n, p, **kw)
    bf16 = gossip_window_roofline(n, p, wire_dtype="bf16", **kw)
    for key in ("window_ppermute", "dense_allgather"):
        assert bf16["ici_bytes"][key] == 0.5 * f32["ici_bytes"][key]
    # HBM terms are fp32-resident: untouched by the wire dtype
    assert bf16["hbm_bytes"] == f32["hbm_bytes"]
    assert bf16["wire_dtype"] == "bf16"
    # the history ring residency halves independently
    d32 = gossip_window_roofline(n, p, n_participating=8, delay_depth=2,
                                 n_stale_events=4)
    d16 = gossip_window_roofline(n, p, n_participating=8, delay_depth=2,
                                 n_stale_events=4, history_dtype="bf16")
    assert d16["hist_resident_bytes"] == 0.5 * d32["hist_resident_bytes"]
    assert d16["hbm_bytes"]["history"] == 0.5 * d32["hbm_bytes"]["history"]
    assert d16["hbm_bytes"]["window_masked"] == d32["hbm_bytes"]["window_masked"]


# ---------------------------------------------------------------------------
# numerics helpers
# ---------------------------------------------------------------------------


def test_wire_dtype_helpers():
    assert canonical_wire_dtype(None) == jnp.float32
    assert canonical_wire_dtype("bf16") == jnp.bfloat16
    assert canonical_wire_dtype(jnp.float16) == jnp.float16
    assert wire_dtype_name(jnp.bfloat16) == "bf16"
    assert wire_itemsize("f32") == 4 and wire_itemsize("bf16") == 2
    # u = eps/2: round-to-nearest halves the machine epsilon
    assert wire_error_bound("f32") == 0.0
    assert wire_error_bound("bf16") == float(jnp.finfo(jnp.bfloat16).eps) / 2
    assert wire_error_bound("bf16") == 2.0 ** -8
    assert wire_error_bound("f16") == float(jnp.finfo(jnp.float16).eps) / 2
    assert wire_error_bound("f16") == 2.0 ** -11
    with pytest.raises(ValueError, match="wire_dtype"):
        canonical_wire_dtype("f64")
    # dtype-likes outside the wire set are rejected like their spellings
    # (an int/f64 wire would corrupt, not compress)
    with pytest.raises(ValueError, match="wire_dtype"):
        canonical_wire_dtype(jnp.float64)
    with pytest.raises(ValueError, match="wire_dtype"):
        canonical_wire_dtype(jnp.int32)
    x = jnp.asarray([1.0, 2.0, 3.0], jnp.float32)
    assert wire_roundtrip(x, "f32") is x  # STRUCTURAL no-op, same object
    y = wire_roundtrip(jnp.asarray([1.0 + 2.0 ** -10]), "bf16")
    assert y.dtype == jnp.float32 and float(y[0]) == 1.0  # really rounded
    # the worst-case single cast stays within u (midpoint rounding)
    z = jnp.asarray([1.0 + 2.0 ** -8], jnp.float32)
    rel = abs(float(wire_roundtrip(z, "bf16")[0]) - float(z[0])) / float(z[0])
    assert rel <= wire_error_bound("bf16")


# ---------------------------------------------------------------------------
# InferenceSpec plumbing: engines, sessions, validation
# ---------------------------------------------------------------------------


def _gossip_session_spec(wire="f32", clock=None, n=4, n_rounds=3, **inf_kw):
    return ExperimentSpec(
        topology=TopologySpec.gossip(
            "bidirectional_ring", {"n": n},
            clock=clock or {"kind": "poisson", "rate": 0.8, "seed": 1},
        ),
        data=DataSpec(
            dataset_params=dict(n_classes=3, dim=8, n_train_per_class=30),
            partition="iid", partition_params=dict(n_agents=n),
            batch_size=4, local_updates=2,
        ),
        inference=InferenceSpec(hidden=8, depth=1, lr=1e-2, wire_dtype=wire,
                                **inf_kw),
        run=RunSpec(n_rounds=n_rounds, seed=0),
    )


def test_wire_spec_validation():
    with pytest.raises(ValueError, match="wire_dtype"):
        InferenceSpec(wire_dtype="f64").validate()
    with pytest.raises(ValueError, match="mean_only"):
        InferenceSpec(wire_dtype="bf16", consensus="mean_only").validate()
    with pytest.raises(ValueError, match="exchanges nothing"):
        InferenceSpec(wire_dtype="bf16", consensus="none").validate()
    with pytest.raises(ValueError, match="conjugate_linreg"):
        InferenceSpec(wire_dtype="bf16", method="conjugate_linreg").validate()
    with pytest.raises(ValueError, match="history_dtype"):
        InferenceSpec(history_dtype="f64").validate()
    # history_dtype without a gossip topology is silently-dead config
    with pytest.raises(ValueError, match="history_dtype"):
        ExperimentSpec(
            topology=TopologySpec.complete(4),
            data=DataSpec(partition_params=dict(n_agents=4)),
            inference=InferenceSpec(history_dtype="bf16"),
        ).validate()
    # ... and a gossip clock without delay rejects it at engine build
    with pytest.raises(ValueError, match="delay"):
        build_session(_gossip_session_spec(history_dtype="bf16"))
    InferenceSpec(wire_dtype="bf16").validate()


def test_build_session_refuses_f16_where_tpu_runs_a_pallas_kernel(
    monkeypatch,
):
    """On a TPU backend an f16 wire is refused at build time wherever the
    consensus is a Pallas kernel (Mosaic cannot compile its f16 round
    trip); the XLA delayed event-gather still builds.  The backend is
    steered here: nothing is compiled or run."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(ValueError, match="f16.*Pallas"):
        build_session(_gossip_session_spec(wire="f16"))
    delayed = {"kind": "delayed",
               "inner": {"kind": "poisson", "rate": 0.8, "seed": 1},
               "latency": {"kind": "constant", "delay": 1}}
    s = build_session(_gossip_session_spec(wire="f16", clock=delayed))
    assert not s.engine.pallas_consensus


def test_gossip_engine_wire_f32_bitwise_and_bf16_runs():
    """Engine plumbing: wire_dtype="f32" session is bit-identical to the
    default; a bf16 session runs finite, reports its wire dtype in the
    telemetry, and tracks the f32 trajectory closely."""
    s_def = build_session(_gossip_session_spec())
    s_f32 = build_session(_gossip_session_spec(wire="f32"))
    s_bf = build_session(_gossip_session_spec(wire="bf16"))
    s_def.run()
    s_f32.run()
    hist = s_bf.run(eval_every=1)
    np.testing.assert_array_equal(
        np.asarray(s_def.posterior().mean), np.asarray(s_f32.posterior().mean)
    )
    np.testing.assert_array_equal(
        np.asarray(s_def.posterior().rho), np.asarray(s_f32.posterior().rho)
    )
    assert np.isfinite(hist[-1]["loss"])
    assert s_bf.evaluate()["engine"]["wire_dtype"] == "bf16"
    assert "wire_dtype" not in s_f32.evaluate()["engine"]
    np.testing.assert_allclose(
        np.asarray(s_bf.posterior().mean), np.asarray(s_f32.posterior().mean),
        rtol=0.1, atol=0.1,
    )
    assert s_bf.engine.n_traces == 1  # wire rounding adds no retrace


def test_simulated_engine_wire_f32_bitwise():
    """The synchronous SimulatedEngine consensus also routes the wire dtype
    (core.flat dispatch): f32 is bitwise the default."""
    def spec(wire):
        return ExperimentSpec(
            topology=TopologySpec(kind="bidirectional_ring", params={"n": 4}),
            data=DataSpec(
                dataset_params=dict(n_classes=3, dim=8, n_train_per_class=30),
                partition="iid", partition_params=dict(n_agents=4),
                batch_size=4, local_updates=2,
            ),
            inference=InferenceSpec(hidden=8, depth=1, lr=1e-2,
                                    wire_dtype=wire),
            run=RunSpec(n_rounds=2, seed=0),
        )

    s_def, s_bf = build_session(spec("f32")), build_session(spec("bf16"))
    s_def.run()
    s_bf.run()
    base = build_session(spec("f32"))
    base.run()
    np.testing.assert_array_equal(
        np.asarray(s_def.posterior().mean), np.asarray(base.posterior().mean)
    )
    # bf16 genuinely compresses (different bits) but stays close
    assert not np.array_equal(
        np.asarray(s_bf.posterior().mean), np.asarray(s_def.posterior().mean)
    )
    np.testing.assert_allclose(
        np.asarray(s_bf.posterior().mean), np.asarray(s_def.posterior().mean),
        rtol=0.1, atol=0.1,
    )


def test_bf16_history_ring_session_and_checkpoint(tmp_path):
    """The delayed engine's [K, N, P] ring can be bf16-resident
    (history_dtype): state leaves carry the narrow dtype (half the resident
    bytes), the run stays finite, and save/load resumes BIT-identically
    (the checkpoint round-trips extension dtypes by name)."""
    clock = {"kind": "delayed",
             "inner": {"kind": "poisson", "rate": 0.9, "seed": 2},
             "latency": {"kind": "constant", "delay": 2}}
    s = build_session(
        _gossip_session_spec(clock=clock, n_rounds=6, history_dtype="bf16")
    )
    assert s.state.hist_mean.dtype == jnp.bfloat16
    assert s.evaluate()["engine"]["history_dtype"] == "bf16"
    s.run(3)
    path = os.path.join(tmp_path, "bf16hist.ckpt")
    s.save(path)
    s2 = Session.load(path)
    assert s2.state.hist_mean.dtype == jnp.bfloat16
    s.run(3)
    s2.run(3)
    np.testing.assert_array_equal(
        np.asarray(s.posterior().mean), np.asarray(s2.posterior().mean)
    )
    np.testing.assert_array_equal(
        np.asarray(s.state.hist_mean), np.asarray(s2.state.hist_mean)
    )
    # f32 residency stays the default with unchanged leaf dtype
    s32 = build_session(_gossip_session_spec(clock=clock))
    assert s32.state.hist_mean.dtype == jnp.float32


# ---------------------------------------------------------------------------
# sharded wire exchange: real multi-device ppermute payload
# ---------------------------------------------------------------------------


_SHARD_PRELUDE = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
"""


@pytest.mark.slow
@pytest.mark.multidevice
def test_ppermute_window_wire_bitwise_vs_masked_multidevice():
    """Acceptance (8 virtual devices): the sharded window consensus with a
    compressed ppermute payload is BIT-identical to the dense masked kernel
    at the same wire dtype, for several shard counts and windows — and the
    f32 wire is bit-identical to the no-argument baseline."""
    from conftest import run_multidevice_subprocess

    run_multidevice_subprocess(_SHARD_PRELUDE + textwrap.dedent("""
    from repro.core.flat import (FlatLayout, FlatPosterior,
                                 consensus_flat_masked)
    from repro.core.graphs import bidirectional_ring_w
    from repro.gossip.clocks import PoissonClock
    from repro.launch.consensus_opt import consensus_ppermute_window

    n, p = 8, 200
    ks = jax.random.split(jax.random.key(5), 2)
    layout = FlatLayout.for_pytree({"w": jnp.zeros((p,))})
    posts = FlatPosterior(
        mean=jax.random.normal(ks[0], (n, p)) * 3.0,
        # moderate sigma so the f16 sweep's precisions stay in range
        rho=jax.random.normal(ks[1], (n, p)) * 0.5 - 1.0,
        layout=layout,
    )
    W_base = bidirectional_ring_w(n)
    clock = PoissonClock(W_base, rate=0.7, seed=3)
    for S in (2, 4, 8):
        mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:S]), ("agents",))
        for r in range(3):
            win = clock.window(r)
            for wire in (None, "f32", "bf16", "f16"):
                ref = consensus_flat_masked(
                    posts, jnp.asarray(win.w_eff, jnp.float32),
                    jnp.asarray(win.active), mode="xla", wire_dtype=wire)
                out = consensus_ppermute_window(
                    posts, win, mesh, "agents", wire_dtype=wire)
                assert bool(jnp.all(out.mean == ref.mean)), (S, r, wire)
                assert bool(jnp.all(out.rho == ref.rho)), (S, r, wire)
    print("OK")
    """))


@pytest.mark.slow
@pytest.mark.multidevice
def test_gossip_engine_ppermute_bf16_matches_masked_bf16():
    """Engine-level ladder rung: a sharded (ppermute) bf16-wire gossip run
    equals the dense masked bf16 run bit-identically over the 8-device
    agent mesh."""
    from conftest import run_multidevice_subprocess

    run_multidevice_subprocess(_SHARD_PRELUDE + textwrap.dedent("""
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
                                    consensus_impl=impl, wire_dtype="bf16"),
            run=RunSpec(n_rounds=3, seed=0),
        )

    s_m = build_session(spec("masked"))
    s_p = build_session(spec("ppermute"))
    s_m.run(); s_p.run()
    np.testing.assert_array_equal(np.asarray(s_m.posterior().mean),
                                  np.asarray(s_p.posterior().mean))
    np.testing.assert_array_equal(np.asarray(s_m.posterior().rho),
                                  np.asarray(s_p.posterior().rho))
    assert s_p.evaluate()["engine"]["wire_dtype"] == "bf16"
    print("OK")
    """))
