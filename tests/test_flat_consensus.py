"""Property tests for the flat-buffer posterior + fused network consensus:
flat-fused (XLA and Pallas-interpret, dense and edge-list) must agree with the
``consensus_all_agents`` leaf-loop einsum reference to <= 1e-6 on ragged
mixed-shape pytrees, sparse W rows, and non-divisible P % BLOCK padding."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import restore_flat_posterior, save_flat_posterior
from repro.core.flat import (
    FlatLayout,
    FlatPosterior,
    consensus_flat,
    consensus_flat_segments,
    flat_posterior_from_pytree,
    init_flat_posterior,
    make_flat_nll,
)
from repro.core.graphs import (
    SparseGraph,
    bidirectional_ring_w,
    complete_w,
    star_w,
)
from repro.core.numerics import softplus, softplus_inv
from repro.core.posterior import (
    GaussianPosterior,
    consensus_all_agents,
    init_posterior,
)
from repro.kernels.consensus import consensus_fused_network


def _ragged_posts(n, seed=0, dtypes=None):
    """Deliberately ragged mixed-shape (optionally mixed-dtype) pytree with
    nested containers — scalars, odd 1-D, 2-D, 3-D leaves."""
    rng = np.random.default_rng(seed)
    shapes = {"s": (), "v": (17,), "m": (3, 5), "t": (2, 3, 7), "odd": (129,)}
    dtypes = dtypes or {k: jnp.float32 for k in shapes}
    mean = {
        k: jnp.asarray(rng.normal(size=(n,) + shp), dtypes[k])
        for k, shp in shapes.items()
    }
    rho = {
        k: jnp.asarray(rng.normal(size=(n,) + shp) * 0.3 - 0.5, dtypes[k])
        for k, shp in shapes.items()
    }
    # nest one branch to exercise non-trivial treedefs
    mean["nested"] = (mean.pop("t"), [mean.pop("odd")])
    rho["nested"] = (rho.pop("t"), [rho.pop("odd")])
    return GaussianPosterior(mean=mean, rho=rho)


def _assert_tree_close(a, b, atol=1e-6, rtol=1e-5):
    for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_allclose(
            np.asarray(la, np.float32), np.asarray(lb, np.float32),
            atol=atol, rtol=rtol,
        )


def test_flat_roundtrip_mixed_dtypes():
    posts = _ragged_posts(
        3, dtypes={"s": jnp.float32, "v": jnp.bfloat16, "m": jnp.float32,
                   "t": jnp.float16, "odd": jnp.float32},
    )
    flat = flat_posterior_from_pytree(posts, leading_axes=1)
    assert flat.mean.dtype == jnp.float32 and flat.mean.ndim == 2
    rt = flat.to_pytree()
    assert jax.tree.structure(rt.mean) == jax.tree.structure(posts.mean)
    for orig, back in zip(jax.tree.leaves(posts.mean), jax.tree.leaves(rt.mean)):
        assert orig.dtype == back.dtype  # no silent promotion
        np.testing.assert_allclose(
            np.asarray(orig, np.float32), np.asarray(back, np.float32), atol=1e-3
        )


@pytest.mark.parametrize("topology", ["complete", "ring", "star"])
@pytest.mark.parametrize("mode", ["xla", "interpret"])
def test_flat_consensus_matches_leaf_loop_reference(topology, mode):
    n = 6
    W = {
        "complete": complete_w(n),
        "ring": bidirectional_ring_w(n),
        "star": star_w(n - 1, a=0.4),
    }[topology]
    W = jnp.asarray(W, jnp.float32)
    posts = _ragged_posts(n, seed=topology.__hash__() % 97)
    flat = flat_posterior_from_pytree(posts, leading_axes=1)
    assert flat.layout.n_params % 128 != 0  # padding lanes ARE exercised
    ref = consensus_all_agents(posts, W)
    out = consensus_flat(flat, W, mode=mode, block=128).to_pytree()
    _assert_tree_close(out.mean, ref.mean)
    _assert_tree_close(out.rho, ref.rho)


@pytest.mark.parametrize("mode", ["xla", "interpret"])
def test_flat_sparse_consensus_skips_zero_rows(mode):
    """Edge-list path (XLA segment sum, or the row-gather kernel with
    ``slots`` entries a row) == the leaf-loop reference on a sparse W:
    zero-weight entries are no edges and contribute exactly nothing."""
    n = 8
    W = jnp.asarray(bidirectional_ring_w(n), jnp.float32)
    posts = _ragged_posts(n, seed=5)
    flat = flat_posterior_from_pytree(posts, leading_axes=1)
    g = SparseGraph.from_dense(np.asarray(W))
    assert g.max_in_degree == 3  # ring: self + 2 neighbors, NOT n
    dst, src, w = (jnp.asarray(a) for a in g.edge_arrays())
    ref = consensus_all_agents(posts, W)
    out = consensus_flat_segments(
        flat, dst, src, w, slots=g.max_in_degree, mode=mode, block=128
    ).to_pytree()
    _assert_tree_close(out.mean, ref.mean)
    _assert_tree_close(out.rho, ref.rho)


def test_consensus_identity_and_fixed_point_flat():
    n = 4
    posts = _ragged_posts(n, seed=11)
    flat = flat_posterior_from_pytree(posts, leading_axes=1)
    out = consensus_flat(flat, jnp.eye(n), mode="xla")
    np.testing.assert_allclose(out.mean, flat.mean, atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(out.rho, flat.rho, atol=1e-4, rtol=1e-4)
    # identical agents: any row-stochastic W is a fixed point
    same = FlatPosterior(
        mean=jnp.broadcast_to(flat.mean[:1], flat.mean.shape),
        rho=jnp.broadcast_to(flat.rho[:1], flat.rho.shape),
        layout=flat.layout,
    )
    W = jax.nn.softmax(jax.random.normal(jax.random.key(0), (n, n)), axis=1)
    out = consensus_flat(same, W, mode="xla")
    np.testing.assert_allclose(out.mean, same.mean, atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(out.rho, same.rho, atol=1e-4, rtol=1e-4)


def test_softplus_inv_extreme_sigma_regression():
    """Satellite regression: the shared stable softplus^-1 at tiny/huge
    sigma, and the fused kernel staying finite there."""
    tiny = jnp.asarray([1e-7, 1e-5, 1e-3], jnp.float32)
    huge = jnp.asarray([1e2, 1e4, 3e8], jnp.float32)
    for y in (tiny, huge):
        x = softplus_inv(y)
        assert np.all(np.isfinite(np.asarray(x)))
        np.testing.assert_allclose(np.asarray(softplus(x)), np.asarray(y), rtol=1e-5)
    # kernel round-trip with rho chosen so sigma spans tiny..huge
    n, p = 3, 256
    rho = jnp.stack([
        jnp.full((p,), softplus_inv(jnp.float32(1e-4))),
        jnp.full((p,), softplus_inv(jnp.float32(1.0))),
        jnp.full((p,), jnp.float32(1e4)),  # softplus(x) ~ x for huge x
    ])
    mean = jnp.ones((n, p))
    W = jnp.asarray(complete_w(n), jnp.float32)
    mo, ro = consensus_fused_network(W, mean, rho, block=128, interpret=True)
    assert np.all(np.isfinite(np.asarray(mo)))
    assert np.all(np.isfinite(np.asarray(ro)))


def test_softplus_inv_matches_float64_over_sigma_range():
    """The exp/log-only softplus^-1 (Mosaic has no expm1) against float64
    NumPy over y in [1e-6, 1e4], jitted as the kernels and references use
    it.  Mixed tolerance: the result crosses 0 at y = ln 2, where only an
    absolute bound means anything."""
    y = np.geomspace(1e-6, 1e4, 200_001).astype(np.float32)
    got = np.asarray(jax.jit(softplus_inv)(jnp.asarray(y)), np.float64)
    y64 = y.astype(np.float64)
    ref = y64 + np.log(-np.expm1(-y64))
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-6)


def test_flat_vi_round_and_dispatch():
    """End-to-end flat runtime: init_network + param_layout round
    steps under vmap, consensus_all_agents auto-dispatches on FlatPosterior."""
    from repro.core.simulated import init_network, make_round_fn
    from repro.optim import adam
    from repro.optim.schedules import constant_schedule

    n_agents, dim = 4, 8

    def init_params(key):
        k1, k2 = jax.random.split(key)
        return {
            "w": jax.random.normal(k1, (dim, 2)) * 0.1,
            "b": jnp.zeros((2,)),
        }

    def nll(theta, batch):
        logits = batch["x"] @ theta["w"] + theta["b"]
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, batch["y"][..., None], -1)[..., 0]
        return jnp.sum(logz - gold)

    opt = adam()
    state = init_network(jax.random.key(0), n_agents, init_params, opt)
    assert isinstance(state.posterior, FlatPosterior)
    layout = state.posterior.layout
    round_fn = jax.jit(
        make_round_fn(nll, opt, constant_schedule(1e-2), param_layout=layout)
    )
    rng = np.random.default_rng(0)
    batches = {
        "x": jnp.asarray(rng.normal(size=(n_agents, 2, 6, dim)), jnp.float32),
        "y": jnp.asarray(rng.integers(0, 2, size=(n_agents, 2, 6)), jnp.int32),
    }
    W = jnp.asarray(bidirectional_ring_w(n_agents), jnp.float32)
    losses = None
    for r in range(3):
        state, losses, _ = round_fn(state, batches, W, jax.random.key(r + 1))
    assert isinstance(state.posterior, FlatPosterior)
    assert np.all(np.isfinite(np.asarray(losses)))
    assert int(state.round) == 3
    # the consensus inside the round used the flat dispatch; check the
    # explicit dispatch path agrees with the leaf-loop reference too
    ref = consensus_all_agents(state.posterior.to_pytree(), W)
    out = consensus_all_agents(state.posterior, W).to_pytree()
    _assert_tree_close(out.mean, ref.mean, atol=1e-5)


def test_flat_checkpoint_roundtrip(tmp_path):
    posts = _ragged_posts(5, seed=2)
    flat = flat_posterior_from_pytree(posts, leading_axes=1)
    path = os.path.join(tmp_path, "flat.ckpt")
    save_flat_posterior(path, flat)
    back = restore_flat_posterior(path)
    assert back.layout == flat.layout  # offsets/shapes/dtypes/treedef intact
    np.testing.assert_array_equal(np.asarray(back.mean), np.asarray(flat.mean))
    np.testing.assert_array_equal(np.asarray(back.rho), np.asarray(flat.rho))
    # restored posterior still unflattens to the original structure
    assert jax.tree.structure(back.to_pytree().mean) == jax.tree.structure(posts.mean)


def test_ops_flatten_preserves_mixed_dtypes():
    """Satellite regression: ops._flatten/_unflatten round-trips dtypes
    (jnp.concatenate used to silently promote mixed-dtype leaves)."""
    from repro.kernels.ops import _flatten, _unflatten

    tree = {
        "a": jnp.ones((3, 2), jnp.bfloat16),
        "b": jnp.arange(4, dtype=jnp.float32),
        "c": jnp.ones((2,), jnp.float16),
    }
    flat, treedef, shapes, dtypes = _flatten(tree)
    assert flat.dtype == jnp.float32
    back = _unflatten(flat, treedef, shapes, dtypes)
    for k in tree:
        assert back[k].dtype == tree[k].dtype
        np.testing.assert_allclose(
            np.asarray(back[k], np.float32), np.asarray(tree[k], np.float32)
        )


def test_make_flat_nll_boundary():
    params = {"w": jnp.ones((3, 4)), "b": jnp.zeros((4,))}
    layout = FlatLayout.for_pytree(params)
    flat_post = init_flat_posterior(params, init_sigma=0.1)

    def nll(theta, batch):
        assert set(theta) == {"w", "b"}  # model sees a pytree, not the buffer
        return jnp.sum(theta["w"]) + jnp.sum(theta["b"]) + batch

    fnll = make_flat_nll(nll, layout)
    val = fnll(flat_post.mean, 0.0)
    np.testing.assert_allclose(float(val), 12.0, atol=1e-5)


def test_bench_harness_smoke(tmp_path, capsys):
    """CI/tooling satellite: the `bench` subcommand runs the consensus sweep
    quickly (interpret-mode probe included) and writes valid JSON."""
    import json
    import sys

    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    from benchmarks import run as bench_run

    out = os.path.join(tmp_path, "BENCH_consensus.json")
    bench_run.main(["bench", "--json-out", out])
    doc = json.load(open(out))
    assert doc["benchmark"] == "consensus_eq6" and doc["quick"]
    rec = doc["results"][0]
    assert rec["us"]["flat_fused"] > 0 and rec["us"]["leaf_loop"] > 0
    assert rec["roofline"]["model_speedup_fused_vs_leaf_loop"] >= 3.0
    for err in rec["interpret_max_err"].values():
        assert err < 1e-5
