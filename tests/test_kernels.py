"""Per-kernel allclose sweeps (shapes x dtypes) against the XLA references
(``core.flat``) and the ref.py oracles, interpret=True on CPU."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.flat import consensus_flat_reference
from repro.core.posterior import init_posterior
from repro.kernels import ref
from repro.kernels.consensus import (
    consensus_fused_masked,
    consensus_fused_network,
)
from repro.kernels.flash_attention import flash_attention
from repro.kernels.gauss_vi import sample_and_kl_fused
from repro.kernels.ops import sample_and_kl


@pytest.mark.parametrize("kernel", ["network", "masked"])
@pytest.mark.parametrize("n", [1, 3, 9, 16])
@pytest.mark.parametrize("p", [17, 2048, 5000])
def test_consensus_kernel_shapes(n, p, kernel):
    """The dense network kernel, and the masked window kernel under a mixed
    activity mask, against the XLA reference at ragged shapes."""
    ks = jax.random.split(jax.random.key(p * 31 + n), 3)
    W = jax.nn.softmax(jax.random.normal(ks[0], (n, n)), axis=1)
    mean = jax.random.normal(ks[1], (n, p))
    rho = jax.random.normal(ks[2], (n, p)) * 0.5 - 1.0
    if kernel == "network":
        active = None
        mo, ro = consensus_fused_network(W, mean, rho, block=1024)
    else:
        active = jnp.arange(n) % 3 != 1  # agents 1, 4, 7, ... sit out
        mo, ro = consensus_fused_masked(W, active, mean, rho, block=1024)
    mr, rr = consensus_flat_reference(mean, rho, W, active=active)
    np.testing.assert_allclose(mo, mr, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(ro, rr, rtol=1e-5, atol=1e-5)
    if active is not None:  # idle rows pass through bitwise
        idle = ~np.asarray(active)
        np.testing.assert_array_equal(np.asarray(mo)[idle],
                                      np.asarray(mean)[idle])
        np.testing.assert_array_equal(np.asarray(ro)[idle],
                                      np.asarray(rho)[idle])


def test_consensus_kernel_sparse_weights():
    """Zero-weight neighbors (sparse topologies) contribute nothing: the
    network kernel on a W whose last two columns are zero equals the
    reference over the first two agents alone."""
    n, p = 4, 300
    ks = jax.random.split(jax.random.key(0), 2)
    mean = jax.random.normal(ks[0], (n, p))
    rho = jax.random.normal(ks[1], (n, p)) * 0.3
    W = jnp.asarray([[0.5, 0.5, 0.0, 0.0],
                     [0.3, 0.7, 0.0, 0.0],
                     [0.5, 0.5, 0.0, 0.0],
                     [0.2, 0.8, 0.0, 0.0]])
    mo, ro = consensus_fused_network(W, mean, rho)
    mr, rr = consensus_flat_reference(mean[:2], rho[:2], W[:, :2])
    np.testing.assert_allclose(mo, mr, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(ro, rr, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("p", [5, 1000, 4096, 10000])
def test_gauss_vi_kernel(p):
    ks = jax.random.split(jax.random.key(p), 5)
    mu = jax.random.normal(ks[0], (p,))
    rho = jax.random.normal(ks[1], (p,)) * 0.3 - 1.0
    eps = jax.random.normal(ks[2], (p,))
    mu_p = jax.random.normal(ks[3], (p,)) * 0.1
    rho_p = jax.random.normal(ks[4], (p,)) * 0.1
    th, kl = sample_and_kl_fused(mu, rho, eps, mu_p, rho_p, block=512)
    thr, klr = ref.sample_and_kl_ref(mu, rho, eps, mu_p, rho_p)
    np.testing.assert_allclose(th, thr, rtol=1e-5, atol=1e-6)
    assert np.isclose(float(kl), float(klr), rtol=1e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "s,bq,bk,causal,window",
    [
        (128, 64, 64, True, 0),
        (128, 128, 64, False, 0),
        (256, 64, 64, True, 100),
        (256, 128, 128, True, 0),
        (64, 64, 64, True, 16),
    ],
)
def test_flash_attention_sweep(dtype, s, bq, bk, causal, window):
    ks = jax.random.split(jax.random.key(s + bq), 3)
    hd = 64
    q = jax.random.normal(ks[0], (2, 2, s, hd)).astype(dtype)
    k = jax.random.normal(ks[1], (2, 2, s, hd)).astype(dtype)
    v = jax.random.normal(ks[2], (2, 2, s, hd)).astype(dtype)
    o = flash_attention(q, k, v, causal=causal, window=window, block_q=bq, block_k=bk)
    r = ref.attention_ref(q, k, v, causal=causal, window=window)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(
        np.asarray(o, np.float32), np.asarray(r, np.float32), atol=tol, rtol=tol
    )


def test_flash_attention_matches_chunked_model_path():
    """The Pallas kernel and the model's pure-JAX chunked path agree."""
    from repro.models.attention import chunked_attention

    ks = jax.random.split(jax.random.key(7), 3)
    b, h, s, hd = 2, 3, 128, 32
    q = jax.random.normal(ks[0], (b, h, s, hd))
    k = jax.random.normal(ks[1], (b, h, s, hd))
    v = jax.random.normal(ks[2], (b, h, s, hd))
    o_pallas = flash_attention(q, k, v, causal=True, block_q=64, block_k=64)
    o_chunked = chunked_attention(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3),
        causal=True, chunk_size=64,
    ).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(o_pallas, o_chunked, atol=1e-5, rtol=1e-5)


def test_ops_sample_and_kl_pytree():
    params = {"w": jnp.zeros((10, 3)), "b": jnp.zeros((4,))}
    post = init_posterior(
        jax.tree.map(lambda p: p + 0.3, params), init_sigma=0.2
    )
    prior = init_posterior(params, init_sigma=0.1)
    theta, kl = sample_and_kl(post, prior, jax.random.key(0), interpret=True)
    from repro.core.posterior import kl_gaussian

    assert jax.tree.structure(theta) == jax.tree.structure(params)
    assert np.isclose(float(kl), float(kl_gaussian(post, prior)), rtol=1e-4)
