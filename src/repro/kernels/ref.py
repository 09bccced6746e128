"""Pure-jnp oracles for every Pallas kernel (the allclose references)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def sample_and_kl_ref(mu, rho, eps, mu_p, rho_p):
    """Reparameterized sample + closed-form Gaussian KL (see gauss_vi)."""
    sq = jax.nn.softplus(rho)
    sp = jax.nn.softplus(rho_p)
    theta = mu + sq * eps
    d = mu - mu_p
    kl = jnp.sum(
        jnp.log(sp / sq) + (jnp.square(sq) + jnp.square(d)) / (2.0 * jnp.square(sp)) - 0.5
    )
    return theta, kl


def attention_ref(q, k, v, *, causal=True, window=0):
    """Naive full-materialization attention.  q,k,v: [B,H,S,hd]."""
    b, h, s, hd = q.shape
    sk = k.shape[2]
    s_mat = jnp.einsum(
        "bhqd,bhkd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32)
    ) / jnp.sqrt(hd)
    q_idx = jnp.arange(s)[:, None]
    k_idx = jnp.arange(sk)[None, :]
    mask = jnp.ones((s, sk), bool)
    if causal:
        mask = mask & (k_idx <= q_idx)
    if window:
        mask = mask & (k_idx > q_idx - window)
    s_mat = jnp.where(mask[None, None], s_mat, -1e30)
    p = jax.nn.softmax(s_mat, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32)).astype(q.dtype)
