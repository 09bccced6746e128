"""Basic neural modules (functional, dict-of-arrays params).

All weights are stored in ``param_dtype`` (fp32 — the Bayesian posterior
needs fp32 means/rhos) and cast to the compute dtype inside ``apply``.
Initializers return UNSTACKED per-layer params; the transformer assembly
stacks them over periods for scan.
"""
from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

PyTree = Any


def truncated_normal_init(key, shape, scale, dtype=jnp.float32):
    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    std = scale / jnp.sqrt(fan_in)
    return std * jax.random.truncated_normal(key, -2.0, 2.0, shape, dtype)


def linear_init(key, d_in: int, d_out: int, dtype=jnp.float32):
    return {"w": truncated_normal_init(key, (d_in, d_out), 1.0, dtype)}


def linear(params, x, dtype):
    return x @ params["w"].astype(dtype)


def embed_init(key, vocab: int, d_model: int, dtype=jnp.float32):
    return {"emb": jax.random.normal(key, (vocab, d_model), dtype) * 0.02}


def embed(params, tokens, dtype):
    return params["emb"].astype(dtype)[tokens]


def unembed(params, x, dtype):
    # logits in fp32 for a stable softmax-xent
    return (x @ params["emb"].astype(dtype).T).astype(jnp.float32)


def rmsnorm_init(d: int, dtype=jnp.float32):
    return {"scale": jnp.ones((d,), dtype)}


def rmsnorm(params, x, eps: float = 1e-6):
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    out = x32 * jax.lax.rsqrt(var + eps)
    return (out * params["scale"].astype(jnp.float32)).astype(dtype)


def yarn_inv_freq(dim: int, theta: float, scaling) -> np.ndarray:
    """YaRN's per-pair inverse frequencies [dim // 2] (arXiv:2309.00071, as
    DeepSeek-V2 computes them): the plain RoPE frequency below the
    correction range (``beta_fast`` rotations over the original context),
    the frequency over ``factor`` above it (``beta_slow``), a linear ramp
    between."""
    base = theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    extra, inter = 1.0 / base, 1.0 / (scaling.factor * base)
    orig = scaling.original_max_position_embeddings

    def corr_dim(rotations):
        return (dim * math.log(orig / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(corr_dim(scaling.beta_fast)), 0)
    high = min(math.ceil(corr_dim(scaling.beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    return inter * ramp + extra * (1.0 - ramp)


def rope_scale(scaling) -> float:
    """YaRN's cos/sin magnitude: mscale(factor, mscale) over
    mscale(factor, mscale_all_dim) (1 for DeepSeek-V2, where they agree)."""
    get = scaling.get_mscale
    return (get(scaling.factor, scaling.mscale)
            / get(scaling.factor, scaling.mscale_all_dim))


def rope(x: jax.Array, positions: jax.Array, theta: float,
         inv_freq=None, scale: float = 1.0) -> jax.Array:
    """Rotary position embedding.  x: [..., S, H, hd]; positions: [..., S].
    ``inv_freq`` [hd // 2] replaces theta's plain frequencies (YaRN);
    ``scale`` multiplies cos and sin."""
    hd = x.shape[-1]
    half = hd // 2
    if inv_freq is None:
        freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    else:
        freqs = jnp.asarray(inv_freq, jnp.float32)
    angles = positions[..., None].astype(jnp.float32) * freqs  # [..., S, half]
    cos = jnp.cos(angles)[..., None, :]  # [..., S, 1, half]
    sin = jnp.sin(angles)[..., None, :]
    if scale != 1.0:
        cos, sin = cos * scale, sin * scale
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    )
    return out.astype(x.dtype)


def swiglu_init(key, d_model: int, d_ff: int, dtype=jnp.float32):
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "w_gate": truncated_normal_init(k1, (d_model, d_ff), 1.0, dtype),
        "w_up": truncated_normal_init(k2, (d_model, d_ff), 1.0, dtype),
        "w_down": truncated_normal_init(k3, (d_ff, d_model), 1.0, dtype),
    }


def swiglu(params, x, dtype):
    g = x @ params["w_gate"].astype(dtype)
    u = x @ params["w_up"].astype(dtype)
    return (jax.nn.silu(g) * u) @ params["w_down"].astype(dtype)


def softmax_xent(logits: jax.Array, targets: jax.Array) -> jax.Array:
    """Total (summed) cross-entropy; logits [..., V], targets [...] int."""
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return jnp.sum(logz - gold)
