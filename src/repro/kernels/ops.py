"""Pytree wrapper around the fused sample-and-KL Pallas kernel
(``kernels.gauss_vi``), with interpret/TPU dispatch: ``interpret=None``
means Pallas-compiled on TPU, interpreted (CPU-correctness) elsewhere.

Flatten/unflatten here record per-leaf dtypes and cast through a common
fp32 compute dtype: ``jnp.concatenate`` on mixed-dtype leaves silently
promotes (e.g. f32+bf16 -> f32 but int leaves -> f32 with value change, and
bf16-only trees would stay bf16 while the kernels assume fp32), so the
round-trip casts every leaf back to its recorded dtype.  For the canonical
flat runtime use ``core.flat.FlatLayout``, which caches this layout once
instead of rebuilding it per call.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.core.numerics import COMPUTE_DTYPE
from repro.kernels.dispatch import auto_interpret
from repro.kernels.gauss_vi import sample_and_kl_fused

PyTree = Any


def _flatten(tree: PyTree) -> tuple[jax.Array, Any, list, list]:
    """Flatten to a contiguous fp32 vector, recording shapes AND dtypes so
    ``_unflatten`` restores mixed-dtype trees exactly (no silent promotion)."""
    leaves, treedef = jax.tree.flatten(tree)
    flat = jnp.concatenate([l.reshape(-1).astype(COMPUTE_DTYPE) for l in leaves])
    shapes = [l.shape for l in leaves]
    dtypes = [l.dtype for l in leaves]
    return flat, treedef, shapes, dtypes


def _unflatten(flat: jax.Array, treedef, shapes, dtypes) -> PyTree:
    out, off = [], 0
    for shp, dt in zip(shapes, dtypes):
        n = 1
        for d in shp:
            n *= d
        out.append(flat[off : off + n].reshape(shp).astype(dt))
        off += n
    return jax.tree.unflatten(treedef, out)


def sample_and_kl(post, prior, key: jax.Array, *, interpret: bool | None = None):
    """Fused reparameterized sample + KL over a whole posterior pytree.

    Returns (theta pytree, kl scalar)."""
    mu_flat, treedef, shapes, dtypes = _flatten(post.mean)
    rho_flat, _, _, _ = _flatten(post.rho)
    mu_p_flat, _, _, _ = _flatten(prior.mean)
    rho_p_flat, _, _, _ = _flatten(prior.rho)
    eps = jax.random.normal(key, mu_flat.shape, mu_flat.dtype)
    theta_flat, kl = sample_and_kl_fused(
        mu_flat, rho_flat, eps, mu_p_flat, rho_p_flat, interpret=auto_interpret(interpret)
    )
    return _unflatten(theta_flat, treedef, shapes, dtypes), kl
