"""jit'd public wrappers around the Pallas kernels, with pytree plumbing and
interpret/TPU dispatch.

``on_tpu()`` decides the default execution mode: Pallas-compiled on TPU,
interpret (CPU-correctness) elsewhere.  All wrappers take ``interpret=None``
to mean "auto".

Flatten/unflatten here record per-leaf dtypes and cast through a common
fp32 compute dtype: ``jnp.concatenate`` on mixed-dtype leaves silently
promotes (e.g. f32+bf16 -> f32 but int leaves -> f32 with value change, and
bf16-only trees would stay bf16 while the kernels assume fp32), so the
round-trip now casts every leaf back to its recorded dtype (satellite fix
of ISSUE 1).  For the canonical flat runtime use ``core.flat.FlatLayout``,
which caches this layout once instead of rebuilding it per call.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.core.numerics import COMPUTE_DTYPE
from repro.kernels.dispatch import auto_interpret, on_tpu
from repro.kernels.consensus import (
    consensus_fused,
    consensus_fused_network,
    consensus_fused_sparse,
)
from repro.kernels.gauss_vi import sample_and_kl_fused

PyTree = Any


def _auto(interpret):
    return auto_interpret(interpret)


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


def consensus_posterior(posts, w_row: jax.Array, *, interpret: bool | None = None):
    """Fused eq. (6) over a whole posterior pytree with stacked neighbor axis.

    ``posts``: GaussianPosterior whose leaves are [N, ...].  Returns a
    GaussianPosterior without the leading axis (one agent's consensus).
    """
    from repro.core.posterior import GaussianPosterior

    n = w_row.shape[0]
    mean_leaves, treedef = jax.tree.flatten(posts.mean)
    rho_leaves = treedef.flatten_up_to(posts.rho)
    dtypes = [l.dtype for l in mean_leaves]
    mean_flat = jnp.concatenate(
        [l.reshape(n, -1).astype(COMPUTE_DTYPE) for l in mean_leaves], axis=1
    )
    rho_flat = jnp.concatenate(
        [l.reshape(n, -1).astype(COMPUTE_DTYPE) for l in rho_leaves], axis=1
    )
    mean_o, rho_o = consensus_fused(
        w_row, mean_flat, rho_flat, interpret=_auto(interpret)
    )
    shapes = [l.shape[1:] for l in mean_leaves]
    mean = _unflatten(mean_o, treedef, shapes, dtypes)
    rho = _unflatten(rho_o, treedef, shapes, dtypes)
    return GaussianPosterior(mean=mean, rho=rho)


def consensus_network(posts, W: jax.Array, *, interpret: bool | None = None):
    """Single fused network-wide eq. (6) (``consensus_fused_network``) for a
    ``core.flat.FlatPosterior``: one ``pallas_call`` over the whole [N, P]
    network posterior.  Prefer ``core.flat.consensus_flat`` (auto XLA/Pallas
    dispatch); this wrapper forces the Pallas kernel."""
    import dataclasses

    mean, rho = consensus_fused_network(
        W, posts.mean, posts.rho, interpret=_auto(interpret)
    )
    return dataclasses.replace(posts, mean=mean, rho=rho)


def consensus_network_sparse(
    posts, neighbors: jax.Array, weights: jax.Array, *, interpret: bool | None = None
):
    """Sparse-neighborhood variant of ``consensus_network`` (CSR-style
    tables from ``core.flat.neighbor_tables``)."""
    import dataclasses

    mean, rho = consensus_fused_sparse(
        neighbors, weights, posts.mean, posts.rho, interpret=_auto(interpret)
    )
    return dataclasses.replace(posts, mean=mean, rho=rho)


def sample_and_kl(post, prior, key: jax.Array, *, interpret: bool | None = None):
    """Fused reparameterized sample + KL over a whole posterior pytree.

    Returns (theta pytree, kl scalar)."""
    mu_flat, treedef, shapes, dtypes = _flatten(post.mean)
    rho_flat, _, _, _ = _flatten(post.rho)
    mu_p_flat, _, _, _ = _flatten(prior.mean)
    rho_p_flat, _, _, _ = _flatten(prior.rho)
    eps = jax.random.normal(key, mu_flat.shape, mu_flat.dtype)
    theta_flat, kl = sample_and_kl_fused(
        mu_flat, rho_flat, eps, mu_p_flat, rho_p_flat, interpret=_auto(interpret)
    )
    return _unflatten(theta_flat, treedef, shapes, dtypes), kl
