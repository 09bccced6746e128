"""Grouped matrix products on the TPU: JAX's megablox kernel.

``grouped_matmul`` computes, for each group ``i`` of consecutive rows of
``lhs`` (``group_sizes[i]`` rows, in order), ``lhs[rows_i] @ rhs[i]`` (or
``rhs[i]^T`` with ``transpose_rhs``), through the raw Pallas kernel of
``jax.experimental.pallas.ops.tpu.megablox.gmm``: a grid over (n tiles,
the row tiles the groups touch, k tiles), one expert's weight tile per
row tile, the [tm, tn] partial sums held in VMEM.  A row tile that two
groups share is computed once for each and stored under a row mask, so
a group that starts inside a tile costs a tile of its own
(``tile_fill``).  Groups with no rows cost nothing.  The raw kernel has
no VJP: callers write their backward products themselves, as
``models.moe`` does (megablox's own ``ops.gmm`` VJP also computes weight
gradients a frozen trunk does not need).

Precision: ``lhs`` and ``rhs`` enter in bfloat16, products accumulate in
float32 in VMEM, the output is bfloat16: what ``jax.lax.ragged_dot``
gives for the same arguments.

``TILINGS`` holds the tiles (tm, tk, tn), keyed by (k, n, transpose_rhs),
chosen on a TPU v5e for the routed experts of DeepSeek-V2-Lite (d 2,048,
expert width 1,408) at m = 196,608 rows (8 agents x 4,096 tokens x top-6),
each product timed alone against ``ragged_dot`` over group sizes as skewed
as the cell's router makes them (largest expert 10.7x the mean); the
sweep, ms per product (TPU v5e; tiles tm x tk x tn; 14-20 settings timed
a product, and 4-7 more, all at tm >= 512, refused by the compiler for
overrunning the scoped VMEM):

    product        ragged_dot  256 x full x full   best other
    gate/up x@w       21.13        6.62            7.26 (512x1024x1408)
    down h@w          14.58        6.69            6.89 (256x1408x1024)
    dh dy@w^T         21.85        6.65            7.31 (512x1024x1408)
    dx dg@w^T         15.59        6.69            6.90 (256x1408x1024)

Narrow tiles lose: tn = 128 takes 14-27 ms, tk = 512 7.9-14 ms.  A full tk
sums each output in one pass, and its outputs matched ``ragged_dot``'s
bit for bit.  At 1.134e12 FLOPs a product, 6.62 ms is
87 % of the v5e's 197 TFLOP/s.
"""
from __future__ import annotations

import importlib

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.dispatch import auto_interpret

# the package re-exports ops.gmm (with its VJP) under the submodule's name
_megablox = importlib.import_module(
    "jax.experimental.pallas.ops.tpu.megablox.gmm")

# (k, n, transpose_rhs) -> (tm, tk, tn); out [m, n] = lhs [m, k] @ rhs
TILINGS: dict[tuple[int, int, bool], tuple[int, int, int]] = {
    (2048, 1408, False): (256, 2048, 1408),  # gate, up: x @ w_gate
    (1408, 2048, False): (256, 1408, 2048),  # down: h @ w_down
    (2048, 1408, True): (256, 2048, 1408),  # dh = dy @ w_down^T
    (1408, 2048, True): (256, 1408, 2048),  # dx = dg @ w_gate^T + ...
}


def tiling_for(m: int, k: int, n: int, transpose_rhs: bool = False
               ) -> tuple[int, int, int] | None:
    """``TILINGS``' tiles for an [m, k] x [k, n] grouped product, or None
    when the table has none or m is not a multiple of tm."""
    tiles = TILINGS.get((k, n, transpose_rhs))
    if tiles is None or m % tiles[0]:
        return None
    return tiles


def grouped_matmul(lhs: jax.Array, rhs: jax.Array, group_sizes: jax.Array,
                   *, transpose_rhs: bool = False,
                   tiling: tuple[int, int, int] | None = None,
                   interpret: bool | None = None) -> jax.Array:
    """lhs [m, k] against rhs [G, k, n] (``transpose_rhs``: [G, n, k]),
    row group i (``group_sizes`` [G] int32, summing to m) against
    ``rhs[i]``; returns [m, n] bfloat16.  ``tiling`` (tm, tk, tn) defaults
    to ``tiling_for``'s."""
    m, k = lhs.shape
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    tiling = tiling or tiling_for(m, k, n, transpose_rhs)
    if tiling is None:
        raise ValueError(f"no tiling for a grouped [{m}, {k}] x [{k}, {n}] "
                         f"product (transpose_rhs={transpose_rhs})")
    return _megablox.gmm(
        lhs.astype(jnp.bfloat16), rhs.astype(jnp.bfloat16),
        group_sizes.astype(jnp.int32), preferred_element_type=jnp.bfloat16,
        tiling=tuple(tiling), transpose_rhs=transpose_rhs,
        interpret=auto_interpret(interpret))


def tile_fill(group_sizes, tm: int) -> float:
    """Real rows over the rows the kernel computes at row tile ``tm``: each
    non-empty group computes every tile from the one holding its first
    row to the one holding its last, so a tile that two groups share is
    computed twice.  Host arithmetic on the group sizes."""
    sizes = np.asarray(group_sizes, dtype=np.int64)
    ends = np.cumsum(sizes)
    starts = ends - sizes
    live = sizes > 0
    tiles = (-(-ends[live] // tm) - starts[live] // tm).sum()
    return float(sizes.sum() / (tiles * tm)) if tiles else 1.0
