"""Pallas TPU kernels: fused precision-weighted posterior consensus (eq. 6).

Three kernels, all computing

    prec_j   = softplus(rho_j)^-2
    prec_out = sum_j w_j prec_j
    mean_out = sum_j w_j prec_j mean_j / prec_out
    rho_out  = softplus^-1(prec_out^-1/2)

* ``consensus_fused``          — one agent, stacked neighbor posteriors.
* ``consensus_fused_network``  — ALL agents in one ``pallas_call`` over the
  flat network posterior (mean, rho: ``[N, P]``) with the full row-stochastic
  ``W [N, N]`` resident in VMEM.  Grid ``(P // BLOCK,)``: each program loads
  one ``[N, BLOCK]`` column tile of mean and rho ONCE and produces the
  consensus rows for every agent via an MXU matmul ``W @ prec`` — a single
  HBM pass over the network posterior per round, vs (leaves x agents x ~6)
  elementwise round-trips for the unfused leaf-loop einsum.
* ``consensus_fused_sparse``   — CSR-style neighbor-list variant for sparse
  topologies (ring/grid/star): grid ``(N, P // BLOCK, D)`` with the neighbor
  ids scalar-prefetched so each agent reads only its deg(i) <= D neighbor
  tiles instead of all N rows.
* ``consensus_fused_masked``   — the gossip event-window form (repro.gossip):
  the network kernel plus a per-agent activity mask.  ACTIVE rows run the
  identical MXU math as ``consensus_fused_network`` (bitwise: the all-active
  window reproduces the synchronous kernel exactly); INACTIVE rows pass
  their (mean, rho) through UNTOUCHED — no softplus/softplus^-1 round trip,
  so an idle agent's posterior is bit-stable across any number of windows.
* ``consensus_fused_masked_sparse`` — CSR + activity mask: active agents
  read only their deg(i) fired-neighbor tiles, inactive agents copy their
  own row (the self-padded tables guarantee the last gathered tile IS the
  agent's own row), giving HBM traffic proportional to the window's
  active-edge fraction (``launch.costmodel.gossip_window_roofline``).

The padded neighbor tables of a static topology come from THE one CSR
construction — ``core.graphs.SparseGraph.neighbor_tables()``
(``core.flat.neighbor_tables`` is its dense-W bridge) — so the kernel view
of a topology can never disagree with the graph layer's.

Edge-native gossip windows (``core.flat.consensus_flat_segments``, [E]
dst/src/weight lists, no [N, N] anywhere) run one of two executions:

* on TPU, ``consensus_fused_masked_sparse`` as a destination-major row
  GATHER: ``core.flat.gather_tables`` sorts the window's edges by
  destination in-graph into [N, D] tables (D = the base graph's max
  in-degree + 1, static), and each agent reads its fired sources' rows and
  its own, accumulates in fp32 VMEM and writes its row once.  Nothing is
  scattered.  XLA on TPU runs a data-dependent scatter-add one update at a
  time: the ws512 window's blocked segment sum (43 lane blocks x 3,584
  edge rows) took 104.6 ms of a 222 ms window on a v5e, 25x its HBM floor;
  the gather reads each needed row once a window.
* elsewhere — off TPU (CPU, the tests), for tables too large for the
  scalar memory (N x D > ``SPARSE_TABLE_ENTRIES``, e.g. the N = 10^4
  sweeps), or an f16 wire — the XLA blocked segment sum.

Flat-buffer layout contract (shared with ``core.flat.FlatPosterior``):
  * axis 0 is the agent axis (N rows), axis 1 the flattened parameter axis
    (P fp32 lanes, leaf-major in layout order);
  * the caller's buffers are UNPADDED; kernels pad the lane dim up to a
    BLOCK multiple internally (mean pads 0.0, rho pads 1.0 so pad lanes keep
    finite precision — softplus(1.0) ~ 1.31, so the pad precision ~0.58
    stays finite and exactly representable under EVERY wire dtype,
    including f16's narrow exponent range) and slice the pad back off
    before returning;
  * keep BLOCK a multiple of 128 (TPU lane width); the last dim rides the
    lane dim, agents/neighbors ride sublanes.  The dense kernels' default
    BLOCK shrinks with N (``lane_block``) so the [N, BLOCK] tiles and the
    resident W fit the scoped VMEM; the row-gathering sparse kernels tile
    the lane-dense view [N, P/128, 128] (``_sparse_call``), a whole row a
    tile where it fits (``sparse_block``);
  * ``wire_dtype="f16"`` is refused by the compiled kernels: Mosaic cannot
    lower its round trip on TPU v5e (interpret mode still runs it).

Wire-dtype compression (ROADMAP "Wire precision"): every kernel takes a
static ``wire_dtype`` (default fp32).  The exchanged sufficient statistics
(prec, prec*mu) are rounded through the wire dtype AT THE EXCHANGE BOUNDARY
— immediately before the cross-agent contraction — and the contraction
itself ACCUMULATES IN FP32 (``preferred_element_type``).  ``wire_dtype=
jnp.float32`` is a structural no-op: ``core.numerics.wire_roundtrip``
returns its input unchanged, so the f32 kernels are BITWISE identical to
the pre-wire ones (pinned by tests/test_wire_dtype.py).

Unfused, eq. (6) is ~6 elementwise HBM round-trips over tensors the size of
the model; the consensus step is purely memory-bound, so fusing the whole
network into one pass is the entire game (see launch.costmodel
.consensus_roofline for the analytic pass counts the benchmark reports).

``interpret=None`` on every entry point means auto: Pallas-compiled on TPU,
interpreter (CPU-correctness mode) elsewhere — callers on TPU no longer
silently run the interpreter (satellite fix of ISSUE 1).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.numerics import (
    EXCHANGE_PRECISION,
    canonical_wire_dtype,
    softplus_inv,
    wire_roundtrip,
)
from repro.kernels.dispatch import auto_interpret as _auto_interpret

DEFAULT_BLOCK = 2048
LANES = 128  # TPU lane width: the sparse kernels view [N, P] as [N, P/128, 128]

# Default scoped-VMEM limit Mosaic gives one kernel on a TPU v5e, and the
# number of fp32 [N, block] tiles one grid step of the dense kernels keeps
# live in it: (mean, rho) in and out, double-buffered, plus the softplus /
# matmul temporaries.  A described-v5e compile at N = 256 measured 22.84 MiB
# of scoped VMEM at block 2048, i.e. ~11 tiles beside the resident W.
_VMEM_LIMIT = 16 * 2**20
_LIVE_TILES = 12
# The row-gathering sparse kernels' fp32 tiles per grid step: the gathered
# (mean, rho) and the written (mean, rho), double-buffered, plus the two
# accumulators.
_SPARSE_LIVE_TILES = 10
# Largest N x D whose scalar-prefetched tables ([N * D] int32 ids and fp32
# weights, [N] int32 mask) fit half the 1 MiB SMEM of a TPU v5e; a
# described-v5e compile refuses 2-D [4096, 10] tables (padded to 128 words
# a row, 2 MiB each).
SPARSE_TABLE_ENTRIES = 1 << 16


def lane_block(n: int, resident_bytes: int = 0) -> int:
    """The widest lane block (a multiple of 128, at most DEFAULT_BLOCK) whose
    ``_LIVE_TILES`` fp32 [n, block] tiles plus the double-buffered resident
    operands (``resident_bytes``, e.g. the [N, N] W) fit three quarters of
    the scoped VMEM limit.  Floors at one lane group: an N whose resident W
    alone overflows VMEM is refused by the compiler, not here."""
    room = _VMEM_LIMIT * 3 // 4 - 2 * resident_bytes
    fit = room // (_LIVE_TILES * 4 * n) // LANES * LANES
    return max(LANES, min(DEFAULT_BLOCK, fit))


def _wire_for(wire_dtype, interpret: bool):
    """Canonical wire dtype, refusing f16 for a compiled kernel: Mosaic cannot
    legalize the f16 round trip (``tpu.pack_subelements``) on TPU v5e."""
    wire_dtype = canonical_wire_dtype(wire_dtype)
    if not interpret and wire_dtype == jnp.float16:
        raise ValueError(
            "wire_dtype='f16' does not compile in the Pallas TPU consensus "
            "kernels (Mosaic cannot legalize the f16 round trip, "
            "tpu.pack_subelements); use 'bf16' or 'f32'"
        )
    return wire_dtype


def _pad_lanes(mean, rho, block):
    """Pad the lane (last) dim to a BLOCK multiple.  rho pads with 1.0 so the
    pad lanes keep a finite sigma (inf precision would poison the row sums)."""
    p = mean.shape[-1]
    pad = (-p) % block
    if pad:
        widths = ((0, 0),) * (mean.ndim - 1) + ((0, pad),)
        mean = jnp.pad(mean, widths)
        rho = jnp.pad(rho, widths, constant_values=1.0)
    return mean, rho, p + pad


def _consensus_kernel(w_ref, mean_ref, rho_ref, mean_out_ref, rho_out_ref, *,
                      wire_dtype):
    w = w_ref[...]  # [N, 1]
    mean = mean_ref[...]  # [N, BLOCK]
    rho = rho_ref[...]  # [N, BLOCK]
    sigma = jax.nn.softplus(rho)
    prec = 1.0 / (sigma * sigma)
    if wire_dtype == jnp.float32:
        # pre-wire op order, verbatim — f32 stays bitwise identical
        wp = w * prec  # [N, BLOCK]
        prec_out = jnp.sum(wp, axis=0)  # [BLOCK]
        mean_out = jnp.sum(wp * mean, axis=0) / prec_out
    else:
        # exchange boundary: round (prec, prec*mu), accumulate fp32
        prec_w = wire_roundtrip(prec, wire_dtype)
        pm_w = wire_roundtrip(prec * mean, wire_dtype)
        prec_out = jnp.sum(w * prec_w, axis=0)
        mean_out = jnp.sum(w * pm_w, axis=0) / prec_out
    rho_out = softplus_inv(jax.lax.rsqrt(prec_out))
    mean_out_ref[...] = mean_out[None, :]
    rho_out_ref[...] = rho_out[None, :]


@functools.partial(jax.jit, static_argnames=("block", "interpret", "wire_dtype"))
def consensus_fused(
    w_row: jax.Array,  # [N]
    mean_stack: jax.Array,  # [N, P]
    rho_stack: jax.Array,  # [N, P]
    *,
    block: int | None = None,
    interpret: bool | None = None,
    wire_dtype=None,
) -> tuple[jax.Array, jax.Array]:
    """Fused consensus over a flat parameter block.  Returns (mean, rho) [P].

    ``interpret=None`` auto-dispatches (compiled on TPU, interpreter
    elsewhere); pass an explicit bool to force either mode.  ``wire_dtype``
    rounds (prec, prec*mu) through the wire dtype at the exchange boundary
    (module docstring); ``None``/f32 is the bitwise-identical uncompressed
    path.
    """
    interpret = _auto_interpret(interpret)
    wire_dtype = _wire_for(wire_dtype, interpret)
    n, p = mean_stack.shape
    block = lane_block(n) if block is None else block
    mean_stack, rho_stack, pp = _pad_lanes(mean_stack, rho_stack, block)
    grid = (pp // block,)
    mean_out, rho_out = pl.pallas_call(
        functools.partial(_consensus_kernel, wire_dtype=wire_dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((n, 1), lambda i: (0, 0)),  # w broadcast to all tiles
            pl.BlockSpec((n, block), lambda i: (0, i)),
            pl.BlockSpec((n, block), lambda i: (0, i)),
        ],
        out_specs=[
            pl.BlockSpec((1, block), lambda i: (0, i)),
            pl.BlockSpec((1, block), lambda i: (0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, pp), mean_stack.dtype),
            jax.ShapeDtypeStruct((1, pp), rho_stack.dtype),
        ],
        interpret=interpret,
    )(w_row[:, None], mean_stack, rho_stack)
    return mean_out[0, :p], rho_out[0, :p]


def _consensus_network_kernel(w_ref, mean_ref, rho_ref, mean_out_ref,
                              rho_out_ref, *, wire_dtype):
    w = w_ref[...]  # [N, N], resident in VMEM for every tile
    mean = mean_ref[...]  # [N, BLOCK]
    rho = rho_ref[...]  # [N, BLOCK]
    sigma = jax.nn.softplus(rho)
    prec = 1.0 / (sigma * sigma)
    # exchange boundary: every agent's (prec, prec*mu) contribution crosses
    # through the wire dtype (structural no-op for f32)
    prec_x = wire_roundtrip(prec, wire_dtype)
    pm_x = wire_roundtrip(prec * mean, wire_dtype)
    # new_prec[i] = sum_j W[i,j] prec[j]: one MXU matmul covers every agent,
    # so each [N, BLOCK] column tile is read from HBM exactly once; the
    # contraction accumulates fp32 whatever the wire dtype.
    new_prec = jnp.dot(w, prec_x, precision=EXCHANGE_PRECISION,
                       preferred_element_type=jnp.float32)
    new_pm = jnp.dot(w, pm_x, precision=EXCHANGE_PRECISION,
                     preferred_element_type=jnp.float32)
    mean_out_ref[...] = new_pm / new_prec
    rho_out_ref[...] = softplus_inv(jax.lax.rsqrt(new_prec))


@functools.partial(jax.jit, static_argnames=("block", "interpret", "wire_dtype"))
def consensus_fused_network(
    W: jax.Array,  # [N, N] row-stochastic
    mean: jax.Array,  # [N, P] flat network posterior means
    rho: jax.Array,  # [N, P]
    *,
    block: int | None = None,
    interpret: bool | None = None,
    wire_dtype=None,
) -> tuple[jax.Array, jax.Array]:
    """Eq. (6) for the WHOLE network in one ``pallas_call``.

    Returns (mean, rho), both [N, P].  One HBM pass: grid ``(P // BLOCK,)``,
    W stays in VMEM, each column tile of (mean, rho) is streamed through
    VMEM once and the per-agent reduction runs on the MXU.  ``wire_dtype``
    rounds (prec, prec*mu) at the exchange boundary (accumulate fp32);
    f32/None is bitwise the uncompressed kernel.
    """
    interpret = _auto_interpret(interpret)
    wire_dtype = _wire_for(wire_dtype, interpret)
    n, p = mean.shape
    block = lane_block(n, 4 * n * n) if block is None else block
    mean, rho, pp = _pad_lanes(mean, rho, block)
    grid = (pp // block,)
    mean_out, rho_out = pl.pallas_call(
        functools.partial(_consensus_network_kernel, wire_dtype=wire_dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((n, n), lambda i: (0, 0)),  # W resident across tiles
            pl.BlockSpec((n, block), lambda i: (0, i)),
            pl.BlockSpec((n, block), lambda i: (0, i)),
        ],
        out_specs=[
            pl.BlockSpec((n, block), lambda i: (0, i)),
            pl.BlockSpec((n, block), lambda i: (0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, pp), mean.dtype),
            jax.ShapeDtypeStruct((n, pp), rho.dtype),
        ],
        interpret=interpret,
    )(W.astype(jnp.float32), mean, rho)
    return mean_out[:, :p], rho_out[:, :p]


def _consensus_masked_kernel(
    w_ref, act_ref, mean_ref, rho_ref, mean_out_ref, rho_out_ref, *, wire_dtype
):
    w = w_ref[...]  # [N, N] effective window W-tilde, resident in VMEM
    act = act_ref[...]  # [N, 1] activity mask (1.0 = merges this window)
    mean = mean_ref[...]  # [N, BLOCK]
    rho = rho_ref[...]  # [N, BLOCK]
    sigma = jax.nn.softplus(rho)
    prec = 1.0 / (sigma * sigma)
    # identical op sequence to _consensus_network_kernel (same exchange-
    # boundary rounding) -> active rows are bitwise-equal to the synchronous
    # fused kernel at every wire dtype; inactive rows never touch the wire
    prec_x = wire_roundtrip(prec, wire_dtype)
    pm_x = wire_roundtrip(prec * mean, wire_dtype)
    new_prec = jnp.dot(w, prec_x, precision=EXCHANGE_PRECISION,
                       preferred_element_type=jnp.float32)
    new_pm = jnp.dot(w, pm_x, precision=EXCHANGE_PRECISION,
                     preferred_element_type=jnp.float32)
    mean_out_ref[...] = jnp.where(act > 0, new_pm / new_prec, mean)
    rho_out_ref[...] = jnp.where(
        act > 0, softplus_inv(jax.lax.rsqrt(new_prec)), rho
    )


@functools.partial(jax.jit, static_argnames=("block", "interpret", "wire_dtype"))
def consensus_fused_masked(
    W: jax.Array,  # [N, N] effective window W-tilde (inactive rows = e_i)
    active: jax.Array,  # [N] bool/int/float activity mask
    mean: jax.Array,  # [N, P]
    rho: jax.Array,  # [N, P]
    *,
    block: int | None = None,
    interpret: bool | None = None,
    wire_dtype=None,
) -> tuple[jax.Array, jax.Array]:
    """Event-window eq. (6): masked network-wide consensus in ONE
    ``pallas_call``.

    Active rows compute the exact ``consensus_fused_network`` math on the
    window's W-tilde (including its exchange-boundary ``wire_dtype``
    rounding); inactive rows pass (mean, rho) through untouched.  With
    ``active`` all-true and the same W this is bit-identical to
    ``consensus_fused_network`` — the gossip/synchronous equivalence the
    tests pin, at every wire dtype.  Same layout/padding contract as the
    other kernels.
    """
    interpret = _auto_interpret(interpret)
    wire_dtype = _wire_for(wire_dtype, interpret)
    n, p = mean.shape
    block = lane_block(n, 4 * n * n) if block is None else block
    mean, rho, pp = _pad_lanes(mean, rho, block)
    act = active.astype(jnp.float32)[:, None]
    grid = (pp // block,)
    mean_out, rho_out = pl.pallas_call(
        functools.partial(_consensus_masked_kernel, wire_dtype=wire_dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((n, n), lambda i: (0, 0)),  # W resident across tiles
            pl.BlockSpec((n, 1), lambda i: (0, 0)),  # mask resident too
            pl.BlockSpec((n, block), lambda i: (0, i)),
            pl.BlockSpec((n, block), lambda i: (0, i)),
        ],
        out_specs=[
            pl.BlockSpec((n, block), lambda i: (0, i)),
            pl.BlockSpec((n, block), lambda i: (0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, pp), mean.dtype),
            jax.ShapeDtypeStruct((n, pp), rho.dtype),
        ],
        interpret=interpret,
    )(W.astype(jnp.float32), act, mean, rho)
    return mean_out[:, :p], rho_out[:, :p]


def _consensus_sparse_kernel(
    nbr_ref,  # scalar-prefetch [N * D] int32 neighbor ids (self-padded)
    wts_ref,  # scalar-prefetch [N * D] fp32 neighbor weights (0-padded)
    mean_ref,  # [BLOCK/128, 128] — row nbr[i, d], column tile j
    rho_ref,  # [BLOCK/128, 128]
    mean_out_ref,  # [BLOCK/128, 128] — row i, column tile j
    rho_out_ref,  # [BLOCK/128, 128]
    acc_prec,  # VMEM scratch [BLOCK/128, 128]
    acc_pm,  # VMEM scratch [BLOCK/128, 128]
    *,
    wire_dtype,
):
    i = pl.program_id(0)
    d = pl.program_id(2)
    w = wts_ref[i * pl.num_programs(2) + d]

    @pl.when(d == 0)
    def _init():
        acc_prec[...] = jnp.zeros_like(acc_prec)
        acc_pm[...] = jnp.zeros_like(acc_pm)

    sigma = jax.nn.softplus(rho_ref[...])
    if wire_dtype == jnp.float32:
        # pre-wire op order, verbatim (w/(sigma*sigma) fuses weight and
        # precision) — f32 stays bitwise identical
        wp = w / (sigma * sigma)  # zero-weight pad entries contribute nothing
        acc_prec[...] += wp
        acc_pm[...] += wp * mean_ref[...]
    else:
        # exchange boundary: the gathered neighbor tile's (prec, prec*mu)
        # cross the wire rounded; the scratch accumulators stay fp32
        prec = 1.0 / (sigma * sigma)
        prec_x = wire_roundtrip(prec, wire_dtype)
        pm_x = wire_roundtrip(prec * mean_ref[...], wire_dtype)
        acc_prec[...] += w * prec_x
        acc_pm[...] += w * pm_x

    @pl.when(d == pl.num_programs(2) - 1)
    def _finish():
        prec_out = acc_prec[...]
        mean_out_ref[...] = acc_pm[...] / prec_out
        rho_out_ref[...] = softplus_inv(jax.lax.rsqrt(prec_out))


@functools.partial(jax.jit, static_argnames=("block", "interpret", "wire_dtype"))
def consensus_fused_sparse(
    neighbors: jax.Array,  # [N, D] int32: neighbor ids, padded with self id
    weights: jax.Array,  # [N, D] fp32: W[i, neighbors[i]], padded with 0.0
    mean: jax.Array,  # [N, P]
    rho: jax.Array,  # [N, P]
    *,
    block: int | None = None,
    interpret: bool | None = None,
    wire_dtype=None,
) -> tuple[jax.Array, jax.Array]:
    """Sparse-neighborhood eq. (6): each agent reads only deg(i) <= D
    neighbor tiles (D = max in-degree), not all N rows.

    The (neighbors, weights) tables come from ``core.flat.neighbor_tables``
    (rows of W with zero weight are skipped entirely; ragged degrees are
    padded with the self id at weight 0, which reads a tile the agent already
    needs but adds nothing to the sums).  HBM traffic: sum_i deg(i) tiles vs
    N^2 for the dense kernel — the win for ring/grid/star topologies.
    ``wire_dtype`` rounds each gathered tile's (prec, prec*mu) at the
    exchange boundary (fp32 accumulators); f32/None is bitwise the
    uncompressed kernel.
    """
    interpret = _auto_interpret(interpret)
    wire_dtype = _wire_for(wire_dtype, interpret)
    return _sparse_call(
        functools.partial(_consensus_sparse_kernel, wire_dtype=wire_dtype),
        (neighbors.astype(jnp.int32), weights.astype(jnp.float32)),
        mean, rho, block, interpret,
    )


def sparse_block(p: int) -> int:
    """Lane block of the row-gathering sparse kernels for rows of ``p``
    lanes: the whole row, padded to a multiple of 128, when the
    ``_SPARSE_LIVE_TILES`` fp32 tiles of one grid step fit three quarters of
    the scoped VMEM (a 199,210-lane row does: 10 x 0.8 MB), else the fewest
    equal tiles that do (each a multiple of 8 x 128, the f32 tiling).  One
    tile per row keeps the grid at N x D steps: at a 2048-lane block the
    paper-width grid has ~100x more steps, each moving 8 KB."""
    rows = -(-p // LANES)
    fit = _VMEM_LIMIT * 3 // 4 // (_SPARSE_LIVE_TILES * 4 * LANES) // 8 * 8
    if rows <= fit:
        return rows * LANES
    per_tile = -(-rows // -(-rows // fit))
    return -(-per_tile // 8) * 8 * LANES


def _sparse_call(kernel, prefetch, mean, rho, block, interpret):
    """Run a row-gathering sparse kernel over the lane-dense view
    ``[N, P/128, 128]``: grid ``(N, P // BLOCK, D)``, the scalar-prefetched
    ``prefetch`` tables (neighbor ids first) steer each step's input tile to
    row ``nbr[i, d]``, and two fp32 VMEM accumulators carry the sums over d.
    A (1, BLOCK) block of the 2-D buffer breaks the TPU (8, 128) tiling rule;
    a (BLOCK/128, 128) tile of one squeezed row does not.  The [N, D] tables
    ride flattened: SMEM pads a 2-D table's last dim to 128 words, a 1-D
    one holds N x D (``SPARSE_TABLE_ENTRIES``)."""
    n, p = mean.shape
    d = prefetch[0].shape[1]
    prefetch = tuple(t.reshape(-1) for t in prefetch)
    block = sparse_block(p) if block is None else block
    mean, rho, pp = _pad_lanes(mean, rho, block)
    tile = (pl.squeezed, block // LANES, LANES)
    src = pl.BlockSpec(tile, lambda i, j, k, nbr, *_: (nbr[i * d + k], j, 0))
    dst = pl.BlockSpec(tile, lambda i, j, k, *_: (i, j, 0))
    lane_dense = (n, pp // LANES, LANES)
    mean_out, rho_out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=(n, pp // block, d),
            in_specs=[src, src],
            out_specs=[dst, dst],
            scratch_shapes=[pltpu.VMEM(tile[1:], jnp.float32)] * 2,
        ),
        out_shape=[
            jax.ShapeDtypeStruct(lane_dense, mean.dtype),
            jax.ShapeDtypeStruct(lane_dense, rho.dtype),
        ],
        interpret=interpret,
    )(*prefetch, mean.reshape(lane_dense), rho.reshape(lane_dense))
    return mean_out.reshape(n, pp)[:, :p], rho_out.reshape(n, pp)[:, :p]


def _payload_validity_kernel(mean_ref, rho_ref, ok_ref, *, wire_dtype, bound):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        ok_ref[...] = jnp.ones_like(ok_ref)

    sigma = jax.nn.softplus(rho_ref[...])
    prec = 1.0 / (sigma * sigma)
    prec_x = wire_roundtrip(prec, wire_dtype)
    pm_x = wire_roundtrip(prec * mean_ref[...], wire_dtype)
    ok = (
        jnp.isfinite(prec_x)
        & (prec_x > 0.0)
        & (prec_x <= bound)
        & jnp.isfinite(pm_x)
        & (jnp.abs(pm_x) <= bound)
    )
    tile_ok = jnp.all(ok, axis=-1, keepdims=True)  # [N, 1]
    ok_ref[...] = ok_ref[...] * tile_ok.astype(jnp.float32)


@functools.partial(
    jax.jit, static_argnames=("bound", "block", "interpret", "wire_dtype")
)
def payload_validity_fused(
    mean: jax.Array,  # [N, P]
    rho: jax.Array,  # [N, P]
    *,
    bound: float,
    block: int | None = None,
    interpret: bool | None = None,
    wire_dtype=None,
) -> jax.Array:
    """Fused exchange-payload sanity probe: ONE streaming pass over the flat
    [N, P] buffers returning a per-agent [N] bool — every wire-rounded
    (prec, prec*mu) lane finite, prec > 0, magnitudes within ``bound``.

    Grid ``(P // BLOCK,)`` with a revisited [N, 1] output: tile 0 seeds the
    flags to 1.0, every subsequent tile ANDs (multiplies) its own all-lanes
    verdict in — the same single-HBM-pass shape as the consensus kernels, so
    the quarantine guard adds one read pass, not a gather storm.  Pad lanes
    (mean 0.0, rho 1.0) are always valid and never flip a flag.  Pinned
    bit-equal to the ``core.flat.payload_validity`` XLA reference.
    """
    interpret = _auto_interpret(interpret)
    wire_dtype = _wire_for(wire_dtype, interpret)
    n, _ = mean.shape
    block = lane_block(n) if block is None else block
    mean, rho, pp = _pad_lanes(mean, rho, block)
    grid = (pp // block,)
    ok = pl.pallas_call(
        functools.partial(
            _payload_validity_kernel, wire_dtype=wire_dtype,
            bound=float(bound),
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((n, block), lambda i: (0, i)),
            pl.BlockSpec((n, block), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((n, 1), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((n, 1), jnp.float32),
        interpret=interpret,
    )(mean, rho)
    return ok[:, 0] > 0.0


def _consensus_masked_sparse_kernel(
    nbr_ref,  # scalar-prefetch [N * D] int32 neighbor ids (self-padded)
    wts_ref,  # scalar-prefetch [N * D] fp32 weights (0-padded)
    act_ref,  # scalar-prefetch [N] int32 activity mask
    mean_ref,  # [BLOCK/128, 128] — row nbr[i, d], column tile j
    rho_ref,  # [BLOCK/128, 128]
    mean_out_ref,  # [BLOCK/128, 128] — row i, column tile j
    rho_out_ref,  # [BLOCK/128, 128]
    acc_prec,  # VMEM scratch [BLOCK/128, 128]
    acc_pm,  # VMEM scratch [BLOCK/128, 128]
    *,
    wire_dtype,
):
    i = pl.program_id(0)
    d = pl.program_id(2)
    w = wts_ref[i * pl.num_programs(2) + d]

    @pl.when(d == 0)
    def _init():
        acc_prec[...] = jnp.zeros_like(acc_prec)
        acc_pm[...] = jnp.zeros_like(acc_pm)

    # a weight-0 slot (table padding, or any slot of an inactive row) adds
    # nothing: skip its arithmetic, and the pipeline skips its fetch when
    # it names the same row as the slot before
    @pl.when(w != 0.0)
    def _accumulate():
        sigma = jax.nn.softplus(rho_ref[...])
        if wire_dtype == jnp.float32:
            # pre-wire op order, verbatim — f32 stays bitwise identical
            wp = w / (sigma * sigma)
            acc_prec[...] += wp
            acc_pm[...] += wp * mean_ref[...]
        else:
            prec = 1.0 / (sigma * sigma)
            prec_x = wire_roundtrip(prec, wire_dtype)
            pm_x = wire_roundtrip(prec * mean_ref[...], wire_dtype)
            acc_prec[...] += w * prec_x
            acc_pm[...] += w * pm_x

    @pl.when(d == pl.num_programs(2) - 1)
    def _finish():
        # inactive rows are all-self in the tables (w_eff row == e_i), so the
        # tile currently in (mean_ref, rho_ref) IS the agent's own row — the
        # passthrough never touches anyone else's data
        passthrough = act_ref[i] == 0
        prec_out = acc_prec[...]
        mean_out_ref[...] = jnp.where(
            passthrough, mean_ref[...], acc_pm[...] / prec_out
        )
        rho_out_ref[...] = jnp.where(
            passthrough, rho_ref[...], softplus_inv(jax.lax.rsqrt(prec_out))
        )


@functools.partial(jax.jit, static_argnames=("block", "interpret", "wire_dtype"))
def consensus_fused_masked_sparse(
    neighbors: jax.Array,  # [N, D] int32 window neighbor ids (self-padded)
    weights: jax.Array,  # [N, D] fp32 w_eff[i, neighbors[i]] (0-padded)
    active: jax.Array,  # [N] activity mask
    mean: jax.Array,  # [N, P]
    rho: jax.Array,  # [N, P]
    *,
    block: int | None = None,
    interpret: bool | None = None,
    wire_dtype=None,
) -> tuple[jax.Array, jax.Array]:
    """Active-edge eq. (6): CSR neighbor tables of the window's W-tilde
    (``core.flat.neighbor_tables(w_eff)``) + per-agent activity mask.

    Active agents accumulate only their deg(i) <= D fired-neighbor tiles;
    inactive agents copy their own (mean, rho) row bit-identically (their
    table rows are all-self, so no foreign tile is ever gathered — and
    never crosses the wire, whatever ``wire_dtype`` says).  HBM traffic
    scales with the window's active-edge fraction instead of N — see
    ``launch.costmodel.gossip_window_roofline``.
    """
    interpret = _auto_interpret(interpret)
    wire_dtype = _wire_for(wire_dtype, interpret)
    return _sparse_call(
        functools.partial(
            _consensus_masked_sparse_kernel, wire_dtype=wire_dtype
        ),
        (
            neighbors.astype(jnp.int32),
            weights.astype(jnp.float32),
            active.astype(jnp.int32),
        ),
        mean, rho, block, interpret,
    )
