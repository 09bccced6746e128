"""Beyond-paper consensus optimizations (EXPERIMENTS.md §Perf).

The paper-faithful baseline (core.posterior.consensus_all_agents) computes
eq. (6) as an einsum over the agent axis; under GSPMD with the agent dim
sharded this lowers to an ALL-GATHER of the whole posterior (N x params
bytes) on every consensus.  Two optimizations:

1. ``consensus_ppermute`` — for SPARSE W (ring/torus neighborhoods) exchange
   only with actual graph neighbors via ``lax.ppermute`` inside
   ``shard_map``: deg(i) x params bytes instead of N x params.  Exact
   (bitwise same math, different schedule).
2. ``dtype`` compression — exchange (prec, prec*mu) in bf16: halves the
   wire bytes; approximate, error-bounded by ``core.numerics
   .wire_error_bound`` (tests/test_wire_dtype.py).  Since the wire-dtype
   PR this is a first-class knob (``InferenceSpec(wire_dtype=...)``) and
   every cast site here routes through the ONE shared helper
   ``core.numerics.wire_cast_pair`` (previously each function inlined its
   own copy).
3. ``consensus_ppermute_window`` — the SHARDED GOSSIP WINDOW (ROADMAP
   "Gossip scale-out"): one ``shard_map`` over the flat [N, P] buffers,
   sharded on the agent axis, that executes one ``gossip.clocks
   .EventWindow`` by ppermuting ONLY the shard offsets its fired edges
   cross.  Wire bytes scale with the window's active cross-shard offsets
   (idle windows move zero bytes) instead of the dense all-gather's
   N x params.  BIT-IDENTICAL to ``core.flat.consensus_flat_masked`` —
   the equivalence ladder synchronous == instant gossip == sharded gossip
   is enforced by tests/test_gossip.py.

All preserve the fixed point structure of eq. (6): weights stay
row-stochastic, output precision remains a convex combination.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.flat import XLA_BLOCK, _MAX_UNROLL, FlatPosterior
from repro.core.numerics import (
    EXCHANGE_PRECISION,
    canonical_wire_dtype,
    wire_cast_pair,
)
from repro.core.posterior import GaussianPosterior, softplus, softplus_inv


def consensus_einsum(posts: GaussianPosterior, W: jax.Array,
                     wire_dtype=jnp.float32) -> GaussianPosterior:
    """Dense eq. (6) with optional wire-dtype compression of the exchanged
    sufficient statistics (prec, prec*mean)."""
    wire_dtype = canonical_wire_dtype(wire_dtype)

    def combine(mean_stack, rho_stack):
        prec = 1.0 / jnp.square(softplus(rho_stack))
        # keep the exchanged sufficient statistics in wire_dtype THROUGH the
        # einsum (accumulate in fp32) — casting back before the contraction
        # would let XLA hoist the convert above the all-gather and the wire
        # would stay fp32 (measured: identical collective bytes).
        prec_w, pm = wire_cast_pair(prec, prec * mean_stack, wire_dtype)
        w_cast = W.astype(wire_dtype)
        new_prec = jnp.einsum("ij,j...->i...", w_cast, prec_w,
                              preferred_element_type=jnp.float32)
        new_pm = jnp.einsum("ij,j...->i...", w_cast, pm,
                            preferred_element_type=jnp.float32)
        new_mean = new_pm / new_prec
        new_rho = softplus_inv(jnp.sqrt(1.0 / new_prec))
        return new_mean, new_rho

    flat_mean, treedef = jax.tree.flatten(posts.mean)
    flat_rho = treedef.flatten_up_to(posts.rho)
    out = [combine(m, r) for m, r in zip(flat_mean, flat_rho)]
    return GaussianPosterior(
        mean=jax.tree.unflatten(treedef, [m for m, _ in out]),
        rho=jax.tree.unflatten(treedef, [r for _, r in out]),
    )


def consensus_einsum_flat(
    posts: FlatPosterior, W: jax.Array, wire_dtype=jnp.float32
) -> FlatPosterior:
    """Dense eq. (6) directly on the flat [N, P] buffers: ONE einsum pair for
    the whole network instead of a Python loop over leaves.  Under GSPMD with
    the agent dim sharded this still lowers to an all-gather, but of one
    contiguous buffer — a single collective per round (vs one per leaf), and
    the wire-dtype compression applies to the whole payload at once."""
    wire_dtype = canonical_wire_dtype(wire_dtype)
    prec = 1.0 / jnp.square(softplus(posts.rho))
    prec_w, pm = wire_cast_pair(prec, prec * posts.mean, wire_dtype)
    w_cast = W.astype(wire_dtype)
    new_prec = jnp.einsum("ij,jp->ip", w_cast, prec_w,
                          preferred_element_type=jnp.float32)
    new_pm = jnp.einsum("ij,jp->ip", w_cast, pm,
                        preferred_element_type=jnp.float32)
    return dataclasses.replace(
        posts,
        mean=new_pm / new_prec,
        rho=softplus_inv(jnp.sqrt(1.0 / new_prec)),
    )


def consensus_ppermute_ring_flat(
    posts: FlatPosterior,
    mesh: jax.sharding.Mesh,
    axis: str,
    self_weight: float = 1.0 / 3.0,
    wire_dtype=jnp.float32,
    W: jax.Array | None = None,
) -> FlatPosterior:
    """Bidirectional-ring eq. (6) on the flat buffers: one ``shard_map`` over
    the two [N, P] arrays (the pytree version below issues one shard_map per
    leaf).  Wire bytes per agent: 2 x P (both neighbor directions).

    ``W=None`` uses the uniform ring weights from ``self_weight``;
    passing the [N, N] ring matrix reads each shard's (self, prev, next)
    weights from its own row via ``axis_index`` — the form
    ``make_train_round_step(consensus_impl="ppermute")`` routes flat
    posteriors through (non-ring entries of W are ignored; for n == 2 the
    two neighbor directions coincide and only the fwd direction is mixed,
    exactly like ``consensus_ppermute_pod``).
    """
    wire_dtype = canonical_wire_dtype(wire_dtype)
    n = mesh.shape[axis]
    fwd = [(i, (i + 1) % n) for i in range(n)]  # receive from i-1
    bwd = [(i, (i - 1) % n) for i in range(n)]  # receive from i+1
    if W is None:
        w_static = ring_weights(n, self_weight)
        Wd = None
    else:
        w_static = None
        Wd = jnp.asarray(W, jnp.float32)

    def shard_fn(mean, rho):
        if Wd is None:
            w_self, w_prev, w_next = w_static
        else:
            i = jax.lax.axis_index(axis)
            w_self = Wd[i, i]
            w_prev = Wd[i, (i - 1) % n]
            w_next = Wd[i, (i + 1) % n] if n > 2 else jnp.asarray(0.0)
        prec = 1.0 / jnp.square(softplus(rho))
        pw, pm = wire_cast_pair(prec, prec * mean, wire_dtype)
        prev_p = jax.lax.ppermute(pw, axis, fwd)
        prev_pm = jax.lax.ppermute(pm, axis, fwd)
        next_p = jax.lax.ppermute(pw, axis, bwd)
        next_pm = jax.lax.ppermute(pm, axis, bwd)
        new_prec = (
            w_self * prec
            + w_prev * prev_p.astype(jnp.float32)
            + w_next * next_p.astype(jnp.float32)
        )
        new_pm = (
            w_self * (prec * mean)
            + w_prev * prev_pm.astype(jnp.float32)
            + w_next * next_pm.astype(jnp.float32)
        )
        return new_pm / new_prec, softplus_inv(jnp.sqrt(1.0 / new_prec))

    spec = P(axis, None)
    fn = jax.shard_map(
        shard_fn, mesh=mesh, in_specs=(spec, spec), out_specs=(spec, spec)
    )
    mean, rho = fn(posts.mean, posts.rho)
    return dataclasses.replace(posts, mean=mean, rho=rho)


def consensus_ppermute_pod(
    posts: GaussianPosterior,
    W: jax.Array,  # [A, A]
    mesh: jax.sharding.Mesh,
    shardings,  # GaussianPosterior-shaped tree of NamedSharding for posts
    wire_dtype=jnp.bfloat16,
    axis: str = "pod",
) -> GaussianPosterior:
    """Eq. (6) over the pod axis via explicit neighbor ppermute in shard_map.

    Exchanges ONLY the sufficient statistics (prec, prec*mu) with the other
    pod(s), in ``wire_dtype`` — unlike the einsum path, the collective is
    guaranteed to run on the compressed payload (the einsum path lets XLA's
    dot legalization hoist converts above the all-gather; measured:
    identical f32 wire bytes).  Implemented for rings of any A (each agent
    mixes self + both neighbors); for A=2 both neighbors coincide."""
    wire_dtype = canonical_wire_dtype(wire_dtype)
    n = mesh.shape[axis]
    fwd = [(i, (i + 1) % n) for i in range(n)]
    bwd = [(i, (i - 1) % n) for i in range(n)]
    Wd = jnp.asarray(W, jnp.float32)

    def shard_fn(mean, rho):
        i = jax.lax.axis_index(axis)
        prec = 1.0 / jnp.square(softplus(rho))
        pm = prec * mean
        prec_w, pm_w = wire_cast_pair(prec, pm, wire_dtype)
        prev_p = jax.lax.ppermute(prec_w, axis, fwd).astype(jnp.float32)
        prev_pm = jax.lax.ppermute(pm_w, axis, fwd).astype(jnp.float32)
        w_self = Wd[i, i]
        w_prev = Wd[i, (i - 1) % n]
        if n > 2:
            next_p = jax.lax.ppermute(prec_w, axis, bwd).astype(jnp.float32)
            next_pm = jax.lax.ppermute(pm_w, axis, bwd).astype(jnp.float32)
            w_next = Wd[i, (i + 1) % n]
        else:
            next_p = jnp.zeros_like(prec)
            next_pm = jnp.zeros_like(pm)
            w_next = jnp.asarray(0.0)
        new_prec = w_self * prec + w_prev * prev_p + w_next * next_p
        new_pm = w_self * pm + w_prev * prev_pm + w_next * next_pm
        new_mean = new_pm / new_prec
        new_rho = softplus_inv(jnp.sqrt(1.0 / new_prec))
        return new_mean, new_rho

    flat_mean, treedef = jax.tree.flatten(posts.mean)
    flat_rho = treedef.flatten_up_to(posts.rho)
    flat_shard = treedef.flatten_up_to(shardings.mean)
    outs = []
    for m, r, s in zip(flat_mean, flat_rho, flat_shard):
        spec = s.spec if hasattr(s, "spec") else s
        fn = jax.shard_map(
            shard_fn, mesh=mesh, in_specs=(spec, spec), out_specs=(spec, spec)
        )
        outs.append(fn(m, r))
    return GaussianPosterior(
        mean=jax.tree.unflatten(treedef, [m for m, _ in outs]),
        rho=jax.tree.unflatten(treedef, [r for _, r in outs]),
    )


# ---------------------------------------------------------------------------
# sharded gossip event windows (ROADMAP "Gossip scale-out")
# ---------------------------------------------------------------------------


def window_shard_offsets(window, n_shards: int) -> tuple[int, ...]:
    """The static permutation schedule of one event window: the sorted set
    of nonzero shard offsets ``(dst_shard - src_shard) mod n_shards`` crossed
    by the window's fired edges (agents are block-sharded: agent a lives on
    shard ``a // (N // n_shards)``).  One ``lax.ppermute`` rotation per
    offset moves every cross-shard message of that offset at once;
    intra-shard edges (offset 0) need no communication at all.  Derived
    host-side from ``EventWindow.edges`` — the schedule is a pure function
    of the window, so distinct window supports compile distinct (cached)
    programs while repeated supports reuse them."""
    per = window.n_agents // n_shards
    ev = window.edges[: window.n_events]
    return tuple(sorted(
        {(int(d) // per - int(s) // per) % n_shards for d, s in ev} - {0}
    ))


@functools.lru_cache(maxsize=None)
def _window_consensus_fn(mesh, axis, offsets, n, per, p, block, wire_dtype):
    """Build + cache the jitted shard_map program for one (mesh, schedule,
    shape, wire dtype) signature.  The body mirrors ``core.flat
    .consensus_flat_reference`` op for op (same elementwise chain — wire
    rounding included, same [*, N] x [N, cols] matmul contraction, same
    column blocking, same activity select) so the sharded window is
    bit-identical to the masked reference AT EVERY WIRE DTYPE; only the
    data movement differs (buffers assembled from neighbor-shard ppermutes
    instead of being resident — and at bf16/f16 the ppermuted payload
    itself is wire-dtype, halving the ICI bytes per rotation)."""
    n_shards = mesh.shape[axis]
    compressed = wire_dtype != jnp.float32

    def shard_fn(w_rows, act, mean_l, rho_l):
        # w_rows [per, N]: this shard's rows of W-tilde; mean_l/rho_l
        # [per, P]: this shard's agents
        i = jax.lax.axis_index(axis)
        prec = 1.0 / jnp.square(softplus(rho_l))
        pm = prec * mean_l
        if compressed:
            # exchange boundary: the wire payload is the rounded (prec,
            # prec*mu).  The OWN block decodes the same rounded values the
            # neighbors receive, so the assembled buffer is elementwise
            # identical to the dense masked kernel's rounded buffer (the
            # equivalence ladder stays bitwise per wire dtype).
            prec_w, pm_w = wire_cast_pair(prec, pm, wire_dtype)
            prec = prec_w.astype(jnp.float32)
            pm = pm_w.astype(jnp.float32)
        # assemble the [N, P] sufficient-statistic buffers this shard's rows
        # read: own block always (self loops + intra-shard edges), one
        # ppermute rotation per fired cross-shard offset.  Rows of shards at
        # un-fired offsets stay zero — their W-tilde entries are zero, so
        # they contribute exactly 0.0 to the matmul (bit-stable).
        buf_prec = jnp.zeros((n, prec.shape[-1]), prec.dtype)
        buf_pm = jnp.zeros_like(buf_prec)
        buf_prec = jax.lax.dynamic_update_slice(buf_prec, prec, (i * per, 0))
        buf_pm = jax.lax.dynamic_update_slice(buf_pm, pm, (i * per, 0))
        for d in offsets:
            perm = [(s, (s + d) % n_shards) for s in range(n_shards)]
            if compressed:
                # the collective moves the COMPRESSED statistics (half the
                # ICI bytes per rotation at bf16); decode fp32 on receipt
                r_prec = jax.lax.ppermute(prec_w, axis, perm).astype(jnp.float32)
                r_pm = jax.lax.ppermute(pm_w, axis, perm).astype(jnp.float32)
            else:
                r_prec = jax.lax.ppermute(prec, axis, perm)
                r_pm = jax.lax.ppermute(pm, axis, perm)
            src0 = ((i - d) % n_shards) * per
            buf_prec = jax.lax.dynamic_update_slice(buf_prec, r_prec, (src0, 0))
            buf_pm = jax.lax.dynamic_update_slice(buf_pm, r_pm, (src0, 0))
        a = (act > 0)[:, None]

        def blk(s, e):
            new_prec = jnp.matmul(
                w_rows, buf_prec[:, s:e], precision=EXCHANGE_PRECISION,
                preferred_element_type=jnp.float32,
            )
            new_pm = jnp.matmul(
                w_rows, buf_pm[:, s:e], precision=EXCHANGE_PRECISION,
                preferred_element_type=jnp.float32,
            )
            m_o = new_pm / new_prec
            r_o = softplus_inv(jax.lax.rsqrt(new_prec))
            return (
                jnp.where(a, m_o, mean_l[:, s:e]),
                jnp.where(a, r_o, rho_l[:, s:e]),
            )

        # identical column blocking to consensus_flat_reference (cache
        # blocking + unroll cap) — required for large-P bit-identity
        blk_cols = block
        if p > blk_cols and -(-p // blk_cols) > _MAX_UNROLL:
            blk_cols = -(-p // _MAX_UNROLL)
        if p <= blk_cols:
            return blk(0, p)
        mean_out = jnp.empty_like(mean_l)
        rho_out = jnp.empty_like(rho_l)
        for s in range(0, p, blk_cols):
            e = min(s + blk_cols, p)
            m_o, r_o = blk(s, e)
            mean_out = jax.lax.dynamic_update_slice(mean_out, m_o, (0, s))
            rho_out = jax.lax.dynamic_update_slice(rho_out, r_o, (0, s))
        return mean_out, rho_out

    spec_np = P(axis, None)
    return jax.jit(jax.shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(spec_np, P(axis), spec_np, spec_np),
        out_specs=(spec_np, spec_np),
    ))


def consensus_ppermute_window(
    posts: FlatPosterior,
    window,  # gossip.clocks.EventWindow
    mesh: jax.sharding.Mesh,
    axis: str = "agents",
    *,
    block: int | None = None,
    wire_dtype=None,
    w_eff: jax.Array | None = None,
    active: jax.Array | None = None,
) -> FlatPosterior:
    """Execute ONE gossip event window sharded over the agent axis.

    The flat [N, P] posterior buffers are block-sharded on ``mesh``'s
    ``axis`` (N must divide evenly); the window's static edge list is
    lowered to a permutation schedule (``window_shard_offsets``) and the
    whole window runs as one ``shard_map``: per fired cross-shard offset,
    one ``ppermute`` rotation of the (prec, prec*mu) sufficient statistics,
    then each shard reduces its own W-tilde rows locally.  Wire bytes per
    window: ``n_offsets x 2 x N/S x P`` per shard — proportional to the
    window's cross-shard activity, zero for an idle window — vs the dense
    path's full all-gather (``launch.costmodel.gossip_window_roofline``).

    Bit-identical to ``core.flat.consensus_flat_masked`` on the same
    window AND the same ``wire_dtype`` (equivalence-ladder acceptance test
    in tests/test_gossip.py / test_wire_dtype.py): at bf16/f16 the
    ppermuted payload is the compressed (prec, prec*mu) — half the wire
    bytes per rotation — decoded fp32 on receipt.
    Instant-delivery windows only: delayed windows (``window.max_lag > 0``)
    merge history slots and run the gather path in the engine.

    ``w_eff``/``active`` override the window's W-tilde and activity mask
    WITHOUT changing the (static, edge-derived) permutation schedule — the
    quarantine guard's hook: it zeroes an invalid source's columns and moves
    the mass to self, which only ever REMOVES weight from scheduled edges
    (rotating a sanitized zero-weight payload is harmless), so the cached
    shard_map program is reused unchanged.
    """
    n = window.n_agents
    n_shards = mesh.shape[axis]
    if n % n_shards:
        raise ValueError(
            f"agent axis ({n}) must divide evenly over the {n_shards}-shard "
            f"mesh axis {axis!r}"
        )
    if window.max_lag > 0:
        raise ValueError(
            "consensus_ppermute_window implements instant delivery; delayed "
            "windows (max_lag > 0) run the history-gather path "
            "(core.flat.consensus_flat_delayed)"
        )
    per = n // n_shards
    p = posts.mean.shape[-1]
    fn = _window_consensus_fn(
        mesh, axis, window_shard_offsets(window, n_shards), n, per, p,
        XLA_BLOCK if block is None else block,
        canonical_wire_dtype(wire_dtype),
    )
    mean, rho = fn(
        (jnp.asarray(window.w_eff, jnp.float32) if w_eff is None
         else jnp.asarray(w_eff, jnp.float32)),
        jnp.asarray(window.active) if active is None else jnp.asarray(active),
        posts.mean,
        posts.rho,
    )
    return dataclasses.replace(posts, mean=mean, rho=rho)


def ring_weights(n: int, self_weight: float = 1.0 / 3.0) -> tuple[float, float, float]:
    side = (1.0 - self_weight) / 2.0
    return self_weight, side, side


def consensus_ppermute_ring(
    posts: GaussianPosterior,
    mesh: jax.sharding.Mesh,
    axis: str,
    self_weight: float = 1.0 / 3.0,
    wire_dtype=jnp.float32,
) -> GaussianPosterior:
    """Eq. (6) on a bidirectional RING W via neighbor-only ppermute.

    ``posts`` leaves carry a leading agent dim of size mesh.shape[axis],
    sharded over ``axis``.  Wire bytes per agent: 2 x params (vs N x params
    for the dense all-gather) — the §Perf 'sparse consensus' optimization.
    """
    wire_dtype = canonical_wire_dtype(wire_dtype)
    n = mesh.shape[axis]
    w_self, w_prev, w_next = ring_weights(n, self_weight)
    fwd = [(i, (i + 1) % n) for i in range(n)]  # receive from i-1
    bwd = [(i, (i - 1) % n) for i in range(n)]  # receive from i+1

    def shard_fn(mean, rho):
        # per-shard leading agent dim == 1
        prec = 1.0 / jnp.square(softplus(rho))
        pw, pm = wire_cast_pair(prec, prec * mean, wire_dtype)
        prev_p = jax.lax.ppermute(pw, axis, fwd)
        prev_pm = jax.lax.ppermute(pm, axis, fwd)
        next_p = jax.lax.ppermute(pw, axis, bwd)
        next_pm = jax.lax.ppermute(pm, axis, bwd)
        new_prec = (
            w_self * prec
            + w_prev * prev_p.astype(jnp.float32)
            + w_next * next_p.astype(jnp.float32)
        )
        new_pm = (
            w_self * (prec * mean)
            + w_prev * prev_pm.astype(jnp.float32)
            + w_next * next_pm.astype(jnp.float32)
        )
        new_mean = new_pm / new_prec
        new_rho = softplus_inv(jnp.sqrt(1.0 / new_prec))
        return new_mean, new_rho

    def leaf_spec(leaf):
        return P(axis, *([None] * (leaf.ndim - 1)))

    flat_mean, treedef = jax.tree.flatten(posts.mean)
    flat_rho = treedef.flatten_up_to(posts.rho)
    outs = []
    for m, r in zip(flat_mean, flat_rho):
        spec = leaf_spec(m)
        fn = jax.shard_map(
            shard_fn, mesh=mesh, in_specs=(spec, spec), out_specs=(spec, spec)
        )
        outs.append(fn(m, r))
    return GaussianPosterior(
        mean=jax.tree.unflatten(treedef, [m for m, _ in outs]),
        rho=jax.tree.unflatten(treedef, [r for _, r in outs]),
    )
