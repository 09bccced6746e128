"""Flat-buffer posterior representation — the canonical runtime format.

A ``FlatPosterior`` stores the whole network's mean-field Gaussian posterior
as TWO contiguous fp32 buffers:

    mean: [N_agents, P]     rho: [N_agents, P]

plus a cached, hashable ``FlatLayout`` that records, per model-parameter
leaf: key path, shape, dtype and its (offset, size) column span in the flat
buffer.  The layout is built ONCE (``FlatLayout.for_pytree``) and carried as
static pytree metadata; ``to_pytree``/``from_pytree`` are the only
conversion points and they lower to pure slice/reshape/cast ops that XLA
fuses into the surrounding computation (no data movement beyond the
unavoidable cast when a leaf is not fp32).

Layout contract (shared with ``kernels.consensus``; see that module's
docstring for the kernel-side half):
  * axis 0 = agent axis, axis 1 = flattened parameter axis, leaf-major in
    ``layout.specs`` order, fp32;
  * buffers are UNPADDED (P = exact parameter count); lane padding to the
    kernel BLOCK multiple happens inside the kernels and is sliced off
    before any value escapes (mean pads 0.0, rho pads 1.0 -> finite sigma);
  * per-leaf dtypes are recorded in the layout and restored on
    ``to_pytree`` (mixed-dtype pytrees never silently promote).

Why: the consensus round (paper eq. 6) is purely memory-bound; with the
posterior flat, the whole network round is ONE fused pass over [N, P]
(``kernels.consensus.consensus_fused_network`` on TPU, a single fused XLA
einsum elsewhere) instead of a Python loop over leaves doing ~6 elementwise
HBM round-trips each.  ``benchmarks/bench_consensus.py`` tracks the win in
``BENCH_consensus.json``.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import graphs as _graphs
from repro.core.numerics import (
    COMPUTE_DTYPE,
    EXCHANGE_PRECISION,
    canonical_wire_dtype,
    softplus,
    softplus_inv,
    softplus_inv_py,
    wire_roundtrip,
)

PyTree = Any


@dataclasses.dataclass(frozen=True)
class LeafSpec:
    """One model-parameter leaf's slot in the flat buffer."""

    path: str  # jax key-path string, for error messages / checkpoint docs
    shape: tuple[int, ...]  # per-agent shape (leading agent axes stripped)
    dtype: str  # dtype NAME of the original leaf (name, not np .str — the
    #             numpy byte-string for bfloat16 is a lossy '<V2')
    offset: int  # start column in the flat buffer
    size: int  # number of scalars = prod(shape)


@dataclasses.dataclass(frozen=True)
class FlatLayout:
    """Cached leaf layout: offsets/shapes/dtypes + the pytree structure.

    Hashable (usable as static pytree metadata / jit static argument).
    """

    specs: tuple[LeafSpec, ...]
    treedef: Any  # jax PyTreeDef (hashable)
    n_params: int  # P: total scalars per agent

    @classmethod
    def for_pytree(cls, tree: PyTree, leading_axes: int = 0) -> "FlatLayout":
        """Build the layout from an example pytree.

        ``leading_axes`` axes are stripped off every leaf shape (pass 1 for a
        network-stacked tree whose leaves are [N, ...]).
        """
        leaves_with_path, treedef = jax.tree_util.tree_flatten_with_path(tree)
        specs, off = [], 0
        for path, leaf in leaves_with_path:
            shape = tuple(int(s) for s in leaf.shape[leading_axes:])
            size = int(np.prod(shape)) if shape else 1
            specs.append(
                LeafSpec(
                    path=jax.tree_util.keystr(path),
                    shape=shape,
                    dtype=jnp.dtype(leaf.dtype).name,
                    offset=off,
                    size=size,
                )
            )
            off += size
        return cls(specs=tuple(specs), treedef=treedef, n_params=off)

    # -- conversions ---------------------------------------------------------

    def flatten(self, tree: PyTree) -> jax.Array:
        """Pytree with leaves [*B, *spec.shape] -> fp32 buffer [*B, P].

        Any common leading batch shape B (e.g. the agent axis) is preserved.
        """
        leaves = self.treedef.flatten_up_to(tree)
        batch = None
        flat = []
        for spec, leaf in zip(self.specs, leaves):
            nb = leaf.ndim - len(spec.shape)
            b = tuple(leaf.shape[:nb])
            if tuple(leaf.shape[nb:]) != spec.shape or (batch not in (None, b)):
                raise ValueError(
                    f"leaf {spec.path}: shape {leaf.shape} does not match "
                    f"layout {spec.shape} (batch {batch})"
                )
            batch = b
            flat.append(leaf.reshape(b + (spec.size,)).astype(COMPUTE_DTYPE))
        return jnp.concatenate(flat, axis=-1)

    def unflatten(self, flat: jax.Array) -> PyTree:
        """fp32 buffer [*B, P] -> pytree with leaves [*B, *shape], cast back
        to each leaf's recorded dtype (mixed-dtype trees round-trip exactly
        in structure and dtype)."""
        if flat.shape[-1] != self.n_params:
            raise ValueError(
                f"buffer has {flat.shape[-1]} params, layout expects {self.n_params}"
            )
        b = tuple(flat.shape[:-1])
        leaves = [
            jax.lax.slice_in_dim(flat, s.offset, s.offset + s.size, axis=flat.ndim - 1)
            .reshape(b + s.shape)
            .astype(s.dtype)
            for s in self.specs
        ]
        return jax.tree.unflatten(self.treedef, leaves)

    # -- checkpoint doc ------------------------------------------------------

    def to_doc(self) -> dict:
        """Self-describing msgpack-able doc (see checkpoint.io flat helpers)."""
        skeleton = jax.tree.unflatten(self.treedef, list(range(len(self.specs))))
        return {
            "n_params": self.n_params,
            "specs": [dataclasses.asdict(s) | {"shape": list(s.shape)} for s in self.specs],
            "skeleton": _encode_skeleton(skeleton),
        }

    @classmethod
    def from_doc(cls, doc: dict) -> "FlatLayout":
        skeleton = _decode_skeleton(doc["skeleton"])
        treedef = jax.tree.structure(skeleton)
        specs = tuple(
            LeafSpec(
                path=s["path"],
                shape=tuple(s["shape"]),
                dtype=s["dtype"],
                offset=s["offset"],
                size=s["size"],
            )
            for s in doc["specs"]
        )
        return cls(specs=specs, treedef=treedef, n_params=doc["n_params"])


def _encode_skeleton(node):
    """Encode a dict/list/tuple/int skeleton as msgpack-able JSON-ish data
    (tuples tagged so they survive the round trip)."""
    if isinstance(node, dict):
        if not all(isinstance(k, str) for k in node):
            raise TypeError("FlatLayout checkpoint docs require str dict keys")
        return {k: _encode_skeleton(v) for k, v in node.items()}
    if isinstance(node, tuple):
        return {"__tuple__": [_encode_skeleton(v) for v in node]}
    if isinstance(node, list):
        return [_encode_skeleton(v) for v in node]
    if isinstance(node, int):
        return node
    raise TypeError(
        f"pytree node {type(node)} not supported in a self-describing flat "
        "checkpoint; restore with an explicit `like` tree instead"
    )


def _decode_skeleton(node):
    if isinstance(node, dict):
        if set(node) == {"__tuple__"}:
            return tuple(_decode_skeleton(v) for v in node["__tuple__"])
        return {k: _decode_skeleton(v) for k, v in node.items()}
    if isinstance(node, list):
        return [_decode_skeleton(v) for v in node]
    return node


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class FlatPosterior:
    """Mean-field Gaussian posterior over flat buffers [*B, P].

    Duck-types ``GaussianPosterior`` (mean / rho / sigma / precision /
    sample / n_params) so the VI step, optimizers and KL are shared; the
    leading batch axes B are typically (N_agents,) at the network level and
    () inside the per-agent ``vmap``.
    """

    mean: jax.Array
    rho: jax.Array
    layout: FlatLayout = dataclasses.field(metadata=dict(static=True))

    def sigma(self) -> jax.Array:
        return softplus(self.rho)

    def precision(self) -> jax.Array:
        return 1.0 / jnp.square(softplus(self.rho))

    def sample(self, key: jax.Array) -> jax.Array:
        """Reparameterized sample theta = mu + sigma * eps — a FLAT [*B, P]
        vector; feed it to the model through ``layout.unflatten`` (or use
        ``make_flat_nll`` which does exactly that at the apply boundary)."""
        eps = jax.random.normal(key, self.mean.shape, self.mean.dtype)
        return self.mean + softplus(self.rho) * eps

    def sample_pytree(self, key: jax.Array) -> PyTree:
        return self.layout.unflatten(self.sample(key))

    def n_params(self) -> int:
        return self.layout.n_params

    # -- serving-snapshot views (ROADMAP "Serving") --------------------------

    def astype(self, dtype) -> "FlatPosterior":
        """Both buffers cast to ``dtype`` (layout unchanged) — the decode
        half of the serving-snapshot path: a narrow-resident snapshot is
        ``astype(jnp.float32)``-ed inside the jitted apply, where XLA fuses
        the widening cast into the first read (no extra HBM pass).  A
        same-dtype cast is a structural no-op returning ``self``."""
        dt = jnp.dtype(dtype)
        if (jnp.dtype(self.mean.dtype) == dt
                and jnp.dtype(self.rho.dtype) == dt):
            return self
        return FlatPosterior(
            mean=self.mean.astype(dt), rho=self.rho.astype(dt),
            layout=self.layout,
        )

    def snapshot(self, dtype=None) -> "FlatPosterior":
        """A DECOUPLED copy of both buffers (optionally resident in a
        narrower dtype — ``core.numerics`` wire-dtype names; ``"bf16"``
        halves the snapshot HBM).  This is the publish half of the serving
        tier's double buffer (``repro.serve``): the returned posterior
        shares no storage with the training buffers, so subsequent training
        updates can never change what a reader serves, and the copy only
        READS the live buffers — a training run with a snapshot reader
        attached stays bitwise identical to one without."""
        from repro.core.numerics import canonical_wire_dtype

        dt = canonical_wire_dtype(dtype)
        return FlatPosterior(
            mean=jnp.array(self.mean, dtype=dt, copy=True),
            rho=jnp.array(self.rho, dtype=dt, copy=True),
            layout=self.layout,
        )

    def to_pytree(self):
        """-> ``GaussianPosterior`` over the original parameter pytree."""
        from repro.core.posterior import GaussianPosterior

        return GaussianPosterior(
            mean=self.layout.unflatten(self.mean),
            rho=self.layout.unflatten(self.rho.astype(COMPUTE_DTYPE)),
        )


def flat_posterior_from_pytree(post, layout: FlatLayout | None = None,
                               leading_axes: int = 1) -> FlatPosterior:
    """``GaussianPosterior`` (leaves [*B, ...]) -> ``FlatPosterior``.

    Pass a prebuilt ``layout`` to skip re-deriving it (it never changes for a
    fixed model, so build it once at setup time)."""
    if layout is None:
        layout = FlatLayout.for_pytree(post.mean, leading_axes=leading_axes)
    return FlatPosterior(
        mean=layout.flatten(post.mean), rho=layout.flatten(post.rho), layout=layout
    )


def init_flat_posterior(
    params: PyTree,
    init_sigma: float = 0.05,
    layout: FlatLayout | None = None,
    leading_axes: int = 0,
) -> FlatPosterior:
    """Flat analogue of ``init_posterior``: mean = flatten(params), constant
    rho = softplus^-1(init_sigma)."""
    if layout is None:
        layout = FlatLayout.for_pytree(params, leading_axes=leading_axes)
    mean = layout.flatten(params)
    rho = jnp.full_like(mean, softplus_inv_py(init_sigma))
    return FlatPosterior(mean=mean, rho=rho, layout=layout)


def make_flat_nll(nll_fn: Callable[[PyTree, Any], jax.Array], layout: FlatLayout):
    """Wrap a pytree-parameter nll into one taking a flat theta [P] — the
    single model-apply-boundary conversion of the flat runtime."""

    def flat_nll(theta_flat: jax.Array, batch: Any, *shared) -> jax.Array:
        return nll_fn(layout.unflatten(theta_flat), batch, *shared)

    return flat_nll


# ---------------------------------------------------------------------------
# Network-wide consensus over the flat buffers
# ---------------------------------------------------------------------------


XLA_BLOCK = 16384  # CPU cache-blocking width (lanes) for the XLA path
_MAX_UNROLL = 256  # cap on unrolled column blocks (graph-size guard)


def _eq6_block(W, mean, rho, wire_dtype=jnp.float32):
    """Eq. (6) on one [N, BLOCK] column block (identical math to the Pallas
    network kernel body, including the exchange-boundary wire rounding —
    ``wire_roundtrip`` is a structural no-op at f32)."""
    prec = 1.0 / jnp.square(softplus(rho))
    prec_x = wire_roundtrip(prec, wire_dtype)
    pm_x = wire_roundtrip(prec * mean, wire_dtype)
    new_prec = jnp.matmul(W, prec_x, precision=EXCHANGE_PRECISION,
                          preferred_element_type=COMPUTE_DTYPE)
    new_pm = jnp.matmul(W, pm_x, precision=EXCHANGE_PRECISION,
                        preferred_element_type=COMPUTE_DTYPE)
    return new_pm / new_prec, softplus_inv(jax.lax.rsqrt(new_prec))


def consensus_flat_reference(
    mean: jax.Array,
    rho: jax.Array,
    W: jax.Array,
    block: int = XLA_BLOCK,
    active: jax.Array | None = None,
    wire_dtype=None,
) -> tuple[jax.Array, jax.Array]:
    """Eq. (6) on the flat [N, P] buffers — the reference semantics for the
    Pallas kernels and the fast non-TPU path.

    Processed in unrolled column blocks of ``block`` lanes, assembled with
    ``dynamic_update_slice`` (in-place after XLA copy elision): the block
    intermediates stay cache-resident and independent blocks schedule across
    CPU threads — a monolithic [N, P] matmul pair spills its intermediates
    to DRAM and measures ~2x slower, and a ``concatenate`` assembly costs
    more than the whole computation (measured on XLA:CPU; see
    BENCH_consensus.json).  Math is bitwise identical per block.

    ``active`` (the gossip event-window form, see
    ``consensus_flat_masked_reference``) selects per block between the
    computed row (active agents) and the ORIGINAL (mean, rho) row
    (inactive agents pass through bitwise); ``None`` adds no select at all.
    ``wire_dtype`` rounds (prec, prec*mu) at the exchange boundary
    (``kernels.consensus`` module docstring); f32/None is bitwise the
    uncompressed path.
    """
    wire_dtype = canonical_wire_dtype(wire_dtype)
    act = None if active is None else (active > 0)[:, None]

    def blk(m_in, r_in):
        m_o, r_o = _eq6_block(W, m_in, r_in, wire_dtype)
        if act is None:
            return m_o, r_o
        return jnp.where(act, m_o, m_in), jnp.where(act, r_o, r_in)

    n, p = mean.shape
    if p <= block:
        return blk(mean, rho)
    n_blocks = -(-p // block)
    if n_blocks > _MAX_UNROLL:
        block = -(-p // _MAX_UNROLL)
    mean_out = jnp.empty_like(mean)
    rho_out = jnp.empty_like(rho)
    for s in range(0, p, block):
        e = min(s + block, p)
        m_o, r_o = blk(mean[:, s:e], rho[:, s:e])
        mean_out = jax.lax.dynamic_update_slice(mean_out, m_o, (0, s))
        rho_out = jax.lax.dynamic_update_slice(rho_out, r_o, (0, s))
    return mean_out, rho_out


def consensus_flat(
    posts: FlatPosterior,
    W: jax.Array,
    *,
    mode: str | None = None,
    block: int | None = None,
    wire_dtype=None,
) -> FlatPosterior:
    """Single fused network-wide consensus (eq. 6) on a ``FlatPosterior``.

    mode:
      None        auto — Pallas kernel on TPU, fused XLA einsum elsewhere
      "pallas"    the Pallas network kernel (compiled on TPU, interpreted
                  elsewhere — SLOW off-TPU, correctness checks only)
      "interpret" force the Pallas interpreter
      "xla"       force the fused XLA reference path

    ``wire_dtype`` (``None`` | ``"f32"|"bf16"|"f16"`` | dtype) rounds the
    exchanged (prec, prec*mu) through the wire dtype on every mode —
    f32/None is bitwise the uncompressed path (ROADMAP "Wire precision").
    """
    from repro.kernels.consensus import consensus_fused_network

    if mode is None:
        mode = "pallas" if jax.default_backend() == "tpu" else "xla"
    if mode == "xla":
        mean, rho = consensus_flat_reference(
            posts.mean, posts.rho, W,
            block=(XLA_BLOCK if block is None else block),
            wire_dtype=wire_dtype,
        )
    elif mode in ("pallas", "interpret"):
        mean, rho = consensus_fused_network(
            W, posts.mean, posts.rho,
            block=block,
            interpret=(True if mode == "interpret" else None),
            wire_dtype=canonical_wire_dtype(wire_dtype),
        )
    else:
        raise ValueError(f"unknown consensus_flat mode {mode!r}")
    return FlatPosterior(mean=mean, rho=rho, layout=posts.layout)


def consensus_flat_masked_reference(
    mean: jax.Array,
    rho: jax.Array,
    W: jax.Array,
    active: jax.Array,
    block: int = XLA_BLOCK,
    wire_dtype=None,
) -> tuple[jax.Array, jax.Array]:
    """Masked (event-window) eq. (6) on the flat buffers — reference
    semantics for ``consensus_fused_masked`` and the fast non-TPU path.

    The shared blocked loop of ``consensus_flat_reference`` with the
    activity select: active agents get the computed row, inactive ones
    their ORIGINAL (mean, rho) row.  With ``active`` all-true the select is
    the identity on the computed values, so the output is bit-identical to
    the unmasked reference (the gossip/synchronous equivalence contract,
    which ``wire_dtype`` preserves: both paths round at the same exchange
    boundary).
    """
    return consensus_flat_reference(
        mean, rho, W, block=block, active=active, wire_dtype=wire_dtype
    )


def consensus_flat_masked(
    posts: FlatPosterior,
    W: jax.Array,
    active: jax.Array,
    *,
    mode: str | None = None,
    block: int | None = None,
    mesh: Any = None,
    axis: str = "agents",
    window: Any = None,
    wire_dtype=None,
) -> FlatPosterior:
    """Masked network-wide consensus for one gossip event window.

    ``W`` is the window's effective W-tilde and ``active`` its [N] activity
    mask (``repro.gossip.clocks.EventWindow``).  Active agents merge per
    eq. (6); inactive agents pass through bit-identically (no softplus
    round trip — an idle agent's posterior is bit-stable across windows).
    Same mode semantics as ``consensus_flat``, plus the mesh-aware form:

      "ppermute"  execute the window SHARDED over the agent axis of ``mesh``
                  (``launch.consensus_opt.consensus_ppermute_window``): one
                  ``shard_map`` over the [N, P] buffers that ppermutes only
                  the window's fired shard offsets.  Requires ``mesh`` and
                  the ``window`` (its static edge list IS the permutation
                  schedule); bit-identical to the "xla" path by test.

    ``wire_dtype`` rounds the exchanged (prec, prec*mu) on every mode —
    on the ppermute mode the rounded payload IS the ppermuted wire traffic
    (halved ICI bytes at bf16); f32/None is bitwise uncompressed.
    """
    from repro.kernels.consensus import consensus_fused_masked

    if mode == "ppermute":
        from repro.launch.consensus_opt import consensus_ppermute_window

        if mesh is None or window is None:
            raise ValueError(
                "consensus_flat_masked(mode='ppermute') needs mesh= and "
                "window= (the EventWindow's edges are the static "
                "permutation schedule)"
            )
        return consensus_ppermute_window(
            posts, window, mesh, axis,
            block=(XLA_BLOCK if block is None else block),
            wire_dtype=wire_dtype,
        )
    if mode is None:
        mode = "pallas" if jax.default_backend() == "tpu" else "xla"
    if mode == "xla":
        mean, rho = consensus_flat_masked_reference(
            posts.mean, posts.rho, W, active,
            block=(XLA_BLOCK if block is None else block),
            wire_dtype=wire_dtype,
        )
    elif mode in ("pallas", "interpret"):
        mean, rho = consensus_fused_masked(
            W, active, posts.mean, posts.rho,
            block=block,
            interpret=(True if mode == "interpret" else None),
            wire_dtype=canonical_wire_dtype(wire_dtype),
        )
    else:
        raise ValueError(f"unknown consensus_flat_masked mode {mode!r}")
    return FlatPosterior(mean=mean, rho=rho, layout=posts.layout)


def consensus_flat_delayed(
    posts: FlatPosterior,
    W: jax.Array,
    active: jax.Array,
    edges: jax.Array,
    weights: jax.Array,
    lags: jax.Array,
    hist_mean: jax.Array,
    hist_rho: jax.Array,
    round_idx: jax.Array,
    wire_dtype=None,
) -> FlatPosterior:
    """Delivery-latency eq. (6): one gossip window whose events merge STALE
    source posteriors (``repro.gossip.clocks.DelayedClock``).

    Event k = ``(dst, src) = edges[k]`` with mixing weight ``weights[k]``
    delivers src's posterior as of fire time — window ``round_idx -
    lags[k]`` — read from the [K, N, P] history ring buffer (slot ``r mod
    K``; the engine writes each window's post-local-step, pre-merge
    posterior into its slot BEFORE calling this, so a lag-0 event reads the
    current posterior and the all-lags-zero window reproduces the instant-
    delivery semantics).  Per eq. (6) each active dst accumulates

        prec_out[dst] = W[dst,dst] * prec_now[dst]
                        + sum_k w_k * prec(hist[slot_k, src_k])

    via a segment scatter-add over the static [E_max] event list (pad slots
    carry weight 0.0 and contribute exactly nothing); inactive rows pass
    through bitwise as in ``consensus_flat_masked``.

    ``wire_dtype`` rounds every accumulated (prec, prec*mu) contribution —
    the delivered stale statistics AND the self term, mirroring the dense
    kernels where the whole buffer crosses the exchange boundary — and the
    scatter-add accumulates fp32.  The history ring may be resident in a
    narrower dtype (``GossipEngine`` ``history_dtype``); gathered rows are
    decoded to fp32 before any math.  f32/None is bitwise uncompressed.
    """
    wire_dtype = canonical_wire_dtype(wire_dtype)
    k_slots = hist_mean.shape[0]
    slot = jnp.mod(round_idx - lags, k_slots)  # [E]
    dst, src = edges[:, 0], edges[:, 1]
    # decode from the (possibly bf16-resident) history ring; no-op at f32
    h_mean = hist_mean[slot, src].astype(COMPUTE_DTYPE)  # [E, P] stale rows
    h_rho = hist_rho[slot, src].astype(COMPUTE_DTYPE)
    prec_e = 1.0 / jnp.square(softplus(h_rho))
    w_e = weights[:, None].astype(COMPUTE_DTYPE)
    prec_now = 1.0 / jnp.square(softplus(posts.rho))
    diag = jnp.diagonal(W)[:, None].astype(COMPUTE_DTYPE)
    if wire_dtype == jnp.float32:
        # pre-wire op order, verbatim — f32 stays bitwise identical
        acc_prec = (diag * prec_now).at[dst].add(w_e * prec_e)
        acc_pm = (diag * prec_now * posts.mean).at[dst].add(
            w_e * prec_e * h_mean
        )
    else:
        prec_now_x = wire_roundtrip(prec_now, wire_dtype)
        pm_now_x = wire_roundtrip(prec_now * posts.mean, wire_dtype)
        prec_e_x = wire_roundtrip(prec_e, wire_dtype)
        pm_e_x = wire_roundtrip(prec_e * h_mean, wire_dtype)
        acc_prec = (diag * prec_now_x).at[dst].add(w_e * prec_e_x)
        acc_pm = (diag * pm_now_x).at[dst].add(w_e * pm_e_x)
    act = (active > 0)[:, None]
    mean_out = jnp.where(act, acc_pm / acc_prec, posts.mean)
    rho_out = jnp.where(
        act, softplus_inv(jax.lax.rsqrt(acc_prec)), posts.rho
    )
    return FlatPosterior(mean=mean_out, rho=rho_out, layout=posts.layout)


# Peak [E, BLOCK] gather intermediate cap for the segment path (elements).
# 2^24 f32 elements = 64 MiB per buffer — cache-friendly on CPU, far below
# any [N, N] materialization at the population scales this path serves.
_SEGMENT_GATHER_ELEMS = 1 << 24


def segments_mode(n_agents: int, slots: int | None, wire_dtype=None) -> str:
    """The execution ``consensus_flat_segments`` runs for ``mode=None``.

    "pallas" — the destination-major row gather (``gather_tables`` into
    ``kernels.consensus.consensus_fused_masked_sparse``) — on TPU, when the
    caller bounds every row's entries by ``slots``, the [N, slots] tables
    fit the kernel's scalar memory (N x slots <= ``SPARSE_TABLE_ENTRIES``)
    and the wire is not f16 (which the compiled kernels refuse).  "xla" —
    the blocked segment sum — everywhere else: off TPU, without a row
    bound, and at N = 10^4+ populations."""
    from repro.kernels.consensus import SPARSE_TABLE_ENTRIES
    from repro.kernels.dispatch import on_tpu

    if (slots is not None and on_tpu()
            and n_agents * slots <= SPARSE_TABLE_ENTRIES
            and canonical_wire_dtype(wire_dtype) != jnp.float16):
        return "pallas"
    return "xla"


def gather_tables(
    dst: jax.Array,
    src: jax.Array,
    weights: jax.Array,
    n_agents: int,
    slots: int,
    active: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Destination-major ``[N, slots]`` (neighbour id, weight) tables of an
    edge list, for the row-gather kernel.

    Row i lists the sources of i's nonzero-weight edges in edge order —
    the callers append the self-loops after the fired edges, so a row
    reads: fired sources, then its own id carrying the self weight — then
    its own id at weight 0 in every remaining slot, so consecutive padding
    steps name one row and the kernel fetches it once.  Zero-weight pad
    edges are dropped; a row whose ``active`` is false is all own id at
    weight 0 (the kernel passes it through untouched).  ``slots`` must
    bound every row's entry count: a longer row would lose its last
    entries.  Built in-graph from a stable sort of the E keys and gathers —
    no scatter, nothing O(N^2)."""
    n_edges = dst.shape[0]
    w = weights.astype(COMPUTE_DTYPE)
    keep = w != 0.0
    if active is not None:
        keep = keep & (active > 0)[dst]
    key = jnp.where(keep, dst.astype(jnp.int32), n_agents)
    order = jnp.argsort(key, stable=True)
    key_sorted = key[order]
    rows = jnp.arange(n_agents + 1, dtype=jnp.int32)
    bounds = jnp.searchsorted(key_sorted, rows)  # row i: [bounds[i], bounds[i+1])
    start, count = bounds[:-1], jnp.diff(bounds)
    slot = jnp.arange(slots, dtype=jnp.int32)[None, :]
    real = slot < count[:, None]
    at = order[jnp.minimum(start[:, None] + slot, n_edges - 1)]
    nbr = jnp.where(real, src.astype(jnp.int32)[at], rows[:-1, None])
    return nbr, jnp.where(real, w[at], 0.0)


def consensus_flat_segments(
    posts: FlatPosterior,
    dst: jax.Array,
    src: jax.Array,
    weights: jax.Array,
    *,
    active: jax.Array | None = None,
    block: int | None = None,
    wire_dtype=None,
    slots: int | None = None,
    mode: str | None = None,
) -> FlatPosterior:
    """Edge-native eq. (6): consensus over flat [E] edge arrays.

    The sparse-first counterpart of ``consensus_flat_reference`` — the graph
    arrives as ``(dst, src, weights)`` edge lists (self-loops INCLUDED, e.g.
    ``SparseGraph.edge_arrays()``), never as a dense ``[N, N]`` W:

        prec_out[i] = sum_{e: dst_e = i} w_e * prec_x[src_e]
        pm_out[i]   = sum_{e: dst_e = i} w_e * (prec * mu)_x[src_e]

    with the (prec, prec*mu) buffers rounded through ``wire_dtype`` at the
    exchange boundary exactly as in ``_eq6_block`` (structural no-op at
    f32) and fp32 accumulation throughout.

    mode (``None``: ``segments_mode(N, slots, wire_dtype)``):
      "xla"       per lane block, gather each edge's source statistics and
                  scatter-add (``segment_sum``) them into the destination
                  rows.  Peak memory O(E * block): the default ``block``
                  shrinks with E so the gather intermediate stays under
                  ``_SEGMENT_GATHER_ELEMS`` elements.
      "pallas"    the destination-major row gather: ``gather_tables``
                  (``slots`` entries a row, required) and one
                  ``consensus_fused_masked_sparse`` call, in which each
                  agent reads its sources' rows and its own, accumulates in
                  fp32 VMEM and writes its row once; ``block`` is the
                  kernel's lane block.
      "interpret" the same kernel in the Pallas interpreter.
    No mode is O(N^2).  The row gather accumulates a row's terms in edge
    order, as the scatter does.

    Agrees with the dense reference elementwise to fp32 reduction-order
    tolerance on every wire dtype (the scatter accumulates in edge order,
    the matmul in column order); rows whose accumulation is a single term
    and the wire-rounded exchange values themselves are bitwise identical.

    Zero-weight pad edges (any valid dst/src) contribute exactly nothing,
    matching the ``consensus_flat_delayed`` event-list convention.
    ``active`` masks rows gossip-style: inactive rows pass through bitwise.
    """
    wire_dtype = canonical_wire_dtype(wire_dtype)
    n, p = posts.mean.shape
    if mode is None:
        mode = segments_mode(n, slots, wire_dtype)
    if mode in ("pallas", "interpret"):
        from repro.kernels.consensus import consensus_fused_masked_sparse

        if slots is None:
            raise ValueError(
                "the row-gather segments execution needs slots, a bound on "
                "every row's entries (max in-degree + 1 with self-loops)"
            )
        nbr, wts = gather_tables(dst, src, weights, n, slots, active)
        act = jnp.ones((n,), jnp.int32) if active is None else active
        mean, rho = consensus_fused_masked_sparse(
            nbr, wts, act, posts.mean, posts.rho, block=block,
            interpret=(True if mode == "interpret" else None),
            wire_dtype=wire_dtype,
        )
        return FlatPosterior(mean=mean, rho=rho, layout=posts.layout)
    if mode != "xla":
        raise ValueError(f"unknown consensus_flat_segments mode {mode!r}")
    n_edges = int(dst.shape[0])
    w_e = weights[:, None].astype(COMPUTE_DTYPE)
    act = None if active is None else (active > 0)[:, None]
    if block is None:
        block = max(128, min(XLA_BLOCK, _SEGMENT_GATHER_ELEMS // max(n_edges, 1)))

    def blk(m_in, r_in):
        prec = 1.0 / jnp.square(softplus(r_in))
        prec_x = wire_roundtrip(prec, wire_dtype)
        pm_x = wire_roundtrip(prec * m_in, wire_dtype)
        acc_prec = jnp.zeros_like(prec).at[dst].add(w_e * prec_x[src])
        acc_pm = jnp.zeros_like(prec).at[dst].add(w_e * pm_x[src])
        m_o = acc_pm / acc_prec
        r_o = softplus_inv(jax.lax.rsqrt(acc_prec))
        if act is None:
            return m_o, r_o
        return jnp.where(act, m_o, m_in), jnp.where(act, r_o, r_in)

    if p <= block:
        mean_out, rho_out = blk(posts.mean, posts.rho)
        return FlatPosterior(mean=mean_out, rho=rho_out, layout=posts.layout)
    n_blocks = -(-p // block)
    if n_blocks > _MAX_UNROLL:
        block = -(-p // _MAX_UNROLL)
    mean_out = jnp.empty_like(posts.mean)
    rho_out = jnp.empty_like(posts.rho)
    for s in range(0, p, block):
        e = min(s + block, p)
        m_o, r_o = blk(posts.mean[:, s:e], posts.rho[:, s:e])
        mean_out = jax.lax.dynamic_update_slice(mean_out, m_o, (0, s))
        rho_out = jax.lax.dynamic_update_slice(rho_out, r_o, (0, s))
    return FlatPosterior(mean=mean_out, rho=rho_out, layout=posts.layout)


def consensus_flat_masked_sparse(
    posts: FlatPosterior,
    neighbors: jax.Array,
    weights: jax.Array,
    active: jax.Array,
    *,
    mode: str | None = None,
    block: int | None = None,
    wire_dtype=None,
) -> FlatPosterior:
    """Active-edge window consensus on CSR tables of the window's W-tilde
    (``neighbor_tables(window.w_eff)``): active agents read only their
    fired-neighbor rows, inactive agents copy their own row.  The "xla"
    path rebuilds the tiny dense W-tilde (reference semantics); the
    active-edge HBM saving exists on the Pallas path.  ``wire_dtype``
    rounds the gathered (prec, prec*mu) at the exchange boundary."""
    from repro.kernels.consensus import consensus_fused_masked_sparse

    if mode is None:
        mode = "pallas" if jax.default_backend() == "tpu" else "xla"
    if mode == "xla":
        mean, rho = _sparse_reference(
            posts.mean, posts.rho, neighbors, weights,
            block=(XLA_BLOCK if block is None else block), active=active,
            wire_dtype=wire_dtype,
        )
    elif mode in ("pallas", "interpret"):
        mean, rho = consensus_fused_masked_sparse(
            neighbors, weights, active, posts.mean, posts.rho,
            block=block,
            interpret=(True if mode == "interpret" else None),
            wire_dtype=canonical_wire_dtype(wire_dtype),
        )
    else:
        raise ValueError(f"unknown consensus_flat_masked_sparse mode {mode!r}")
    return FlatPosterior(mean=mean, rho=rho, layout=posts.layout)


def neighbor_tables(W: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """CSR-style padded neighbor tables for ``consensus_fused_sparse``.

    Returns (neighbors [N, D] int32, weights [N, D] float32), D = max
    in-degree.  Zero-weight entries of W are skipped; ragged rows are padded
    with the agent's own id at weight 0.0 (reads a tile the agent already
    touches, contributes nothing).  Host-side/static: call once per topology,
    not per round.

    Delegates to the one CSR construction
    (``graphs.SparseGraph.from_dense(...).neighbor_tables()``) shared with
    ``graphs.neighbor_lists`` / ``graphs.max_in_degree`` — sparse-native
    callers skip the dense bridge and call the method on their
    ``SparseGraph`` directly.
    """
    return _graphs.SparseGraph.from_dense(np.asarray(W)).neighbor_tables()


def _sparse_reference(mean, rho, neighbors, weights, block: int = XLA_BLOCK,
                      active=None, wire_dtype=None):
    """Sparse reference path: rebuild the (tiny, [N, N]) dense W from the
    neighbor tables and reuse the blocked dense path.  Bitwise-identical
    semantics (zero-weight entries contribute nothing; self-padded slots
    scatter-add 0.0 onto the diagonal), and far faster than row-gathers on
    XLA:CPU, whose gather lowers to a scalar loop.  The true deg(i)-tile
    HBM saving only exists on the Pallas path (mode="pallas" on TPU).
    ``active`` is the gossip event-window mask (see
    ``consensus_flat_reference``)."""
    n = mean.shape[0]
    rows = jnp.broadcast_to(jnp.arange(n, dtype=neighbors.dtype)[:, None], neighbors.shape)
    W = jnp.zeros((n, n), COMPUTE_DTYPE).at[rows, neighbors].add(weights)
    return consensus_flat_reference(
        mean, rho, W, block=block, active=active, wire_dtype=wire_dtype
    )


# ---------------------------------------------------------------------------
# Quarantine guard: fault-tolerant consensus (ROADMAP "Robustness")
# ---------------------------------------------------------------------------

# An exchanged |prec| or |prec*mu| lane above this is garbage regardless of
# finiteness (the "huge" corruption kind stays finite on purpose): a prec of
# 1e20 is a sigma of 1e-10 — far outside any posterior this runtime reaches.
QUARANTINE_BOUND = 1e20


def payload_validity(
    mean: jax.Array,
    rho: jax.Array,
    *,
    wire_dtype=None,
    bound: float = QUARANTINE_BOUND,
    mode: str | None = None,
    block: int | None = None,
) -> jax.Array:
    """[N] bool: is each agent's exchanged (prec, prec*mu) payload sane?

    The check runs ON THE WIRE REPRESENTATION — the rounded statistics a
    receiver actually sees (``wire_roundtrip``; structural no-op at f32):
    every lane must be finite, ``prec`` strictly positive, and both
    magnitudes within ``bound``.  This is the exchange-boundary guard the
    quarantined consensus wrappers apply to every incoming contribution; a
    single NaN/Inf/huge lane flags the whole agent (one poisoned lane
    already ruins its row of eq. (6)).

    mode: None auto (Pallas on TPU, XLA elsewhere) | "xla" | "pallas" |
    "interpret" — the fused kernel is pinned bit-equal to the reference.
    """
    wire_dtype = canonical_wire_dtype(wire_dtype)
    if mode is None:
        mode = "pallas" if jax.default_backend() == "tpu" else "xla"
    if mode not in ("xla", "pallas", "interpret"):
        raise ValueError(f"unknown payload_validity mode {mode!r}")
    with jax.named_scope("fault_guard"):
        if mode == "xla":
            prec = 1.0 / jnp.square(softplus(rho))
            prec_x = wire_roundtrip(prec, wire_dtype)
            pm_x = wire_roundtrip(prec * mean, wire_dtype)
            ok = (
                jnp.isfinite(prec_x)
                & (prec_x > 0.0)
                & (prec_x <= bound)
                & jnp.isfinite(pm_x)
                & (jnp.abs(pm_x) <= bound)
            )
            return jnp.all(ok, axis=-1)
        from repro.kernels.consensus import payload_validity_fused

        return payload_validity_fused(
            mean, rho,
            bound=bound,
            block=block,
            interpret=(True if mode == "interpret" else None),
            wire_dtype=wire_dtype,
        )


def quarantine_w(W: jax.Array, valid: jax.Array) -> jax.Array:
    """Zero every column of an invalid source and move the dropped row mass
    onto self — rows stay row-stochastic, mirroring the clock layer's
    ``"conserve"`` rule for crashed agents.  The self column survives even
    for an invalid agent (its own row is restored post-consensus anyway).
    With ``valid`` all-True the result is value-identical to ``W``."""
    n = W.shape[0]
    eye = jnp.eye(n, dtype=bool)
    keep = valid[None, :] | eye
    Wk = jnp.where(keep, W, 0.0)
    dropped = jnp.sum(W - Wk, axis=1)
    return Wk.at[jnp.arange(n), jnp.arange(n)].add(dropped)


def _sanitized_sources(posts, mean_src, rho_src, valid_src, valid_self):
    """Exchange-side (mean, rho) with every invalid payload replaced by a
    finite placeholder.  Zeroing an invalid source's W column is NOT enough:
    ``0 * NaN = NaN`` still poisons the contraction, so the buffer rows
    behind zeroed weights must be finite too.  A corrupted-but-healthy
    sender falls back to its TRUE resident statistics (its self term stays
    truthful); an agent whose resident state is itself garbage gets a
    neutral (0, rho=1) row that only ever multiplies zero weight."""
    v_src = valid_src[:, None]
    v_self = valid_self[:, None]
    safe_mean = jnp.where(v_self, posts.mean, 0.0)
    safe_rho = jnp.where(v_self, posts.rho, 1.0)
    mean_x = jnp.where(v_src, mean_src, safe_mean)
    rho_x = jnp.where(v_src, rho_src, safe_rho)
    return mean_x, rho_x


def consensus_flat_masked_quarantined(
    posts: FlatPosterior,
    W: jax.Array,
    active: jax.Array,
    *,
    mean_src: jax.Array | None = None,
    rho_src: jax.Array | None = None,
    mode: str | None = None,
    block: int | None = None,
    mesh: Any = None,
    axis: str = "agents",
    window: Any = None,
    wire_dtype=None,
    bound: float = QUARANTINE_BOUND,
) -> tuple[FlatPosterior, jax.Array]:
    """Quarantine-guarded ``consensus_flat_masked``: validate every incoming
    contribution at the exchange boundary, drop invalid ones, move their row
    mass to self.  Returns ``(posterior, valid_src [N] bool)``.

    ``mean_src``/``rho_src`` are the statistics agents actually TRANSMIT
    (default: the resident ``posts`` buffers) — the fault-injection hook:
    the engine passes corrupted copies here while ``posts`` stays the
    resident truth.  The guard:

    * ``valid_src`` — wire-payload sanity of each transmission
      (``payload_validity``); invalid sources are dropped from every row
      (``quarantine_w``) and their buffer rows sanitized (``0 * NaN = NaN``
      would otherwise leak through the matmul);
    * a corrupted sender still MERGES (it is a bad transmitter, not a bad
      receiver): its own row mixes its true self term with its valid
      in-edges;
    * an agent whose RESIDENT state is invalid is excluded from merging
      and passes through unchanged (``Session.health`` flags it).

    With zero faults (all payloads valid) every branch is a value-identity
    (``where(True, x, .) = x``, ``W + 0 = W``), so the output is BITWISE
    identical to the unguarded path on every mode — the equivalence-ladder
    rung ``fault_policy="quarantine"`` == ``"strict"``.
    """
    mean_src = posts.mean if mean_src is None else mean_src
    rho_src = posts.rho if rho_src is None else rho_src
    # the validity probe follows the consensus mode: auto (None) runs the
    # fused kernel on TPU; the sharded ppermute buffers take the XLA probe
    vmode = "xla" if mode in ("xla", "ppermute") else mode
    valid_src = payload_validity(
        mean_src, rho_src, wire_dtype=wire_dtype, bound=bound, mode=vmode
    )
    valid_self = payload_validity(
        posts.mean, posts.rho, wire_dtype=wire_dtype, bound=bound, mode=vmode
    )
    mean_x, rho_x = _sanitized_sources(
        posts, mean_src, rho_src, valid_src, valid_self
    )
    posts_x = FlatPosterior(mean=mean_x, rho=rho_x, layout=posts.layout)
    W_g = quarantine_w(jnp.asarray(W, COMPUTE_DTYPE), valid_src)
    act_g = (active > 0) & valid_self
    if mode == "ppermute":
        from repro.launch.consensus_opt import consensus_ppermute_window

        if mesh is None or window is None:
            raise ValueError(
                "consensus_flat_masked_quarantined(mode='ppermute') needs "
                "mesh= and window="
            )
        out = consensus_ppermute_window(
            posts_x, window, mesh, axis,
            block=(XLA_BLOCK if block is None else block),
            wire_dtype=wire_dtype,
            w_eff=W_g, active=act_g,
        )
    else:
        out = consensus_flat_masked(
            posts_x, W_g, act_g,
            mode=mode, block=block, wire_dtype=wire_dtype,
        )
    v_self = valid_self[:, None]
    return (
        FlatPosterior(
            mean=jnp.where(v_self, out.mean, posts.mean),
            rho=jnp.where(v_self, out.rho, posts.rho),
            layout=posts.layout,
        ),
        valid_src,
    )


def consensus_flat_masked_sparse_quarantined(
    posts: FlatPosterior,
    neighbors: jax.Array,
    weights: jax.Array,
    active: jax.Array,
    *,
    mean_src: jax.Array | None = None,
    rho_src: jax.Array | None = None,
    mode: str | None = None,
    block: int | None = None,
    wire_dtype=None,
    bound: float = QUARANTINE_BOUND,
) -> tuple[FlatPosterior, jax.Array]:
    """Quarantine-guarded ``consensus_flat_masked_sparse``: the CSR-table
    form of the dense guard.  Table STRUCTURE stays static (same neighbor
    ids — gathering a sanitized zero-weight row is harmless); only the
    weights adjust in-graph: invalid non-self slots drop to 0.0 and each
    row's dropped mass lands on its real self slot.  Zero faults is a
    value-identity, as in the dense wrapper."""
    mean_src = posts.mean if mean_src is None else mean_src
    rho_src = posts.rho if rho_src is None else rho_src
    valid_src = payload_validity(
        mean_src, rho_src, wire_dtype=wire_dtype, bound=bound, mode=mode
    )
    valid_self = payload_validity(
        posts.mean, posts.rho, wire_dtype=wire_dtype, bound=bound, mode=mode
    )
    mean_x, rho_x = _sanitized_sources(
        posts, mean_src, rho_src, valid_src, valid_self
    )
    n = posts.mean.shape[0]
    rows = jnp.arange(n, dtype=neighbors.dtype)[:, None]
    self_mask = neighbors == rows
    keep = valid_src[neighbors] | self_mask
    wts_k = jnp.where(keep, weights, 0.0)
    dropped = jnp.sum(weights - wts_k, axis=1)
    # each row's REAL self entry (nonzero weight; pad slots are self at 0.0
    # and must not receive mass) absorbs the dropped in-weights
    self_slot = jnp.argmax(self_mask & (weights > 0.0), axis=1)
    wts_g = wts_k.at[jnp.arange(n), self_slot].add(dropped)
    act_g = (active > 0) & valid_self
    out = consensus_flat_masked_sparse(
        FlatPosterior(mean=mean_x, rho=rho_x, layout=posts.layout),
        neighbors, wts_g, act_g,
        mode=mode, block=block, wire_dtype=wire_dtype,
    )
    v_self = valid_self[:, None]
    return (
        FlatPosterior(
            mean=jnp.where(v_self, out.mean, posts.mean),
            rho=jnp.where(v_self, out.rho, posts.rho),
            layout=posts.layout,
        ),
        valid_src,
    )


def consensus_flat_delayed_quarantined(
    posts: FlatPosterior,
    W: jax.Array,
    active: jax.Array,
    edges: jax.Array,
    weights: jax.Array,
    lags: jax.Array,
    hist_mean: jax.Array,
    hist_rho: jax.Array,
    round_idx: jax.Array,
    *,
    corrupt: jax.Array | None = None,
    fill_mean: jax.Array | None = None,
    fill_rho: jax.Array | None = None,
    wire_dtype=None,
    bound: float = QUARANTINE_BOUND,
) -> tuple[FlatPosterior, jax.Array]:
    """Quarantine-guarded ``consensus_flat_delayed``: validate each DELIVERED
    event's stale (prec, prec*mu) contribution, drop invalid events (their
    weight moves to the dst's self term), keep agents with garbage resident
    state out of the merge.  Returns ``(posterior, valid_event [E] bool)``.

    ``corrupt``/``fill_mean``/``fill_rho`` ([N] arrays) inject sender-side
    corruption into the gathered history rows by src id — applied at
    DELIVERY time (the history ring itself stays clean; a flaky sender
    garbles whatever it transmits, however old).  Zero faults (no corrupt
    mask, all-finite history) is a value-identity against
    ``consensus_flat_delayed`` — the f32 branch keeps its op order verbatim.
    """
    wire_dtype = canonical_wire_dtype(wire_dtype)
    k_slots = hist_mean.shape[0]
    slot = jnp.mod(round_idx - lags, k_slots)
    dst, src = edges[:, 0], edges[:, 1]
    h_mean = hist_mean[slot, src].astype(COMPUTE_DTYPE)
    h_rho = hist_rho[slot, src].astype(COMPUTE_DTYPE)
    if corrupt is not None:
        bad = corrupt[src][:, None]
        h_mean = jnp.where(bad, fill_mean[src][:, None], h_mean)
        h_rho = jnp.where(bad, fill_rho[src][:, None], h_rho)
    prec_e = 1.0 / jnp.square(softplus(h_rho))
    w_e = weights[:, None].astype(COMPUTE_DTYPE)
    prec_now = 1.0 / jnp.square(softplus(posts.rho))
    diag = jnp.diagonal(W)[:, None].astype(COMPUTE_DTYPE)

    # per-event wire-payload sanity of the delivered contribution
    prec_e_x = wire_roundtrip(prec_e, wire_dtype)
    pm_e_x = wire_roundtrip(prec_e * h_mean, wire_dtype)
    ok_e = (
        jnp.isfinite(prec_e_x)
        & (prec_e_x > 0.0)
        & (prec_e_x <= bound)
        & jnp.isfinite(pm_e_x)
        & (jnp.abs(pm_e_x) <= bound)
    )
    valid_e = jnp.all(ok_e, axis=-1)  # [E]
    v_e = valid_e[:, None]
    # dropped events: weight to the dst's self term, rows sanitized so the
    # zero weight never multiplies a non-finite lane
    w_e_g = jnp.where(v_e, w_e, 0.0)
    drop = jnp.zeros((posts.mean.shape[0], 1), COMPUTE_DTYPE).at[dst].add(
        w_e - w_e_g
    )
    diag_g = diag + drop
    prec_e = jnp.where(v_e, prec_e, 1.0)
    h_mean = jnp.where(v_e, h_mean, 0.0)
    valid_self = payload_validity(
        posts.mean, posts.rho, wire_dtype=wire_dtype, bound=bound, mode="xla"
    )
    if wire_dtype == jnp.float32:
        acc_prec = (diag_g * prec_now).at[dst].add(w_e_g * prec_e)
        acc_pm = (diag_g * prec_now * posts.mean).at[dst].add(
            w_e_g * prec_e * h_mean
        )
    else:
        prec_now_x = wire_roundtrip(prec_now, wire_dtype)
        pm_now_x = wire_roundtrip(prec_now * posts.mean, wire_dtype)
        prec_e_x = wire_roundtrip(prec_e, wire_dtype)
        pm_e_x = wire_roundtrip(prec_e * h_mean, wire_dtype)
        acc_prec = (diag_g * prec_now_x).at[dst].add(w_e_g * prec_e_x)
        acc_pm = (diag_g * pm_now_x).at[dst].add(w_e_g * pm_e_x)
    act = (active > 0) & valid_self
    act = act[:, None]
    mean_out = jnp.where(act, acc_pm / acc_prec, posts.mean)
    rho_out = jnp.where(
        act, softplus_inv(jax.lax.rsqrt(acc_prec)), posts.rho
    )
    return (
        FlatPosterior(mean=mean_out, rho=rho_out, layout=posts.layout),
        valid_e,
    )


def consensus_flat_segments_quarantined(
    posts: FlatPosterior,
    dst: jax.Array,
    src: jax.Array,
    weights: jax.Array,
    self_weight: jax.Array,
    *,
    active: jax.Array,
    mean_src: jax.Array | None = None,
    rho_src: jax.Array | None = None,
    block: int | None = None,
    wire_dtype=None,
    bound: float = QUARANTINE_BOUND,
    slots: int | None = None,
    mode: str | None = None,
) -> tuple[FlatPosterior, jax.Array]:
    """Quarantine-guarded ``consensus_flat_segments`` for edge-native event
    windows (``gossip.clocks.SparseWindow``): validate every FIRED edge's
    wire payload, drop invalid contributions (their weight moves to the
    dst's self term), keep agents with garbage resident state out of the
    merge.  Returns ``(posterior, valid_edge [E] bool)``.

    ``dst``/``src``/``weights`` are the window's fired NON-SELF edges
    (zero-weight pad slots allowed) and ``self_weight`` the per-agent
    conserve-rule self term; the guard adjusts both in-graph and then
    delegates to ``consensus_flat_segments`` over the same
    fired-then-self concatenation the engine's unguarded path builds — so
    with zero faults (all payloads valid) every argument is bitwise the
    unguarded call's and the output is BITWISE identical to it, the same
    equivalence-ladder rung the dense quarantined wrappers pin.

    ``mean_src``/``rho_src`` are the statistics agents actually TRANSMIT
    (the corruption-injection hook; default: the resident ``posts``).
    Mirroring ``consensus_flat_masked_quarantined``: an invalid
    transmission is dropped from every receiving row while the sender's
    own self term falls back to its TRUE resident statistics
    (``_sanitized_sources``); an agent whose RESIDENT state is invalid
    passes through unchanged.  ``slots``/``mode`` pick the execution as in
    ``consensus_flat_segments``.
    """
    wire_dtype = canonical_wire_dtype(wire_dtype)
    mean_src = posts.mean if mean_src is None else mean_src
    rho_src = posts.rho if rho_src is None else rho_src
    valid_src = payload_validity(
        mean_src, rho_src, wire_dtype=wire_dtype, bound=bound, mode="xla"
    )
    valid_self = payload_validity(
        posts.mean, posts.rho, wire_dtype=wire_dtype, bound=bound, mode="xla"
    )
    mean_x, rho_x = _sanitized_sources(
        posts, mean_src, rho_src, valid_src, valid_self
    )
    n = posts.mean.shape[0]
    valid_e = valid_src[src]  # [E] fired-edge wire validity
    w_e = weights.astype(COMPUTE_DTYPE)
    w_e_g = jnp.where(valid_e, w_e, 0.0)
    # dropped in-edge mass lands on the dst's self term — rows stay
    # row-stochastic, the segment form of quarantine_w's diagonal add
    drop = jnp.zeros((n,), COMPUTE_DTYPE).at[dst].add(w_e - w_e_g)
    w_self_g = self_weight.astype(COMPUTE_DTYPE) + drop
    ar = jnp.arange(n, dtype=dst.dtype)
    act_g = (active > 0) & valid_self
    out = consensus_flat_segments(
        FlatPosterior(mean=mean_x, rho=rho_x, layout=posts.layout),
        jnp.concatenate([dst, ar]),
        jnp.concatenate([src, ar]),
        jnp.concatenate([w_e_g, w_self_g]),
        active=act_g, block=block, wire_dtype=wire_dtype,
        slots=slots, mode=mode,
    )
    v_self = valid_self[:, None]
    return (
        FlatPosterior(
            mean=jnp.where(v_self, out.mean, posts.mean),
            rho=jnp.where(v_self, out.rho, posts.rho),
            layout=posts.layout,
        ),
        valid_e,
    )


def consensus_flat_sparse(
    posts: FlatPosterior,
    neighbors: jax.Array,
    weights: jax.Array,
    *,
    mode: str | None = None,
    block: int | None = None,
    wire_dtype=None,
) -> FlatPosterior:
    """Sparse-neighborhood consensus: agents read only their deg(i) neighbor
    rows (Pallas path).  Same mode/block/wire_dtype semantics as
    ``consensus_flat``: the block default is per-mode (XLA cache block vs
    kernel lane block); the "xla" path rebuilds the tiny dense W (reference
    semantics — the deg(i) traffic saving exists only on the Pallas
    path)."""
    from repro.kernels.consensus import consensus_fused_sparse

    if mode is None:
        mode = "pallas" if jax.default_backend() == "tpu" else "xla"
    if mode == "xla":
        mean, rho = _sparse_reference(
            posts.mean, posts.rho, neighbors, weights,
            block=(XLA_BLOCK if block is None else block),
            wire_dtype=wire_dtype,
        )
    elif mode in ("pallas", "interpret"):
        mean, rho = consensus_fused_sparse(
            neighbors, weights, posts.mean, posts.rho,
            block=block,
            interpret=(True if mode == "interpret" else None),
            wire_dtype=canonical_wire_dtype(wire_dtype),
        )
    else:
        raise ValueError(f"unknown consensus_flat_sparse mode {mode!r}")
    return FlatPosterior(mean=mean, rho=rho, layout=posts.layout)
