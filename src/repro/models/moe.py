"""Mixture-of-experts FFNs: top-k with capacity-based dispatch (``moe_ffn``)
and DeepSeekMoE's dropless routed + shared experts (``deepseek_moe``).

Routing: softmax router -> top-k experts per token -> capacity-limited
dispatch (tokens over capacity are dropped, standard Switch/GShard
semantics) -> batched expert SwiGLU via einsum over the expert dim ->
weighted combine.  The expert dim shards over the ``model`` mesh axis
(expert parallelism); under GSPMD the gather/scatter around the expert
einsum lowers to cross-shard collectives.  The hand-scheduled shard_map
all-to-all variant lives in launch/expert_parallel.py (the beyond-paper
optimization in EXPERIMENTS.md §Perf).

Also emits the load-balancing auxiliary loss (Switch-style
E * sum_e f_e * p_e) — the paper-external but production-required router
regularizer.

``deepseek_moe`` (DeepSeek-V2 §2.2) scores experts with a softmax over the
router's logits (float32), takes the greedy top-k, optionally renormalizes
the k weights, scales them by ``routed_scaling_factor`` and adds the
shared experts (one SwiGLU of ``n_shared_experts`` times the expert
width).  Dispatch is dropless: the (token, expert) pairs are sorted by
expert and each expert's SwiGLU runs as grouped matmuls over all of its
tokens: megablox's Pallas kernel (``kernels.grouped_matmul``) on the TPU
where the shapes meet its tiling, ``jax.lax.ragged_dot`` elsewhere
(``expert_matmul_path``).  The expert weights are frozen, so the backward
pass runs the activation-gradient products alone, written out in
``_grouped_swiglu_vjp``: nine grouped products a layer and step with the
recomputed forward.  Under ``vmap`` (the agents of the simulated runtime,
which share the frozen experts) the grouped product sees the tokens of
every batch element at once: one product per layer, not one per agent
(``_flat_over_vmap``).  No auxiliary loss: the router is frozen wherever
this path trains.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.custom_batching import custom_vmap

from repro.kernels import grouped_matmul
from repro.kernels.dispatch import on_tpu
from repro.models.modules import swiglu, swiglu_init, truncated_normal_init


def moe_init(key, cfg):
    ks = jax.random.split(key, 4)
    e, d, f = cfg.n_experts, cfg.d_model, cfg.d_ff
    return {
        "router": truncated_normal_init(ks[0], (d, e), 1.0),
        "w_gate": truncated_normal_init(ks[1], (e, d, f), 1.0),
        "w_up": truncated_normal_init(ks[2], (e, d, f), 1.0),
        "w_down": truncated_normal_init(ks[3], (e, f, d), 1.0),
    }


def route_topk(router_logits: jax.Array, top_k: int):
    """[T, E] -> (weights [T, k], expert_idx [T, k], probs [T, E]).
    Top-k softmax weights renormalized over the selected experts."""
    probs = jax.nn.softmax(router_logits.astype(jnp.float32), axis=-1)
    weights, idx = jax.lax.top_k(probs, top_k)
    weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return weights, idx, probs


def load_balance_loss(probs: jax.Array, idx: jax.Array, n_experts: int) -> jax.Array:
    """Switch-transformer aux loss: E * sum_e (fraction routed to e) * (mean prob e)."""
    t = probs.shape[0]
    counts = jnp.zeros((n_experts,), jnp.float32).at[idx.reshape(-1)].add(1.0)
    frac = counts / (t * idx.shape[-1])
    mean_prob = jnp.mean(probs, axis=0)
    return n_experts * jnp.sum(frac * mean_prob)


def _capacity(n_tokens: int, n_experts: int, top_k: int, factor: float) -> int:
    c = int(n_tokens * top_k * factor / n_experts)
    return max(8, -(-c // 8) * 8)  # round up to 8, floor 8


def moe_ffn(params, x: jax.Array, cfg, dtype=None):
    """x: [B, S, D] -> (y [B, S, D], aux_loss scalar).

    Dispatch is fully static-shaped: for each (expert, capacity-slot) we
    compute the source token index, gather, run the expert batched matmuls,
    and scatter-add back with the router weights.
    """
    dtype = dtype or x.dtype
    b, s, d = x.shape
    t = b * s
    e, k = cfg.n_experts, cfg.top_k
    cap = _capacity(t, e, k, cfg.capacity_factor)
    xt = x.reshape(t, d)

    logits = xt @ params["router"].astype(dtype)  # [T, E]
    weights, idx, probs = route_topk(logits, k)  # [T,k], [T,k], [T,E]
    aux = load_balance_loss(probs, idx, e)

    # position of each (token, k) assignment within its expert's capacity
    onehot = jax.nn.one_hot(idx, e, dtype=jnp.int32)  # [T, k, E]
    flat = onehot.reshape(t * k, e)
    pos_in_expert = jnp.cumsum(flat, axis=0) * flat - 1  # [T*k, E], -1 elsewhere
    slot = jnp.max(pos_in_expert, axis=-1)  # [T*k] slot id (within expert)
    keep = (slot >= 0) & (slot < cap)
    expert_of = idx.reshape(t * k)
    token_of = jnp.repeat(jnp.arange(t), k)
    w_of = weights.reshape(t * k)

    # scatter (expert, slot) -> token index (+1; 0 = empty, token row T is zeros)
    dispatch = jnp.zeros((e, cap), jnp.int32)
    dispatch = dispatch.at[
        jnp.where(keep, expert_of, 0), jnp.where(keep, slot, 0)
    ].max(jnp.where(keep, token_of + 1, 0))
    xt_pad = jnp.concatenate([jnp.zeros((1, d), xt.dtype), xt], axis=0)
    x_disp = xt_pad[dispatch]  # [E, C, D]

    # batched expert SwiGLU: expert dim shards over "model"
    g = jnp.einsum("ecd,edf->ecf", x_disp, params["w_gate"].astype(dtype))
    u = jnp.einsum("ecd,edf->ecf", x_disp, params["w_up"].astype(dtype))
    yd = jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * u, params["w_down"].astype(dtype))

    # combine: scatter-add back to tokens with router weights
    out = jnp.zeros((t + 1, d), jnp.float32)
    gathered = yd[jnp.where(keep, expert_of, 0), jnp.where(keep, slot, 0)]  # [T*k, D]
    contrib = jnp.where(keep[:, None], gathered.astype(jnp.float32) * w_of[:, None], 0.0)
    out = out.at[jnp.where(keep, token_of + 1, 0)].add(contrib)
    return out[1:].astype(dtype).reshape(b, s, d), aux


# ---------------------------------------------------------------------------
# DeepSeekMoE: dropless routed experts + shared experts
# ---------------------------------------------------------------------------


def deepseek_moe_init(key, cfg):
    ks = jax.random.split(key, 5)
    e, d, f = cfg.n_experts, cfg.d_model, cfg.expert_d_ff
    p = {
        "router": truncated_normal_init(ks[0], (d, e), 1.0),
        "w_gate": truncated_normal_init(ks[1], (e, d, f), 1.0),
        "w_up": truncated_normal_init(ks[2], (e, d, f), 1.0),
        "w_down": truncated_normal_init(ks[3], (e, f, d), 1.0),
    }
    if cfg.n_shared_experts:
        p["shared"] = swiglu_init(ks[4], d, cfg.n_shared_experts * f)
    return p


def route_greedy(router_logits: jax.Array, cfg):
    """[T, E] float32 logits -> (weights [T, k], experts [T, k]): softmax
    scores, greedy top-k, renormalized only if ``norm_topk_prob``, times
    ``routed_scaling_factor``."""
    probs = jax.nn.softmax(router_logits.astype(jnp.float32), axis=-1)
    weights, idx = jax.lax.top_k(probs, cfg.top_k)
    if cfg.norm_topk_prob:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return weights * cfg.routed_scaling_factor, idx


def expert_matmul_path(m: int, k: int, n: int) -> str:
    """The execution of the routed experts' grouped products over ``m``
    (token, expert) rows, model width ``k`` and expert width ``n``:
    "pallas_gmm" (``kernels.grouped_matmul``) on the TPU when every one of
    the four products (gate/up, down and their transposes) meets the
    kernel's tiling, else "ragged_dot" (``jax.lax.ragged_dot``).  Decided
    at trace time from the backend and the shapes; ``Session`` reads it
    for the registry's ``model.expert_matmul_path``."""
    if on_tpu() and all(grouped_matmul.tiling_for(m, a, b, t) is not None
                        for a, b in ((k, n), (n, k)) for t in (False, True)):
        return "pallas_gmm"
    return "ragged_dot"


def expert_tile_fill(group_sizes, m: int, k: int, n: int) -> float:
    """Real rows over the rows the gate/up product computes, on the path
    ``expert_matmul_path`` picks (1 on the ``ragged_dot`` one)."""
    if expert_matmul_path(m, k, n) == "ragged_dot":
        return 1.0
    tm = grouped_matmul.tiling_for(m, k, n)[0]
    return grouped_matmul.tile_fill(group_sizes, tm)


def _grouped_product(path: str):
    """(lhs [m, k], rhs [G, k, n] or, transposed, [G, n, k], group sizes)
    -> [m, n] in lhs's dtype, on ``path``."""
    if path == "pallas_gmm":
        return grouped_matmul.grouped_matmul

    def ragged(lhs, rhs, sizes, *, transpose_rhs=False):
        return jax.lax.ragged_dot(
            lhs, jnp.swapaxes(rhs, 1, 2) if transpose_rhs else rhs, sizes)

    return ragged


def _sort_pairs(idx, n_experts: int):
    """The T*k (token, expert) pair slots sorted by expert (stable) and
    each expert's pair count."""
    flat = idx.reshape(-1)
    order = jnp.argsort(flat, stable=True)  # pair slots grouped by expert
    return order, jnp.bincount(flat, length=n_experts).astype(jnp.int32)


def _swiglu_act(g, u):
    return (jax.nn.silu(g.astype(jnp.float32)) * u.astype(jnp.float32)
            ).astype(g.dtype)


def _combine(y, order, weights):
    """Expert-sorted outputs y [T*k, D] back in pair order, each token's k
    outputs summed with its weights: [T, D] in y's dtype."""
    t, k = weights.shape
    inverse = jnp.zeros_like(order).at[order].set(
        jnp.arange(order.shape[0], dtype=order.dtype))
    pairs = y[inverse].reshape(t, k, -1).astype(jnp.float32)
    return jnp.einsum("tkd,tk->td", pairs, weights.astype(jnp.float32)
                      ).astype(y.dtype)


def _grouped_swiglu(x, weights, idx, w_gate, w_up, w_down):
    """Dropless routed experts over tokens x [T, D]: sort the T*k (token,
    expert) pairs by expert, gather their rows, run gate/up/down as grouped
    matmuls over the experts' contiguous row groups (on
    ``expert_matmul_path``'s path), and combine each token's k outputs
    with its weights.  Returns y [T, D] (x's dtype)."""
    k = idx.shape[-1]
    order, sizes = _sort_pairs(idx, w_gate.shape[0])
    rows = x[order // k]
    mm = _grouped_product(expert_matmul_path(rows.shape[0], *w_gate.shape[1:]))
    h = _swiglu_act(mm(rows, w_gate, sizes), mm(rows, w_up, sizes))
    return _combine(mm(h, w_down, sizes), order, weights).astype(x.dtype)


def _flat_over_vmap(fn, n_token_args: int):
    """``fn`` whose first ``n_token_args`` arguments (and every output) are
    token-major, under a batching rule that folds a vmapped leading axis
    into the token axis instead of batching ``fn``: the grouped matmuls of
    N vmapped agents become one over all their tokens.  The trailing
    arguments (the expert weights) must be unbatched."""

    @custom_vmap
    def f(*args):
        return fn(*args)

    @f.def_vmap
    def rule(axis_size, in_batched, *args):
        if any(in_batched[n_token_args:]):
            raise NotImplementedError(
                "the grouped experts fold a vmapped token axis; batched "
                "expert weights are not supported")
        args = [jnp.broadcast_to(a, (axis_size,) + a.shape) if not b else a
                for a, b in zip(args[:n_token_args], in_batched)] + list(
                    args[n_token_args:])
        merged = [a.reshape((-1,) + a.shape[2:]) for a in args[:n_token_args]]
        out = f(*merged, *args[n_token_args:])
        unfold = lambda o: o.reshape((axis_size, -1) + o.shape[1:])
        return jax.tree.map(unfold, out), jax.tree.map(lambda _: True, out)

    return f


_experts_fwd = _flat_over_vmap(_grouped_swiglu, 3)


def _grouped_swiglu_vjp(x, weights, idx, w_gate, w_up, w_down, g):
    """(dx, dweights) of ``_grouped_swiglu`` at cotangent g [T, D], the
    expert weights frozen: the forward's three products recomputed, then
    dh = dy w_down^T and dx_rows = dg w_gate^T + du w_up^T as grouped
    products (six in all); the sort, gathers and combine differentiated
    by JAX."""
    k = idx.shape[-1]
    order, sizes = _sort_pairs(idx, w_gate.shape[0])
    rows, rows_vjp = jax.vjp(lambda x: x[order // k], x)
    mm = _grouped_product(expert_matmul_path(rows.shape[0], *w_gate.shape[1:]))
    h, act_vjp = jax.vjp(_swiglu_act, mm(rows, w_gate, sizes),
                         mm(rows, w_up, sizes))
    _, combine_vjp = jax.vjp(lambda y, w: _combine(y, order, w),
                             mm(h, w_down, sizes), weights)
    dy, dweights = combine_vjp(g)
    dg, du = act_vjp(mm(dy, w_down, sizes, transpose_rhs=True))
    dx_rows = (mm(dg, w_gate, sizes, transpose_rhs=True)
               + mm(du, w_up, sizes, transpose_rhs=True))
    (dx,) = rows_vjp(dx_rows.astype(x.dtype))
    return dx, dweights


def _experts_bwd_rule(x, weights, idx, g, w_gate, w_up, w_down):
    return _grouped_swiglu_vjp(x, weights, idx, w_gate, w_up, w_down, g)


_experts_bwd = _flat_over_vmap(_experts_bwd_rule, 4)


@jax.custom_vjp
def routed_experts(x, weights, idx, w_gate, w_up, w_down):
    """Dropless grouped routed experts (``_grouped_swiglu``), differentiable
    in the tokens and the routing weights; the expert weights are frozen
    (no cotangent).  Forward and backward each fold vmapped agents into
    one grouped product."""
    return _experts_fwd(x, weights, idx, w_gate, w_up, w_down)


def _routed_fwd(x, weights, idx, w_gate, w_up, w_down):
    y = _experts_fwd(x, weights, idx, w_gate, w_up, w_down)
    return y, (x, weights, idx, w_gate, w_up, w_down)


def _routed_bwd(res, g):
    x, weights, idx, w_gate, w_up, w_down = res
    dx, dw = _experts_bwd(x, weights, idx, g, w_gate, w_up, w_down)
    return dx, dw, None, None, None, None


routed_experts.defvjp(_routed_fwd, _routed_bwd)


def deepseek_moe(params, x: jax.Array, cfg):
    """x [B, S, D] -> (y [B, S, D], tokens per routed expert [E] int32)."""
    b, s, d = x.shape
    dt = x.dtype
    xt = x.reshape(b * s, d)
    with jax.named_scope("moe"):
        with jax.named_scope("router"):
            logits = jnp.matmul(xt.astype(jnp.float32),
                                params["router"].astype(jnp.float32),
                                precision=jax.lax.Precision.HIGHEST)
            weights, idx = route_greedy(logits, cfg)
            counts = jnp.bincount(idx.reshape(-1), length=cfg.n_experts)
        with jax.named_scope("experts"):
            y = routed_experts(xt, weights, idx,
                               *(params[w].astype(dt)
                                 for w in ("w_gate", "w_up", "w_down")))
        if "shared" in params:
            with jax.named_scope("shared_experts"):
                y = y + swiglu(params["shared"], xt, dt)
    return y.reshape(b, s, d), counts.astype(jnp.int32)
