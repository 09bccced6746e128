"""GQA attention with RoPE, optional qk-norm, sliding windows, KV caches.

Training/prefill uses ``chunked_attention`` — the flash-attention algorithm
(running max / running denominator over KV chunks) written in pure JAX so it
(a) never materializes the [S, S] score matrix (required for prefill_32k),
(b) lowers on any backend, and (c) shards under GSPMD.  Every attention
here runs it, except ``mla_block``'s on the TPU (below).

``mla_block`` is DeepSeek-V2's multi-head latent attention (MLA, arXiv:
2405.04434 §2.1) without query compression, for training and prefill only
(no latent decode cache).  Departures from the published description:

* RoPE pairs dimension i with i + d/2 of the rope part (rotate-half);
  DeepSeek's checkpoint interleaves pairs (2i, 2i+1) and permutes them
  before rotating.  With seeded weights this is a fixed permutation of
  W_q's and W_kv_a's rope columns, so the function family is the same;
* an optional low-rank adapter (LoRA) sits on each of the four
  projections: ``x W + (alpha / r) (x A) B``.

``mla_attention_path`` picks its score/softmax/value product from what it
can observe: on the TPU, when the sequence meets the kernels' tiling, the
causal Pallas flash kernels of ``repro.kernels.causal_flash`` (score tiles
kept in VMEM, key blocks above the diagonal skipped); ``chunked_attention``
everywhere else.

Decode uses a fixed-size KV cache: full-length for decode_32k, a ring buffer
of ``window`` slots for sliding-window long-context decode (long_500k).
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.kernels import causal_flash
from repro.kernels.dispatch import on_tpu
from repro.models.modules import (
    rmsnorm,
    rope,
    rope_scale,
    truncated_normal_init,
    yarn_inv_freq,
)

NEG_INF = -1e30


def attn_init(key, cfg, cross: bool = False):
    hd = cfg.hd
    ks = jax.random.split(key, 6)
    p = {
        "wq": truncated_normal_init(ks[0], (cfg.d_model, cfg.n_heads * hd), 1.0),
        "wk": truncated_normal_init(ks[1], (cfg.d_model, cfg.n_kv_heads * hd), 1.0),
        "wv": truncated_normal_init(ks[2], (cfg.d_model, cfg.n_kv_heads * hd), 1.0),
        "wo": truncated_normal_init(ks[3], (cfg.n_heads * hd, cfg.d_model), 1.0),
    }
    if cfg.qk_norm and not cross:
        p["q_norm"] = {"scale": jnp.ones((hd,), jnp.float32)}
        p["k_norm"] = {"scale": jnp.ones((hd,), jnp.float32)}
    return p


def _split_heads(x, n, hd):
    return x.reshape(x.shape[:-1] + (n, hd))


def _repeat_kv(k, n_heads):
    """[B, S, kv, hd] -> [B, S, H, hd] by group replication."""
    kv = k.shape[-2]
    if kv == n_heads:
        return k
    return jnp.repeat(k, n_heads // kv, axis=-2)


def chunked_attention(
    q: jax.Array,  # [B, Sq, H, hd]
    k: jax.Array,  # [B, Sk, H, hd]
    v: jax.Array,  # [B, Sk, H, hd]
    *,
    causal: bool,
    window: int = 0,
    q_offset: jax.Array | int = 0,
    k_valid: jax.Array | None = None,  # [B, Sk] bool (cache slots)
    k_positions: jax.Array | None = None,  # [B, Sk] absolute positions
    chunk_size: int = 512,
    scale: float | None = None,
    q_chunk_size: int = 0,
) -> jax.Array:
    """Flash-attention algorithm over KV chunks (pure JAX).

    ``q_offset``: absolute position of q[0] (prefill continuation / decode).
    ``window`` > 0 masks keys older than ``window`` positions behind a query.
    ``scale``: the softmax scale (default hd^-1/2).  v may have its own head
    width (MLA: q/k 192, v 128).  ``q_chunk_size`` > 0 runs the queries in
    blocks of that many, each block rematerialized in the backward pass,
    so the backward holds one block's scores at a time.
    """
    b, sq, h, hd = q.shape
    if q_chunk_size and q_chunk_size < sq:
        if sq % q_chunk_size:
            raise ValueError(f"{sq} queries are not a multiple of the query "
                             f"chunk {q_chunk_size}")
        kw = dict(causal=causal, window=window, k_valid=k_valid,
                  k_positions=k_positions, chunk_size=chunk_size,
                  scale=scale)

        @jax.checkpoint
        def block(args):
            q_i, off = args
            return chunked_attention(q_i, k, v, q_offset=off, **kw)

        n_q = sq // q_chunk_size
        out = jax.lax.map(block, (
            q.reshape(b, n_q, q_chunk_size, h, hd).swapaxes(0, 1),
            q_offset + q_chunk_size * jnp.arange(n_q)))
        return out.swapaxes(0, 1).reshape(b, sq, h, v.shape[-1])
    sk, dv = k.shape[1], v.shape[-1]
    if scale is None:
        scale = 1.0 / jnp.sqrt(hd).astype(jnp.float32)
    n_chunks = -(-sk // chunk_size)
    pad = n_chunks * chunk_size - sk
    if pad:
        padcfg = ((0, 0), (0, pad), (0, 0), (0, 0))
        k = jnp.pad(k, padcfg)
        v = jnp.pad(v, padcfg)
        valid_pad = jnp.zeros((b, pad), bool)
        k_valid = (
            jnp.concatenate([k_valid, valid_pad], axis=1)
            if k_valid is not None
            else jnp.concatenate([jnp.ones((b, sk), bool), valid_pad], axis=1)
        )
        if k_positions is not None:
            k_positions = jnp.pad(k_positions, ((0, 0), (0, pad)))
    skp = k.shape[1]
    if k_positions is None:
        k_positions = jnp.broadcast_to(jnp.arange(skp), (b, skp))
    if k_valid is None:
        k_valid = jnp.ones((b, skp), bool)

    q_pos = q_offset + jnp.arange(sq)  # [Sq]
    kc = k.reshape(b, n_chunks, chunk_size, h, hd).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(b, n_chunks, chunk_size, h, dv).transpose(1, 0, 2, 3, 4)
    kpos_c = k_positions.reshape(b, n_chunks, chunk_size).transpose(1, 0, 2)
    kval_c = k_valid.reshape(b, n_chunks, chunk_size).transpose(1, 0, 2)

    m0 = jnp.full((b, h, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, sq), jnp.float32)
    acc0 = jnp.zeros((b, sq, h, dv), jnp.float32)

    def body_fixed(carry, xs):
        m, l, acc = carry
        k_j, v_j, kp_j, kv_j = xs
        s = jnp.einsum(
            "bqhd,bkhd->bhqk", q.astype(jnp.float32), k_j.astype(jnp.float32)
        ) * scale
        mask = kv_j[:, None, None, :]
        if causal:
            mask = mask & (kp_j[:, None, None, :] <= q_pos[None, None, :, None])
        if window:
            mask = mask & (
                kp_j[:, None, None, :] > q_pos[None, None, :, None] - window
            )
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        m_safe = jnp.where(m_new <= NEG_INF / 2, 0.0, m_new)
        p = jnp.where(mask, jnp.exp(s - m_safe[..., None]), 0.0)
        corr = jnp.where(m <= NEG_INF / 2, 0.0, jnp.exp(m - m_safe))
        l_new = l * corr + jnp.sum(p, axis=-1)
        acc_new = acc * corr.transpose(0, 2, 1)[..., None] + jnp.einsum(
            "bhqk,bkhd->bqhd", p, v_j.astype(jnp.float32)
        )
        return (m_new, l_new, acc_new), None

    (m, l, acc), _ = jax.lax.scan(body_fixed, (m0, l0, acc0), (kc, vc, kpos_c, kval_c))
    l = jnp.maximum(l, 1e-30)
    out = acc / l.transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


def init_kv_cache(cfg, batch: int, capacity: int, dtype=jnp.bfloat16):
    """Fixed-capacity KV cache (ring buffer when capacity < context).

    ``dtype=jnp.int8`` enables quantized storage: per-(slot, head) absmax
    scales dequantize on read — the §Perf memory-bound-decode optimization
    (halves KV HBM traffic vs bf16)."""
    hd = cfg.hd
    cache = {
        "k": jnp.zeros((batch, capacity, cfg.n_kv_heads, hd), dtype),
        "v": jnp.zeros((batch, capacity, cfg.n_kv_heads, hd), dtype),
        "pos": jnp.full((batch, capacity), -1, jnp.int32),  # absolute positions
    }
    if dtype == jnp.int8:
        cache["k_scale"] = jnp.zeros((batch, capacity, cfg.n_kv_heads), jnp.float32)
        cache["v_scale"] = jnp.zeros((batch, capacity, cfg.n_kv_heads), jnp.float32)
    return cache


def _quantize_kv(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """[..., hd] bf16/f32 -> (int8, per-[...]-scale fp32)."""
    absmax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    scale = jnp.maximum(absmax / 127.0, 1e-8)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]), -127, 127)
    return q.astype(jnp.int8), scale


def _dequantize_kv(q: jax.Array, scale: jax.Array, dtype) -> jax.Array:
    return (q.astype(jnp.float32) * scale[..., None]).astype(dtype)


def cache_update(cache, k_new, v_new, position):
    """Write one decode step (Sq=1) at slot position % capacity."""
    cap = cache["k"].shape[1]
    slot = position % cap
    quant = cache["k"].dtype == jnp.int8
    out = dict(cache)
    if quant:
        kq, ks = _quantize_kv(k_new)
        vq, vs = _quantize_kv(v_new)
        out["k_scale"] = jax.lax.dynamic_update_slice_in_dim(
            cache["k_scale"], ks.astype(jnp.float32), slot, axis=1
        )
        out["v_scale"] = jax.lax.dynamic_update_slice_in_dim(
            cache["v_scale"], vs.astype(jnp.float32), slot, axis=1
        )
        k_new, v_new = kq, vq
    out["k"] = jax.lax.dynamic_update_slice_in_dim(
        cache["k"], k_new.astype(cache["k"].dtype), slot, axis=1
    )
    out["v"] = jax.lax.dynamic_update_slice_in_dim(
        cache["v"], v_new.astype(cache["v"].dtype), slot, axis=1
    )
    out["pos"] = jax.lax.dynamic_update_slice_in_dim(
        cache["pos"],
        jnp.full((cache["pos"].shape[0], 1), position, jnp.int32),
        slot,
        axis=1,
    )
    return out


def cache_read_kv(cache, dtype):
    """Materialize (k, v) from the cache, dequantizing if int8-stored."""
    if cache["k"].dtype == jnp.int8:
        k = _dequantize_kv(cache["k"], cache["k_scale"], dtype)
        v = _dequantize_kv(cache["v"], cache["v_scale"], dtype)
        return k, v
    return cache["k"].astype(dtype), cache["v"].astype(dtype)


def attention_block(
    params,
    x: jax.Array,  # [B, S, D]
    cfg,
    *,
    causal: bool = True,
    window: int = 0,
    positions: jax.Array | None = None,  # [S] absolute positions
    cache: dict | None = None,  # decode path
    cross_x: jax.Array | None = None,  # encoder output for cross-attn
    use_rope: bool = True,
    chunk_size: int = 512,
):
    """Returns (y [B,S,D], new_cache_or_None)."""
    b, s, d = x.shape
    hd = cfg.hd
    dt = x.dtype
    q = _split_heads(x @ params["wq"].astype(dt), cfg.n_heads, hd)
    kv_src = cross_x if cross_x is not None else x
    k = _split_heads(kv_src @ params["wk"].astype(dt), cfg.n_kv_heads, hd)
    v = _split_heads(kv_src @ params["wv"].astype(dt), cfg.n_kv_heads, hd)

    if "q_norm" in params:
        q = rmsnorm(params["q_norm"], q, cfg.norm_eps)
        k = rmsnorm(params["k_norm"], k, cfg.norm_eps)

    if positions is None:
        positions = jnp.arange(s)
    if use_rope and cross_x is None:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)

    new_cache = None
    if cache is not None and s > 1:
        # prefill: bulk-write k/v into the cache, attend over the fresh k/v
        cap = cache["k"].shape[1]
        quant = cache["k"].dtype == jnp.int8
        if quant:
            k_st, k_sc = _quantize_kv(k)
            v_st, v_sc = _quantize_kv(v)
        else:
            k_st, v_st = k.astype(cache["k"].dtype), v.astype(cache["v"].dtype)
            k_sc = v_sc = None
        if cap >= s:
            new_cache = {
                "k": jax.lax.dynamic_update_slice_in_dim(cache["k"], k_st, 0, axis=1),
                "v": jax.lax.dynamic_update_slice_in_dim(cache["v"], v_st, 0, axis=1),
                "pos": jax.lax.dynamic_update_slice_in_dim(
                    cache["pos"],
                    jnp.broadcast_to(positions[None, :], (b, s)).astype(jnp.int32),
                    0,
                    axis=1,
                ),
            }
            if quant:
                new_cache["k_scale"] = jax.lax.dynamic_update_slice_in_dim(
                    cache["k_scale"], k_sc, 0, axis=1
                )
                new_cache["v_scale"] = jax.lax.dynamic_update_slice_in_dim(
                    cache["v_scale"], v_sc, 0, axis=1
                )
        else:
            # ring buffer (sliding-window): keep only the LAST cap positions,
            # each at its slot position % cap (continues seamlessly in decode)
            tail_pos = positions[s - cap :]
            slots = tail_pos % cap
            new_cache = {
                "k": cache["k"].at[:, slots].set(k_st[:, s - cap :]),
                "v": cache["v"].at[:, slots].set(v_st[:, s - cap :]),
                "pos": cache["pos"].at[:, slots].set(
                    jnp.broadcast_to(tail_pos[None, :], (b, cap)).astype(jnp.int32)
                ),
            }
            if quant:
                new_cache["k_scale"] = cache["k_scale"].at[:, slots].set(
                    k_sc[:, s - cap :]
                )
                new_cache["v_scale"] = cache["v_scale"].at[:, slots].set(
                    v_sc[:, s - cap :]
                )
        k = _repeat_kv(k, cfg.n_heads)
        v = _repeat_kv(v, cfg.n_heads)
        out = chunked_attention(
            q, k, v, causal=causal, window=window, q_offset=0, chunk_size=chunk_size
        )
    elif cache is not None:
        # decode: S == 1; append to cache, attend over the whole cache
        new_cache = cache_update(cache, k, v, positions[0])
        k_deq, v_deq = cache_read_kv(new_cache, dt)
        k_full = _repeat_kv(k_deq, cfg.n_heads)
        v_full = _repeat_kv(v_deq, cfg.n_heads)
        out = chunked_attention(
            q,
            k_full,
            v_full,
            causal=causal,
            window=window,
            q_offset=positions[0],
            k_valid=new_cache["pos"] >= 0,
            k_positions=new_cache["pos"],
            chunk_size=chunk_size,
        )
    else:
        k = _repeat_kv(k, cfg.n_heads)
        v = _repeat_kv(v, cfg.n_heads)
        out = chunked_attention(
            q,
            k,
            v,
            causal=causal and cross_x is None,
            window=window,
            q_offset=positions[0] if s != positions.shape[0] else 0,
            chunk_size=chunk_size,
        )
    y = out.reshape(b, s, cfg.n_heads * hd) @ params["wo"].astype(dt)
    return y, new_cache


# ---------------------------------------------------------------------------
# multi-head latent attention (DeepSeek-V2)
# ---------------------------------------------------------------------------

MLA_PROJECTIONS = ("q", "kv_a", "kv_b", "o")


def mla_dims(cfg) -> dict:
    """(d_in, d_out) of each MLA projection."""
    h = cfg.n_heads
    return {
        "q": (cfg.d_model, h * (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)),
        "kv_a": (cfg.d_model, cfg.kv_lora_rank + cfg.qk_rope_head_dim),
        "kv_b": (cfg.kv_lora_rank, h * (cfg.qk_nope_head_dim + cfg.v_head_dim)),
        "o": (h * cfg.v_head_dim, cfg.d_model),
    }


def mla_init(key, cfg):
    ks = jax.random.split(key, 4)
    dims = mla_dims(cfg)
    p = {f"w{name}": truncated_normal_init(k, dims[name], 1.0)
         for k, name in zip(ks, MLA_PROJECTIONS)}
    p["kv_norm"] = {"scale": jnp.ones((cfg.kv_lora_rank,), jnp.float32)}
    return p


def mla_softmax_scale(cfg) -> float:
    """(nope + rope)^-1/2, times YaRN's mscale(factor, mscale_all_dim)^2
    when the rope is scaled (DeepSeek-V2)."""
    scale = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
    ys = cfg.rope_scaling
    if ys is not None and ys.mscale_all_dim:
        m = ys.get_mscale(ys.factor, ys.mscale_all_dim)
        scale *= m * m
    return scale


def mla_attention_path(s: int) -> str:
    """The execution of ``mla_block``'s attention over ``s`` positions:
    "flash" (``causal_flash``) on the TPU when ``s`` meets the kernels'
    tiling, else "chunked" (``chunked_attention``).  Decided at trace time
    from the backend and the shape; ``Session`` reads it for the registry's
    ``model.attention_path``."""
    if on_tpu() and causal_flash.block_sizes(s) is not None:
        return "flash"
    return "chunked"


def mla_block_fill(s: int) -> float:
    """Key blocks the attention's forward computes over all key blocks:
    the causal kernel's live blocks on the flash path, every key block (1)
    on the chunked one."""
    if mla_attention_path(s) == "flash":
        return causal_flash.block_fill(s, causal_flash.block_sizes(s))
    return 1.0


def _project(params, name: str, x, lora_scale: float):
    """x @ W, plus (alpha / r) (x @ A) @ B when the layer carries an
    adapter (``params["lora"][name]``, in float32)."""
    dt = x.dtype
    y = x @ params[f"w{name}"].astype(dt)
    lora = params.get("lora")
    if lora is not None:
        ad = lora[name]
        y = y + (lora_scale * ((x @ ad["a"].astype(dt)) @ ad["b"].astype(dt))
                 ).astype(dt)
    return y


def mla_block(params, x: jax.Array, cfg, *, positions: jax.Array,
              lora_scale: float = 1.0, chunk: int = 256) -> jax.Array:
    """MLA over x [B, S, D] (causal).  Per token: q = x W_q split into
    heads of (nope, rope); [c_kv, k_pe] = x W_kv_a with c_kv RMS-normed;
    [k_nope, v] = c_kv W_kv_b; YaRN RoPE on q_pe and the shared k_pe;
    o = softmax(q k^T * scale) v, run as ``mla_attention_path(S)`` says;
    y = o W_o."""
    b, s, _ = x.shape
    h, nope, rd = cfg.n_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    with jax.named_scope("mla"):
        q = _project(params, "q", x, lora_scale).reshape(b, s, h, nope + rd)
        ckv = _project(params, "kv_a", x, lora_scale)
        c, k_pe = ckv[..., :cfg.kv_lora_rank], ckv[..., cfg.kv_lora_rank:]
        c = rmsnorm(params["kv_norm"], c, cfg.norm_eps)
        kv = _project(params, "kv_b", c, lora_scale).reshape(
            b, s, h, nope + cfg.v_head_dim)
        k_nope, v = kv[..., :nope], kv[..., nope:]
        ys = cfg.rope_scaling
        inv_freq = (None if ys is None
                    else yarn_inv_freq(rd, cfg.rope_theta, ys))
        rs = 1.0 if ys is None else rope_scale(ys)
        q_pe = rope(q[..., nope:], positions, cfg.rope_theta, inv_freq, rs)
        k_pe = rope(k_pe[:, :, None, :], positions, cfg.rope_theta, inv_freq,
                    rs)
        q = jnp.concatenate([q[..., :nope], q_pe], axis=-1)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_pe, (b, s, h, rd))], axis=-1)
        if mla_attention_path(s) == "flash":
            o = causal_flash.causal_flash(
                q, k, v, scale=mla_softmax_scale(cfg),
                blocks=causal_flash.block_sizes(s))
        else:
            o = chunked_attention(q, k, v, causal=True, chunk_size=s,
                                  scale=mla_softmax_scale(cfg),
                                  q_chunk_size=chunk)
        return _project(params, "o", o.reshape(b, s, h * cfg.v_head_dim),
                        lora_scale)
