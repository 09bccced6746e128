"""Composable transformer assembly.

An architecture is ``n_periods`` repetitions of ``cfg.pattern`` (+ a tail
remainder).  Per-kind parameter stacks carry leaves of shape
[n_periods, c_kind, ...], and the layer loop is ONE ``lax.scan`` over
periods — compile time and HLO size stay O(pattern), not O(n_layers), which
is what makes the 52-layer/42-B dry-runs tractable.  Caches (KV / recurrent
state) are threaded through the same scan as xs/ys.

Supported block kinds: attn, local_attn, moe, mlstm, slstm, rglru,
enc_attn, dec_attn, mla, mla_moe (see configs.base docstring).

Latent-attention models (DeepSeek-V2: ``cfg.is_mla``) run ``mla_hidden``:
the ``first_k_dense`` leading ``mla`` layers, then one scan over the
``mla_moe`` layers, each layer rematerialized under ``remat``; optional
low-rank adapters ride along each layer's parameters, and the scan
returns every MoE layer's tokens per routed expert.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.models import moe as moe_lib
from repro.models.attention import (
    attention_block,
    attn_init,
    init_kv_cache,
    mla_block,
    mla_init,
)
from repro.models.modules import (
    embed,
    embed_init,
    rmsnorm,
    rmsnorm_init,
    swiglu,
    swiglu_init,
    truncated_normal_init,
    unembed,
)
from repro.models.rglru import rglru_block, rglru_init, rglru_state_init
from repro.models.xlstm import (
    mlstm_block,
    mlstm_init,
    mlstm_state_init,
    slstm_block,
    slstm_init,
    slstm_state_init,
)

PyTree = Any

ATTN_KINDS = ("attn", "local_attn", "moe", "enc_attn", "dec_attn")


# ---------------------------------------------------------------------------
# per-kind init / apply / cache
# ---------------------------------------------------------------------------


def block_init(key, kind: str, cfg):
    if kind in ("attn", "local_attn", "enc_attn", "dec_attn"):
        ks = jax.random.split(key, 5)
        p = {
            "norm1": rmsnorm_init(cfg.d_model),
            "attn": attn_init(ks[0], cfg),
            "norm2": rmsnorm_init(cfg.d_model),
            "mlp": swiglu_init(ks[1], cfg.d_model, cfg.d_ff),
        }
        if kind == "dec_attn":
            p["norm_x"] = rmsnorm_init(cfg.d_model)
            p["xattn"] = attn_init(ks[2], cfg, cross=True)
        return p
    if kind == "moe":
        ks = jax.random.split(key, 2)
        return {
            "norm1": rmsnorm_init(cfg.d_model),
            "attn": attn_init(ks[0], cfg),
            "norm2": rmsnorm_init(cfg.d_model),
            "moe": moe_lib.moe_init(ks[1], cfg),
        }
    if kind in ("mla", "mla_moe"):
        ks = jax.random.split(key, 2)
        p = {"norm1": rmsnorm_init(cfg.d_model), "attn": mla_init(ks[0], cfg),
             "norm2": rmsnorm_init(cfg.d_model)}
        if kind == "mla":
            p["mlp"] = swiglu_init(ks[1], cfg.d_model, cfg.d_ff)
        else:
            p["moe"] = moe_lib.deepseek_moe_init(ks[1], cfg)
        return p
    if kind == "mlstm":
        return mlstm_init(key, cfg)
    if kind == "slstm":
        return slstm_init(key, cfg)
    if kind == "rglru":
        ks = jax.random.split(key, 2)
        return {
            "rec": rglru_init(ks[0], cfg),
            "norm2": rmsnorm_init(cfg.d_model),
            "mlp": swiglu_init(ks[1], cfg.d_model, cfg.d_ff),
        }
    raise ValueError(f"unknown block kind {kind!r}")


def block_cache_init(kind: str, cfg, batch: int, capacity: int, dtype=jnp.bfloat16):
    """Decode-time cache for one layer of ``kind``."""
    if kind in ("attn", "moe", "dec_attn"):
        return init_kv_cache(cfg, batch, capacity, dtype)
    if kind == "local_attn":
        cap = min(capacity, cfg.sliding_window or capacity)
        return init_kv_cache(cfg, batch, cap, dtype)
    if kind == "mlstm":
        return mlstm_state_init(cfg, batch)
    if kind == "slstm":
        return slstm_state_init(cfg, batch)
    if kind == "rglru":
        return rglru_state_init(cfg, batch)
    if kind in ("mla", "mla_moe"):
        raise NotImplementedError(
            "latent attention has no decode cache here (training and "
            "prefill only)")
    raise ValueError(kind)


def block_apply(
    kind: str,
    params,
    x,
    cfg,
    *,
    positions,
    cache=None,
    enc_out=None,
    window_override: int | None = None,
):
    """Returns (x', new_cache, aux_loss)."""
    aux = jnp.asarray(0.0, jnp.float32)
    if kind in ("attn", "local_attn", "moe", "enc_attn", "dec_attn"):
        window = cfg.sliding_window if kind == "local_attn" else 0
        if window_override is not None and kind in ("attn", "local_attn"):
            window = window_override
        h = rmsnorm(params["norm1"], x, cfg.norm_eps)
        y, new_cache = attention_block(
            params["attn"],
            h,
            cfg,
            causal=kind != "enc_attn",
            window=window,
            positions=positions,
            cache=cache,
            use_rope=kind not in ("enc_attn", "dec_attn"),
        )
        x = x + y
        if kind == "dec_attn":
            hx = rmsnorm(params["norm_x"], x, cfg.norm_eps)
            yx, _ = attention_block(
                params["xattn"],
                hx,
                cfg,
                causal=False,
                positions=positions,
                cross_x=enc_out,
                use_rope=False,
            )
            x = x + yx
        h2 = rmsnorm(params["norm2"], x, cfg.norm_eps)
        if kind == "moe":
            y2, aux = moe_lib.moe_ffn(params["moe"], h2, cfg)
        else:
            y2 = swiglu(params["mlp"], h2, x.dtype)
        return x + y2, new_cache, aux
    if kind == "mlstm":
        y, new_state = mlstm_block(params, x, cfg, state=cache)
        return y, new_state, aux
    if kind == "slstm":
        y, new_state = slstm_block(params, x, cfg, state=cache)
        return y, new_state, aux
    if kind == "rglru":
        y, new_state = rglru_block(params["rec"], x, cfg, state=cache)
        h2 = rmsnorm(params["norm2"], y, cfg.norm_eps)
        y2 = swiglu(params["mlp"], h2, x.dtype)
        return y + y2, new_state, aux
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# whole-model init
# ---------------------------------------------------------------------------


def _stack_inits(key, kind: str, cfg, n: int):
    keys = jax.random.split(key, n)
    return jax.vmap(lambda k: block_init(k, kind, cfg))(keys)


def init_params(cfg, key) -> PyTree:
    cfg.validate()
    ks = jax.random.split(key, 8)
    params: dict = {"embed": embed_init(ks[0], cfg.padded_vocab, cfg.d_model)}
    if not cfg.tie_embeddings:
        params["lm_head"] = {
            "w": truncated_normal_init(ks[1], (cfg.d_model, cfg.padded_vocab), 1.0)
        }
    params["final_norm"] = rmsnorm_init(cfg.d_model)

    counts = cfg.kind_counts()
    stacks = {}
    kkeys = jax.random.split(ks[2], len(counts))
    for kk, (kind, c) in zip(kkeys, counts.items()):
        n = cfg.n_periods * c
        if n:
            stk = _stack_inits(kk, kind, cfg, n)
            stacks[kind] = jax.tree.map(
                lambda a: a.reshape((cfg.n_periods, c) + a.shape[1:]), stk
            )
    params["stacks"] = stacks
    if cfg.first_k_dense:
        params["lead"] = _stack_inits(ks[6], "mla", cfg, cfg.first_k_dense)
    if cfg.tail:
        tkeys = jax.random.split(ks[3], len(cfg.tail))
        params["tail"] = [
            block_init(tk, kind, cfg) for tk, kind in zip(tkeys, cfg.tail)
        ]
    if cfg.is_encdec:
        ekeys = jax.random.split(ks[4], 2)
        params["enc_stack"] = jax.tree.map(
            lambda a: a[:, None],
            _stack_inits(ekeys[0], "enc_attn", cfg, cfg.encoder_layers),
        )
        params["enc_norm"] = rmsnorm_init(cfg.d_model)
    if cfg.frontend == "vision_stub":
        params["patch_proj"] = {
            "w": truncated_normal_init(ks[5], (cfg.d_model, cfg.d_model), 1.0)
        }
    return params


def init_cache(cfg, batch: int, capacity: int, dtype=jnp.bfloat16) -> PyTree:
    """Stacked decode caches matching the scan layout."""
    counts = cfg.kind_counts()
    cache: dict = {"stacks": {}}
    for kind, c in counts.items():
        if cfg.n_periods:
            one = block_cache_init(kind, cfg, batch, capacity, dtype)
            cache["stacks"][kind] = jax.tree.map(
                lambda a: jnp.broadcast_to(
                    a, (cfg.n_periods, c) + a.shape
                ).copy(),
                one,
            )
    if cfg.tail:
        cache["tail"] = [
            block_cache_init(kind, cfg, batch, capacity, dtype) for kind in cfg.tail
        ]
    return cache


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _sinusoidal(positions, d_model):
    half = d_model // 2
    freqs = jnp.exp(-jnp.arange(half, dtype=jnp.float32) * jnp.log(10000.0) / half)
    ang = positions[:, None].astype(jnp.float32) * freqs[None, :]
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


def _apply_period(cfg, pattern, stacks_slice, x, positions, cache_slice, enc_out,
                  window_override=None):
    """Apply one period's blocks.  stacks_slice / cache_slice leaves are
    [c_kind, ...]; returns (x, new_cache_slice, aux)."""
    offsets: dict[str, int] = {}
    aux = jnp.asarray(0.0, jnp.float32)
    upd: dict[str, list] = {}
    for kind in pattern:
        o = offsets.get(kind, 0)
        offsets[kind] = o + 1
        p = jax.tree.map(lambda a: a[o], stacks_slice[kind])
        c = (
            jax.tree.map(lambda a: a[o], cache_slice[kind])
            if cache_slice is not None
            else None
        )
        x, nc, a = block_apply(
            kind, p, x, cfg, positions=positions, cache=c, enc_out=enc_out,
            window_override=window_override,
        )
        aux = aux + a
        if cache_slice is not None:
            upd.setdefault(kind, []).append(nc)
    new_cache_slice = None
    if cache_slice is not None:
        new_cache_slice = {
            kind: jax.tree.map(lambda *xs: jnp.stack(xs), *lst)
            for kind, lst in upd.items()
        }
    return x, new_cache_slice, aux


def _scan_layers(cfg, pattern, stacks, x, positions, cache, enc_out, remat=False,
                 window_override=None):
    """lax.scan over periods.  stacks leaves: [n_periods, c_kind, ...]."""

    def body(carry, xs):
        h, aux = carry
        stacks_slice, cache_slice = xs
        h, new_cache_slice, a = _apply_period(
            cfg, pattern, stacks_slice, h, positions, cache_slice, enc_out,
            window_override,
        )
        return (h, aux + a), new_cache_slice

    if remat:
        body = jax.checkpoint(body)

    xs = (stacks, cache)
    (x, aux), new_cache = jax.lax.scan(body, (x, jnp.asarray(0.0, jnp.float32)), xs)
    return x, new_cache, aux


def forward(
    params: PyTree,
    cfg,
    tokens: jax.Array,  # [B, S_text]
    *,
    positions: jax.Array | None = None,  # [S_total] absolute positions
    cache: PyTree | None = None,
    frames: jax.Array | None = None,  # audio stub embeddings [B, F, D]
    patches: jax.Array | None = None,  # vision stub embeddings [B, P, D]
    remat: bool = False,
    window_override: int | None = None,
    logits_tail: int = 0,
):
    """Returns (logits [B, S_total, padded_vocab], new_cache, aux_loss).

    ``window_override``: force a sliding window on ``attn``/``local_attn``
    kinds (the dense-arch long_500k SWA variant).
    ``logits_tail``: if > 0, unembed only the last ``logits_tail`` positions
    (prefill returns next-token logits without materializing [S, V]).
    """
    dt = jnp.dtype(cfg.dtype)
    if cfg.is_mla:
        if cache is not None or positions is not None:
            raise NotImplementedError("latent attention runs whole "
                                      "sequences from position 0 only")
        x, _ = mla_hidden(params, cfg, tokens, remat=remat)
        if logits_tail:
            x = x[:, -logits_tail:]
        logits = (x @ params["lm_head"]["w"].astype(dt)).astype(jnp.float32)
        return logits, None, jnp.asarray(0.0, jnp.float32)
    x = embed(params["embed"], tokens, dt)
    if cfg.frontend == "vision_stub" and patches is not None:
        pe = patches.astype(dt) @ params["patch_proj"]["w"].astype(dt)
        x = jnp.concatenate([pe, x], axis=1)
    b, s, _ = x.shape
    if positions is None:
        positions = jnp.arange(s)

    enc_out = None
    if cfg.is_encdec:
        assert frames is not None, "enc-dec model needs frame embeddings"
        fpos = jnp.arange(frames.shape[1])
        ex = frames.astype(dt) + _sinusoidal(fpos, cfg.d_model)[None].astype(dt)
        ex, _, _ = _scan_layers(
            cfg, ("enc_attn",), {"enc_attn": params["enc_stack"]}, ex, fpos, None,
            None, remat,
        )
        enc_out = rmsnorm(params["enc_norm"], ex, cfg.norm_eps)
        x = x + _sinusoidal(positions, cfg.d_model)[None].astype(dt)

    cache_stacks = cache["stacks"] if cache is not None else None
    new_cache = None
    x, new_stack_cache, aux = _scan_layers(
        cfg, cfg.pattern, params["stacks"], x, positions, cache_stacks, enc_out,
        remat, window_override,
    )
    tail_cache = []
    if cfg.tail:
        for i, kind in enumerate(cfg.tail):
            c = cache["tail"][i] if cache is not None else None
            x, nc, a = block_apply(
                kind,
                params["tail"][i],
                x,
                cfg,
                positions=positions,
                cache=c,
                enc_out=enc_out,
                window_override=window_override,
            )
            aux = aux + a
            tail_cache.append(nc)
    if cache is not None:
        new_cache = {"stacks": new_stack_cache}
        if cfg.tail:
            new_cache["tail"] = tail_cache

    if logits_tail:
        x = x[:, -logits_tail:]
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    if cfg.tie_embeddings:
        logits = unembed(params["embed"], x, dt)
    else:
        logits = (x @ params["lm_head"]["w"].astype(dt)).astype(jnp.float32)
    return logits, new_cache, aux


def _mla_layer(kind: str, cfg, positions, lora_scale: float):
    """One latent-attention layer: (x, (params, adapters)) -> (x', tokens
    per routed expert, or None for a dense layer)."""

    def layer(x, p_ad):
        p, adapters = p_ad
        attn = p["attn"] if adapters is None else {**p["attn"],
                                                   "lora": adapters}
        h = rmsnorm(p["norm1"], x, cfg.norm_eps)
        x = x + mla_block(attn, h, cfg, positions=positions,
                          lora_scale=lora_scale)
        h = rmsnorm(p["norm2"], x, cfg.norm_eps)
        if kind == "mla_moe":
            y, counts = moe_lib.deepseek_moe(p["moe"], h, cfg)
        else:
            y, counts = swiglu(p["mlp"], h, x.dtype), None
        return x + y, counts

    return layer


def mla_hidden(params, cfg, tokens, *, adapters=None, lora_scale: float = 1.0,
               remat: bool = False):
    """Final-normed hidden states [B, S, D] of a latent-attention model and
    the tokens per routed expert of each MoE layer [n_moe, E].

    ``adapters``: optional low-rank adapters of the MLA projections,
    ``{"lead": {proj: {"a", "b"}}, "moe": ...}`` with a leading layer axis
    matching ``params["lead"]`` and the ``mla_moe`` stack."""
    dt = jnp.dtype(cfg.dtype)
    x = embed(params["embed"], tokens, dt)
    positions = jnp.arange(tokens.shape[1])
    adapters = adapters or {}

    def run(kind, stack, ads, x):
        layer = _mla_layer(kind, cfg, positions, lora_scale)
        if remat:
            layer = jax.checkpoint(layer)
        return jax.lax.scan(layer, x, (stack, ads))

    if cfg.first_k_dense:
        x, _ = run("mla", params["lead"], adapters.get("lead"), x)
    moe_stack = jax.tree.map(lambda a: a[:, 0],
                             params["stacks"]["mla_moe"])  # [n_periods, ...]
    x, counts = run("mla_moe", moe_stack, adapters.get("moe"), x)
    return rmsnorm(params["final_norm"], x, cfg.norm_eps), counts


def nll_loss(params, cfg, batch, remat: bool = False) -> tuple[jax.Array, jax.Array]:
    """Total next-token NLL (summed over tokens) + MoE aux.  Returns
    (total_nll, aux).  ``batch``: dict(tokens, targets[, loss_mask, frames,
    patches])."""
    logits, _, aux = forward(
        params,
        cfg,
        batch["tokens"],
        frames=batch.get("frames"),
        patches=batch.get("patches"),
        remat=remat,
    )
    targets = batch["targets"]
    # vlm: logits cover [patches; text] — take the text tail
    if logits.shape[1] != targets.shape[1]:
        logits = logits[:, logits.shape[1] - targets.shape[1] :]
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    nll = logz - gold
    mask = batch.get("loss_mask")
    if mask is not None:
        nll = nll * mask
    return jnp.sum(nll), aux


def decode_step(
    params: PyTree,
    cfg,
    token: jax.Array,  # [B, 1]
    position: jax.Array,  # scalar int32 — absolute position of this token
    cache: PyTree,
    enc_out_frames: jax.Array | None = None,
    window_override: int | None = None,
):
    """One-token autoregressive step against the cache.  Returns
    (logits [B, 1, V], new_cache)."""
    positions = position[None] if position.ndim == 0 else position
    logits, new_cache, _ = forward(
        params,
        cfg,
        token,
        positions=positions,
        cache=cache,
        frames=enc_out_frames,
        window_override=window_override,
    )
    return logits, new_cache
