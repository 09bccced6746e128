"""Causal multi-head flash attention on the TPU: JAX's splash kernels.

``causal_flash`` runs exact causal softmax attention through the Pallas
kernels of ``jax.experimental.pallas.ops.tpu.splash_attention`` (a forward
kernel and a backward kernel, dq fused into the dkv one, under one
``custom_vjp``).  Each kernel keeps its score tile in VMEM and never writes
it to HBM, and the causal mask's block table skips every key block above
the diagonal.  q and k may be wider than v (MLA: 192 and 128).

Precision: q, k and v enter the kernels in bfloat16; products accumulate
in float32, and the softmax statistics (running max, denominator,
logsumexp) are float32.  The backward casts the probabilities and their
gradients to bfloat16 for its products; the forward's P·V takes P in
float32 against v upcast from bfloat16 (splash's choice, not a knob).
Splash applies no softmax scale, so the scale multiplies q in float32
before q's cast to bfloat16.

``BLOCKS`` holds the tile sizes chosen on a TPU v5e for the
DeepSeek-V2-Lite cell (S 4,096, 16 heads, widths 192 / 128; the forward
and the fused backward timed apart over 16 settings).  ``block_sizes``
cuts them to a shorter sequence and says when a sequence does not meet
the kernels' tiling.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu import splash_attention as splash

from repro.kernels.dispatch import auto_interpret

LANES = 128
BLOCKS = splash.BlockSizes(
    block_q=1024, block_kv=1024, block_kv_compute=512,
    block_q_dkv=512, block_kv_dkv=512, block_kv_dkv_compute=512,
    block_q_dq=None, block_kv_dq=None, use_fused_bwd_kernel=True,
)
_SIZES = ("block_q", "block_kv", "block_kv_compute", "block_q_dkv",
          "block_kv_dkv", "block_kv_dkv_compute", "block_q_dq",
          "block_kv_dq")


def block_sizes(s: int, blocks: splash.BlockSizes = BLOCKS
                ) -> splash.BlockSizes | None:
    """``blocks`` with each size cut to at most ``s``; None when a cut
    size is not a multiple of 128 or does not divide ``s``."""
    cut = {f: min(b, s) for f in _SIZES
           if (b := getattr(blocks, f)) is not None}
    if any(b % LANES or s % b for b in cut.values()):
        return None
    return dataclasses.replace(blocks, **cut)


def block_fill(s: int, blocks: splash.BlockSizes) -> float:
    """Key blocks the causal forward computes over all of them:
    n (n + 1) / 2 of n^2 at one block size, in general the (q, kv) block
    pairs with some key at or before some query."""
    bq, bkv = blocks.block_q, blocks.block_kv
    nq, nkv = s // bq, s // bkv
    live = sum(min(nkv, ((i + 1) * bq - 1) // bkv + 1) for i in range(nq))
    return live / (nq * nkv)


def causal_flash(q: jax.Array, k: jax.Array, v: jax.Array, *, scale: float,
                 blocks: splash.BlockSizes,
                 interpret: bool | None = None) -> jax.Array:
    """softmax(q k^T * scale, causal) v for q, k [B, S, H, dqk] and
    v [B, S, H, dv]; returns [B, S, H, dv] in v's dtype (bfloat16).
    ``blocks`` from ``block_sizes(S)``.  Batches under ``jax.vmap``."""
    _, s, h, _ = q.shape
    mask = splash.MultiHeadMask([splash.CausalMask((s, s))] * h)
    kernel = splash.make_splash_mha(
        mask, block_sizes=blocks, head_shards=1, q_seq_shards=1,
        interpret=auto_interpret(interpret))
    dt = v.dtype
    q = (q.astype(jnp.float32) * scale).astype(dt)
    heads_major = lambda x: x.astype(dt).swapaxes(1, 2)  # [B, H, S, d]
    o = jax.vmap(kernel)(heads_major(q), heads_major(k), heads_major(v))
    return o.swapaxes(1, 2)
