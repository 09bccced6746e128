"""The causal flash kernels (``repro.kernels.causal_flash``) in interpret
mode against ``chunked_attention``, and ``mla_block``'s dispatch between
them (``repro.models.attention.mla_attention_path``).

The kernel test runs at MLA's real head widths (q/k 192, v 128) at a small
sequence and head count that meet the kernels' tiling (S 256, H 2, blocks
of 128), on bfloat16 inputs.  Tolerances come from bfloat16 rounding (unit
roundoff u = 2^-8): the kernels round q * scale, and in the backward the
probabilities and their gradients, to bfloat16 where the reference keeps
float32, so the forward output may differ by two bfloat16 spacings of its
largest entry and each gradient by 2u in norm."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import (DataSpec, ExperimentSpec, InferenceSpec, ObsSpec,
                       RunSpec, TopologySpec, build_session)
from repro.configs import get_config
from repro.configs.registry import ARCHS
from repro.kernels import causal_flash
from repro.models import attention

S, H, DQK, DV = 256, 2, 192, 128
SCALE = 0.0917
U = 2.0 ** -8
BLOCKS_128 = causal_flash.block_sizes(S, dataclasses.replace(
    causal_flash.BLOCKS, **{f: 128 for f in causal_flash._SIZES
                            if getattr(causal_flash.BLOCKS, f) is not None}))


def _qkvg(agents):
    lead = (agents,) if agents else ()
    ks = jax.random.split(jax.random.key(2_147_483_659), 4)
    shape = lambda d: lead + (1, S, H, d)
    draw = lambda k, d: jax.random.normal(k, shape(d)).astype(jnp.bfloat16)
    return (draw(ks[0], DQK), draw(ks[1], DQK), draw(ks[2], DV),
            jax.random.normal(ks[3], shape(DV)))


def _value_and_grads(attn, agents, args):
    q, k, v, g = args
    f = jax.vmap(attn) if agents else attn

    def loss(q, k, v):
        o = f(q, k, v)
        return jnp.sum(o.astype(jnp.float32) * g), o

    (_, o), grads = jax.jit(jax.value_and_grad(
        loss, argnums=(0, 1, 2), has_aux=True))(q, k, v)
    return o, grads


@pytest.mark.parametrize("agents", [0, 3])
def test_causal_flash_matches_chunked_attention(agents):
    """Forward output and the gradients of q, k and v, bare and under an
    outer ``jax.vmap`` over agents (the engine's)."""
    args = _qkvg(agents)
    o, grads = _value_and_grads(
        lambda q, k, v: causal_flash.causal_flash(
            q, k, v, scale=SCALE, blocks=BLOCKS_128, interpret=True),
        agents, args)
    o_ref, grads_ref = _value_and_grads(
        lambda q, k, v: attention.chunked_attention(
            q, k, v, causal=True, chunk_size=S, scale=SCALE,
            q_chunk_size=128),
        agents, args)
    assert o.dtype == jnp.bfloat16 and o.shape == o_ref.shape
    o, o_ref = np.asarray(o, np.float32), np.asarray(o_ref, np.float32)
    assert np.abs(o - o_ref).max() <= 2 * U * np.abs(o_ref).max()
    for a, b in zip(grads, grads_ref):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert np.linalg.norm(a - b) <= 2 * U * np.linalg.norm(b)


def test_causal_block_fill_counts_live_blocks():
    """36 of 64 key blocks at 512 x 512 over 4,096 positions; one query
    block over two key blocks where the query block is twice as tall."""
    at = lambda bq, bkv: causal_flash.block_fill(4096, dataclasses.replace(
        causal_flash.BLOCKS, block_q=bq, block_kv=bkv))
    assert at(512, 512) == 36 / 64
    assert at(128, 128) == (32 * 33 / 2) / 32 ** 2
    assert at(1024, 512) == (2 + 4 + 6 + 8) / 32


def test_block_sizes_follow_the_tiling():
    assert causal_flash.block_sizes(4096) == causal_flash.BLOCKS
    cut = causal_flash.block_sizes(256)
    assert all(getattr(cut, f) == 256 for f in causal_flash._SIZES
               if getattr(causal_flash.BLOCKS, f) is not None)
    for s in (200, 4000, 4096 + 128 * 3, 64):
        assert causal_flash.block_sizes(s) is None


def test_mla_path_is_chunked_on_the_cpu():
    assert attention.mla_attention_path(4096) == "chunked"
    assert attention.mla_block_fill(4096) == 1.0


@pytest.mark.parametrize("s", [4096, 256, 4000, 200])
def test_mla_path_is_flash_on_a_tpu_where_the_tiling_holds(s, monkeypatch):
    """Told the backend is a TPU, the cell's 4,096 positions (and any
    multiple of the blocks) take the kernel; other lengths keep
    ``chunked_attention``."""
    monkeypatch.setattr(attention, "on_tpu", lambda: True)
    tiled = causal_flash.block_sizes(s) is not None
    assert attention.mla_attention_path(s) == ("flash" if tiled else
                                               "chunked")
    fill = attention.mla_block_fill(s)
    if tiled:
        assert fill == causal_flash.block_fill(s, causal_flash.block_sizes(s))
        assert 0.5 < fill <= 1.0
    else:
        assert fill == 1.0


# ---------------------------------------------------------------------------
# mla_block and the session on a small latent-attention model
# ---------------------------------------------------------------------------

ARCH = "deepseek-v2-lite-flash-test"
TINY = dataclasses.replace(
    get_config("deepseek-v2-lite"), name=ARCH, n_layers=2, d_model=64,
    n_heads=2, n_kv_heads=2, d_ff=96, vocab_size=256, n_experts=4, top_k=2,
    moe_d_ff=32, n_shared_experts=1, kv_lora_rank=16, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16)
N, L = 3, 128


@pytest.fixture
def tiny_arch(monkeypatch):
    monkeypatch.setitem(ARCHS, ARCH, TINY)


def _spec():
    return ExperimentSpec(
        topology=TopologySpec(kind="bidirectional_ring", params={"n": N}),
        data=DataSpec(dataset="zipf_tokens",
                      dataset_params={"vocab_size": 256, "seq_len": L},
                      batch_size=1, local_updates=1),
        inference=InferenceSpec(model="lm", arch=ARCH, lora_rank=4,
                                lora_alpha=8.0),
        run=RunSpec(n_rounds=1, seed=2_147_483_693),
        obs=ObsSpec(enabled=True, convergence=False))


def _round(monkeypatch, path):
    """One obs-enabled round; ``path`` "flash" steers the dispatch to the
    kernels, which run in interpret mode off the TPU."""
    calls = {"chunked": 0, "flash": 0}
    for name, mod, fn in (("chunked", attention, "chunked_attention"),
                          ("flash", causal_flash, "causal_flash")):
        def counted(*a, _f=getattr(mod, fn), _name=name, **kw):
            calls[_name] += 1
            return _f(*a, **kw)
        monkeypatch.setattr(mod, fn, counted)
    if path == "flash":
        monkeypatch.setattr(attention, "on_tpu", lambda: True)
    s = build_session(_spec())
    rec = s.round()
    return s, rec, calls


def test_session_records_the_chunked_path_on_the_cpu(tiny_arch, monkeypatch):
    s, rec, calls = _round(monkeypatch, "chunked")
    assert calls["chunked"] > 0 and calls["flash"] == 0
    reg = s.obs.registry
    paths = reg.counter("model.attention_path")
    assert paths.value(path="chunked") == 1 and paths.value(path="flash") == 0
    assert reg.gauge("model.attention_block_fill").value() == 1.0


def test_session_round_on_the_flash_path_matches_chunked(tiny_arch,
                                                         monkeypatch):
    """The whole LM round (agents' vmap, the layer's remat, the gradient)
    through the kernel: the same loss as the chunked round to bfloat16
    rounding, and the registry says which path ran."""
    _, ref, _ = _round(monkeypatch, "chunked")
    s, rec, calls = _round(monkeypatch, "flash")
    assert calls["flash"] > 0 and calls["chunked"] == 0
    assert abs(rec["loss"] - ref["loss"]) <= 2 * U * abs(ref["loss"])
    reg = s.obs.registry
    assert reg.counter("model.attention_path").value(path="flash") == 1
    assert reg.gauge("model.attention_block_fill").value() == 1.0  # one block
