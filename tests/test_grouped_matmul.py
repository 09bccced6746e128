"""The grouped-matmul kernel (``repro.kernels.grouped_matmul``) in interpret
mode against ``jax.lax.ragged_dot``, its tile fill, and the routed
experts' dispatch between them (``repro.models.moe.expert_matmul_path``).

Tolerances come from bfloat16 rounding (unit roundoff u = 2^-8): both
products accumulate in float32 and round once to bfloat16, in orders that
may differ, so an output may differ by two bfloat16 spacings of the
largest entry; a gradient, through the bf16 activations of the backward,
by 2u in norm."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import (DataSpec, ExperimentSpec, InferenceSpec, ObsSpec,
                       RunSpec, TopologySpec, build_session)
from repro.configs import get_config
from repro.configs.registry import ARCHS
from repro.kernels import grouped_matmul as gm
from repro.models import moe

U = 2.0 ** -8
M, K, N = 512, 256, 384
TILES = (128, 128, 128)
SIZES = {
    "empty_groups": [200, 0, 312, 0],
    "unaligned": [100, 37, 250, 125],
    "one_expert": [0, 512, 0, 0],
    "all_equal": [128, 128, 128, 128],
}


def _operands(transpose_rhs):
    kx, kw = jax.random.split(jax.random.key(2_147_483_659))
    lhs = jax.random.normal(kx, (M, K)).astype(jnp.bfloat16)
    rhs = (jax.random.normal(kw, (4, K, N)) / 16).astype(jnp.bfloat16)
    return lhs, (jnp.swapaxes(rhs, 1, 2) if transpose_rhs else rhs), rhs


@pytest.mark.parametrize("transpose_rhs", [False, True])
@pytest.mark.parametrize("case", sorted(SIZES))
def test_grouped_matmul_matches_ragged_dot(case, transpose_rhs):
    lhs, rhs, rhs_kn = _operands(transpose_rhs)
    sizes = jnp.asarray(SIZES[case], jnp.int32)
    out = gm.grouped_matmul(lhs, rhs, sizes, transpose_rhs=transpose_rhs,
                            tiling=TILES, interpret=True)
    ref = jax.lax.ragged_dot(lhs, rhs_kn, sizes)
    assert out.dtype == jnp.bfloat16 and out.shape == (M, N)
    out, ref = np.asarray(out, np.float32), np.asarray(ref, np.float32)
    assert np.abs(out - ref).max() <= 2 * U * np.abs(ref).max()


def _fill_by_rows(sizes, tm):
    """Each non-empty group computes every tm-row tile holding one of its
    rows: count them row by row."""
    start, tiles = 0, 0
    for s in sizes:
        tiles += len({r // tm for r in range(start, start + s)})
        start += s
    return sum(sizes) / (tiles * tm)


@pytest.mark.parametrize("tm", [128, 256])
@pytest.mark.parametrize("case", sorted(SIZES))
def test_tile_fill_counts_the_tiles_each_group_touches(case, tm):
    assert gm.tile_fill(SIZES[case], tm) == pytest.approx(
        _fill_by_rows(SIZES[case], tm), rel=1e-12)


def test_tile_fill_of_skewed_groups_at_the_cell_size():
    """196,608 rows over 64 groups: 63 group starts inside a tile cost at
    most 63 extra tiles."""
    rng = np.random.default_rng(7)
    sizes = rng.multinomial(196_608, rng.dirichlet(np.full(64, 0.5)))
    for tm in (256, 512, 1024):
        fill = gm.tile_fill(sizes, tm)
        assert fill == pytest.approx(_fill_by_rows(sizes, tm), rel=1e-12)
        assert 196_608 / (196_608 + 63 * tm) <= fill <= 1.0


def test_tilings_cover_the_cell_products():
    """The four products of DeepSeek-V2-Lite's routed experts (d 2,048,
    expert width 1,408) at 196,608 rows; a row count that is no multiple of
    tm, and other widths, have none."""
    m, d, f = 196_608, 2048, 1408
    for k, n in ((d, f), (f, d)):
        for t in (False, True):
            tm, tk, tn = gm.tiling_for(m, k, n, t)
            assert m % tm == 0 and tm % 8 == 0
            assert tk % 128 == 0 and tk <= k and tn % 128 == 0 and tn <= n
    assert gm.tiling_for(m + 8, d, f) is None
    assert gm.tiling_for(m, 64, 32) is None
    with pytest.raises(ValueError, match="no tiling"):
        gm.grouped_matmul(jnp.zeros((16, 64), jnp.bfloat16),
                          jnp.zeros((2, 64, 32), jnp.bfloat16),
                          jnp.array([8, 8], jnp.int32))


# ---------------------------------------------------------------------------
# the routed experts' dispatch
# ---------------------------------------------------------------------------

CELL = (196_608, 2048, 1408)


def test_expert_path_is_ragged_dot_on_the_cpu():
    assert moe.expert_matmul_path(*CELL) == "ragged_dot"
    assert moe.expert_tile_fill([3072] * 64, *CELL) == 1.0


@pytest.mark.parametrize("m", [196_608, 24_576, 196_608 + 8, 1000])
def test_expert_path_is_the_kernel_on_a_tpu_where_the_tiling_holds(
        m, monkeypatch):
    """Told the backend is a TPU, the cell's widths take the kernel at
    every row count that tiles (all agents' pairs, or one agent's); other
    row counts keep ``ragged_dot``."""
    monkeypatch.setattr(moe, "on_tpu", lambda: True)
    tiled = all(gm.tiling_for(m, a, b, t) is not None
                for a, b in ((2048, 1408), (1408, 2048))
                for t in (False, True))
    assert moe.expert_matmul_path(m, 2048, 1408) == (
        "pallas_gmm" if tiled else "ragged_dot")
    assert tiled == (m in (196_608, 24_576))


T, D, F, E, TOP = 64, 256, 128, 8, 2


def _experts(agents):
    """(loss, its arguments): the routed experts of ``agents`` vmapped
    agents, summed against a fixed cotangent."""
    ks = jax.random.split(jax.random.key(2_147_483_693), 6)
    x = jax.random.normal(ks[0], (agents, T, D)).astype(jnp.bfloat16)
    weights, idx = jax.lax.top_k(
        jax.nn.softmax(jax.random.normal(ks[1], (agents, T, E))), TOP)
    w_gate, w_up = ((jax.random.normal(k, (E, D, F)) / 16).astype(
        jnp.bfloat16) for k in ks[2:4])
    w_down = (jax.random.normal(ks[4], (E, F, D)) / 11).astype(jnp.bfloat16)
    g = jax.random.normal(ks[5], (agents, T, D))

    def loss(x, weights):
        y = jax.vmap(lambda x, w, i: moe.routed_experts(
            x, w, i, w_gate, w_up, w_down))(x, weights, idx)
        return jnp.sum(y.astype(jnp.float32) * g), y

    return jax.value_and_grad(loss, argnums=(0, 1), has_aux=True), (x, weights)


def _count(jaxpr, primitive: str) -> int:
    """Equations of ``primitive`` in ``jaxpr`` and every jaxpr inside it."""
    n = 0
    for eqn in jaxpr.eqns:
        n += eqn.primitive.name == primitive
        for p in eqn.params.values():
            for sub in p if isinstance(p, (list, tuple)) else [p]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    n += _count(sub, primitive)
    return n


@pytest.mark.parametrize("agents", [1, 3])
def test_routed_experts_on_the_kernel_match_ragged_dot(agents, monkeypatch):
    """Output and the gradients in the tokens and the routing weights, the
    agents folded into one product of agents x 128 rows: the kernel's
    path (interpret mode) against ``ragged_dot``'s, each with the nine
    grouped products of a forward and backward pass (three forward, three
    recomputed, three activation-gradient; no weight gradients)."""
    fn, args = _experts(agents)
    assert _count(jax.make_jaxpr(fn)(*args).jaxpr, "ragged_dot_general") == 9
    (_, y_ref), grads_ref = jax.jit(fn)(*args)
    monkeypatch.setattr(moe, "on_tpu", lambda: True)
    for key in ((D, F, False), (F, D, False), (D, F, True), (F, D, True)):
        monkeypatch.setitem(gm.TILINGS, key, (128, 128, 128))
    fn, args = _experts(agents)
    jaxpr = jax.make_jaxpr(fn)(*args).jaxpr
    assert _count(jaxpr, "pallas_call") == 9
    assert _count(jaxpr, "ragged_dot_general") == 0
    (_, y), grads = jax.jit(fn)(*args)
    y, y_ref = np.asarray(y, np.float32), np.asarray(y_ref, np.float32)
    assert np.abs(y - y_ref).max() <= 2 * U * np.abs(y_ref).max()
    for a, b in zip(grads, grads_ref):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert np.linalg.norm(a - b) <= 2 * U * np.linalg.norm(b)


# ---------------------------------------------------------------------------
# the session on a small DeepSeekMoE model
# ---------------------------------------------------------------------------

ARCH = "deepseek-v2-lite-gmm-test"
TINY = dataclasses.replace(
    get_config("deepseek-v2-lite"), name=ARCH, n_layers=2, d_model=64,
    n_heads=2, n_kv_heads=2, d_ff=96, vocab_size=256, n_experts=4, top_k=2,
    moe_d_ff=32, n_shared_experts=1, kv_lora_rank=16, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16)
AGENTS, L = 3, 128


def _round(monkeypatch, path):
    """One obs-enabled round of the tiny model; ``path`` "pallas_gmm"
    gives its widths tiles, so the experts run the kernel (interpret
    mode)."""
    monkeypatch.setitem(ARCHS, ARCH, TINY)
    if path == "pallas_gmm":
        monkeypatch.setattr(moe, "on_tpu", lambda: True)
        for k, n in ((64, 32), (32, 64)):
            for t in (False, True):
                monkeypatch.setitem(gm.TILINGS, (k, n, t), (128, k, n))
    spec = ExperimentSpec(
        topology=TopologySpec(kind="bidirectional_ring",
                              params={"n": AGENTS}),
        data=DataSpec(dataset="zipf_tokens",
                      dataset_params={"vocab_size": 256, "seq_len": L},
                      batch_size=1, local_updates=1),
        inference=InferenceSpec(model="lm", arch=ARCH, lora_rank=4,
                                lora_alpha=8.0),
        run=RunSpec(n_rounds=1, seed=2_147_483_693),
        obs=ObsSpec(enabled=True, convergence=False))
    s = build_session(spec)
    return s, s.round()


def test_session_records_the_ragged_dot_path_on_the_cpu(monkeypatch):
    s, _ = _round(monkeypatch, "ragged_dot")
    reg = s.obs.registry
    paths = reg.counter("model.expert_matmul_path")
    assert paths.value(path="ragged_dot") == 1
    assert paths.value(path="pallas_gmm") == 0
    assert reg.gauge("model.expert_tile_fill").value() == 1.0


def test_session_round_on_the_kernel_matches_ragged_dot(monkeypatch):
    """The whole LM round (agents' vmap, the layers' remat, the gradient)
    through the kernel: the loss of the ragged_dot round to bfloat16
    rounding; the registry says which path ran and how full its tiles
    were (3 agents x 128 tokens x top-2 = 768 rows, tm 128)."""
    _, ref = _round(monkeypatch, "ragged_dot")
    s, rec = _round(monkeypatch, "pallas_gmm")
    assert abs(rec["loss"] - ref["loss"]) <= 2 * U * abs(ref["loss"])
    reg = s.obs.registry
    assert reg.counter("model.expert_matmul_path").value(
        path="pallas_gmm") == 1
    tokens = np.asarray(s.engine.last_aux["expert_tokens"]).sum(axis=0)
    fill = reg.gauge("model.expert_tile_fill").value()
    assert fill == min(gm.tile_fill(row, 128) for row in tokens)
    assert 768 / (768 + 3 * 128) <= fill <= 1.0
