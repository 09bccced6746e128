"""Compile the Pallas kernels for a described TPU v5e, without a chip.

Interpret mode (every other Pallas test) cannot see what Mosaic refuses:
primitives it has no lowering for, blocks that break the (8, 128) tiling
rule, kernels that overrun the scoped VMEM.  These tests compile each
kernel with ``interpret=False`` for one chip of a described ``v5e:2x2``
topology at the paper MLP's width (P = 199,210 parameters per agent), and
the latent attention's causal flash kernels and the routed experts'
grouped-matmul kernels at the LM cell's shapes, and check that the program
really holds the kernel (``tpu_custom_call``).

The topology is described inside a fixture, never at import: only the
process that runs these tests loads the TPU compiler library.
"""
from __future__ import annotations

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core.flat import QUARANTINE_BOUND, gather_tables
from repro.kernels import causal_flash
from repro.kernels import consensus as kc
from repro.kernels import dispatch
from repro.kernels import grouped_matmul as gm
from repro.models import moe
from repro.models.attention import mla_softmax_scale

P_MLP = 199_210  # 784-200-200-10 MLP
DEGREE = 5  # torus in-degree + self
# the paper_mlp.ws512 gossip window: N = 512 on WS(k = 6, beta = 0.1), max
# in-degree 9 + self; 3,072 fired-edge slots + 512 self-loops
WS_N, WS_SLOTS, WS_EDGES = 512, 10, 3_072 + 512


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 - any failure means no compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a described-chip compile is written to the persistent cache but can
    # never be read back without the chip; keep it out of any cache
    jax.config.update("jax_enable_compilation_cache", False)
    return SingleDeviceSharding(topo.devices[0])


def _kernel_call(name, n, wire):
    """(fn, argument shapes) of one kernel, compiled (interpret=False)."""
    kw = dict(interpret=False, wire_dtype=wire)
    nn, vec, buf = (n, n), (n,), (n, P_MLP)
    tables = ((n, DEGREE), jnp.int32), ((n, DEGREE), jnp.float32)
    if name == "network":
        return (lambda W, m, r: kc.consensus_fused_network(W, m, r, **kw),
                [(nn, jnp.float32), (buf, jnp.float32), (buf, jnp.float32)])
    if name == "masked":
        return (lambda W, a, m, r: kc.consensus_fused_masked(W, a, m, r, **kw),
                [(nn, jnp.float32), (vec, jnp.bool_), (buf, jnp.float32),
                 (buf, jnp.float32)])
    if name == "validity":
        return (lambda m, r: kc.payload_validity_fused(
                    m, r, bound=QUARANTINE_BOUND, **kw),
                [(buf, jnp.float32), (buf, jnp.float32)])
    assert name == "masked_sparse"
    return (lambda nb, w, a, m, r: kc.consensus_fused_masked_sparse(
                nb, w, a, m, r, **kw),
            [*tables, (vec, jnp.bool_), (buf, jnp.float32),
             (buf, jnp.float32)])


KERNELS = ["network", "masked", "validity", "masked_sparse"]


@pytest.mark.parametrize("wire", ["f32", "bf16"])
@pytest.mark.parametrize("n", [16, 256])
@pytest.mark.parametrize("name", KERNELS)
def test_kernel_compiles_for_v5e_at_paper_width(one_chip, name, n, wire):
    fn, shapes = _kernel_call(name, n, wire)
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _edge_gather(n, slots, edges, p, wire):
    """The segments window's row-gather consensus, compiled: the in-graph
    table build and the masked sparse kernel, as ``consensus_flat_segments``
    runs them on TPU."""
    def fn(dst, src, w, active, mean, rho):
        nbr, wts = gather_tables(dst, src, w, n, slots, active)
        return kc.consensus_fused_masked_sparse(
            nbr, wts, active, mean, rho, interpret=False, wire_dtype=wire)

    ids, buf = ((edges,), jnp.int32), ((n, p), jnp.float32)
    return fn, [ids, ids, ((edges,), jnp.float32), ((n,), jnp.bool_), buf,
                buf]


@pytest.mark.parametrize("wire", ["f32", "bf16"])
def test_edge_list_gather_compiles_for_v5e_at_paper_width(one_chip, wire):
    """The ws512 window's consensus at N = 512, D = 10, P = 199,210: one
    Pallas call whose lane tile is the whole row and fits the scoped VMEM
    (a described-v5e compile refuses a tile that does not)."""
    block = kc.sparse_block(P_MLP)
    assert block % kc.LANES == 0 and block >= P_MLP
    assert kc._SPARSE_LIVE_TILES * 4 * block <= kc._VMEM_LIMIT * 3 // 4
    fn, shapes = _edge_gather(WS_N, WS_SLOTS, WS_EDGES, P_MLP, wire)
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
    assert "tpu_custom_call" in jax.jit(fn).lower(*args).compile().as_text()
    assert WS_N * WS_SLOTS <= kc.SPARSE_TABLE_ENTRIES


def test_mla_flash_attention_compiles_for_v5e_at_cell_shapes(one_chip):
    """``mla_block``'s attention on the chip at the LM cell's shapes (8
    agents, S 4,096, 16 heads, q/k 192, v 128), forward and backward under
    the layer's remat and the agents' vmap: the forward and the fused
    backward kernels, and no float32 score buffer of a 256-query block
    over all 4,096 keys (``chunked_attention``'s ``[.., 16, 256, 4096]``)."""
    s, blocks = 4096, causal_flash.block_sizes(4096)
    scale = mla_softmax_scale(get_config("deepseek-v2-lite"))
    attn = jax.checkpoint(lambda q, k, v: causal_flash.causal_flash(
        q, k, v, scale=scale, blocks=blocks, interpret=False))

    def loss(q, k, v, g):
        return jnp.sum(jax.vmap(attn)(q, k, v).astype(jnp.float32) * g)

    shape = lambda d, dt=jnp.bfloat16: jax.ShapeDtypeStruct(
        (8, 1, s, 16, d), dt, sharding=one_chip)
    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        shape(192), shape(192), shape(128), shape(128, jnp.float32)
    ).compile().as_text()
    assert "tpu_custom_call" in text
    for kernel in ("splash_mha_fwd", "splash_mha_dkv"):  # dkv fuses dq
        assert kernel in text
    assert not re.search(r"f32\[[\d,]*16,256,4096\]", text)


@pytest.mark.parametrize("key", sorted(gm.TILINGS))
def test_grouped_matmul_compiles_for_v5e_at_cell_shapes(one_chip, key):
    """Each of the routed experts' four grouped products at the LM cell's
    196,608 rows (8 agents x 4,096 tokens x top-6) over 64 experts, at the
    table's tiles: the kernel's blocks fit the scoped VMEM."""
    k, n, transpose_rhs = key
    rhs = (64, n, k) if transpose_rhs else (64, k, n)
    shape = lambda s, dt=jnp.bfloat16: jax.ShapeDtypeStruct(
        s, dt, sharding=one_chip)
    fn = lambda x, w, g: gm.grouped_matmul(
        x, w, g, transpose_rhs=transpose_rhs, interpret=False)
    text = jax.jit(fn).lower(shape((196_608, k)), shape(rhs),
                             shape((64,), jnp.int32)).compile().as_text()
    assert "tpu_custom_call" in text


def test_routed_experts_run_the_kernel_under_experts_on_v5e(one_chip,
                                                            monkeypatch):
    """``routed_experts`` forward and backward at the cell's widths (2
    agents x 256 tokens, top-6 of 64 experts, d 2,048, expert width 1,408)
    on the TPU path: nine kernel calls (three forward, three recomputed,
    three activation-gradient), each with ``experts`` on its scope path,
    which the benchmark's scope reader needs, and no ``ragged_dot`` left."""
    monkeypatch.setattr(moe, "on_tpu", lambda: True)
    monkeypatch.setattr(dispatch, "on_tpu", lambda: True)
    a, t, d, f, e, k = 2, 256, 2048, 1408, 64, 6

    def loss(x, w, idx, w_gate, w_up, w_down):
        with jax.named_scope("experts"):
            y = jax.vmap(lambda x, w, i: moe.routed_experts(
                x, w, i, w_gate, w_up, w_down))(x, w, idx)
        return jnp.sum(y.astype(jnp.float32)), y

    shape = lambda s, dt=jnp.bfloat16: jax.ShapeDtypeStruct(
        s, dt, sharding=one_chip)
    text = jax.jit(jax.value_and_grad(loss, (0, 1), has_aux=True)).lower(
        shape((a, t, d)), shape((a, t, k), jnp.float32),
        shape((a, t, k), jnp.int32), shape((e, d, f)), shape((e, d, f)),
        shape((e, f, d))).compile().as_text()
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 9
    for line in calls:
        scope = re.search(r'op_name="([^"]*)"', line).group(1)
        assert re.search(r"(^|[/(])experts\)*/", scope), scope
    assert "%ragged-dot" not in text  # XLA's ragged_dot custom calls


def test_gather_tables_at_the_smem_bound_compile(one_chip):
    """N x D = SPARSE_TABLE_ENTRIES: the scalar-prefetched tables still fit
    the v5e's 1 MiB SMEM (a narrow row keeps the HBM side small)."""
    n, slots = 8_192, kc.SPARSE_TABLE_ENTRIES // 8_192
    fn, shapes = _edge_gather(n, slots, 4 * n, 1_024, "f32")
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
    assert "tpu_custom_call" in jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("p", [128, 1_000, P_MLP, 400_000])
def test_sparse_block_tiles_whole_rows_within_vmem(p):
    """The sparse kernels' lane block: a multiple of 128 lanes, the whole
    row while its live tiles fit 3/4 of the scoped VMEM, else equal tiles
    of 8 x 128 multiples that do."""
    block = kc.sparse_block(p)
    assert block % kc.LANES == 0
    assert kc._SPARSE_LIVE_TILES * 4 * block <= kc._VMEM_LIMIT * 3 // 4
    if block < p:
        # equal tiles: the padding is less than one 8 x 128 group a tile
        tiles = -(-p // block)
        assert block % (8 * kc.LANES) == 0
        assert tiles * block - p < tiles * 8 * kc.LANES
    else:
        assert block - p < kc.LANES


@pytest.mark.parametrize("name", KERNELS)
def test_compiled_kernel_refuses_f16_wire(name):
    """Mosaic cannot legalize the f16 round trip on v5e
    (``tpu.pack_subelements``): a compiled kernel refuses f16 up front
    instead of failing inside the compiler (or quietly running elsewhere)."""
    fn, shapes = _kernel_call(name, 8, "f16")
    args = [jnp.zeros(s, dt) for s, dt in shapes]
    with pytest.raises(ValueError, match="f16"):
        fn(*args)


@pytest.mark.parametrize("n", [16, 256, 1024])
def test_lane_block_fits_scoped_vmem(n):
    """The dense kernels' default lane block keeps ~12 fp32 [n, block]
    tiles plus the double-buffered [n, n] W inside 3/4 of the 16 MiB
    scoped VMEM, and stays a positive multiple of 128."""
    block = kc.lane_block(n, 4 * n * n)
    assert block % 128 == 0 and 128 <= block <= kc.DEFAULT_BLOCK
    if n <= 256:
        assert 12 * 4 * n * block + 2 * 4 * n * n <= 12 * 2**20
