"""Multi-pod dry-run: prove the distribution config is coherent without
hardware.  For every (architecture x input shape x mesh) this lowers and
compiles the production step function against ShapeDtypeStruct stand-ins
(no allocation), then extracts:

  * memory_analysis()  — per-device bytes (fits / doesn't fit)
  * cost_analysis()    — HLO FLOPs + bytes accessed (roofline numerator)
  * collective bytes   — parsed from the post-SPMD HLO text per collective
                         kind (all-gather / all-reduce / reduce-scatter /
                         all-to-all / collective-permute)

and derives the three roofline terms (seconds) for TPU v5e:
  compute    = FLOPs_global / (chips * 197e12)
  memory     = bytes_global / (chips * 819e9)
  collective = coll_bytes_global / (chips * 50e9)

Usage:
  python -m repro.launch.dryrun --arch qwen3-8b --shape train_4k [--multi-pod]
  python -m repro.launch.dryrun --all [--multi-pod] --out-dir benchmarks/results
"""
import argparse
import json
import os
import re
import sys
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import INPUT_SHAPES, get_config, list_archs
from repro.launch.mesh import (
    HBM_BW,
    ICI_BW,
    PEAK_FLOPS_BF16,
    make_production_mesh,
    mesh_n_agents,
    mesh_n_chips,
)
from repro.launch.sharding import (
    batch_pspec,
    cache_shardings,
    param_shardings,
    replicated,
)
from repro.launch.steps import (
    BayesTrainState,
    init_train_state,
    make_agent_cache,
    make_decode_step,
    make_prefill_step,
    make_train_round_step,
    serve_params,
)
from repro.optim import adam

COLLECTIVE_KINDS = (
    "all-gather",
    "all-reduce",
    "reduce-scatter",
    "all-to-all",
    "collective-permute",
)

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "s32": 4, "s16": 2, "s8": 1, "u64": 8, "u32": 4, "u16": 2,
    "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_SHAPE_RE = re.compile(r"\b([a-z0-9]+)\[([0-9,]*)\]")


def _shape_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def parse_collectives(hlo_text: str) -> dict[str, dict[str, float]]:
    """Per-device collective op output bytes by kind, from post-SPMD HLO."""
    out = {k: {"count": 0, "bytes": 0} for k in COLLECTIVE_KINDS}
    for line in hlo_text.splitlines():
        s = line.strip()
        if "=" not in s:
            continue
        lhs, rhs = s.split("=", 1)
        rhs = rhs.strip()
        for kind in COLLECTIVE_KINDS:
            # match op name at the start of the RHS expression, e.g.
            #   %ag = bf16[...] all-gather(...)
            m = re.search(r"\b" + kind + r"(-start|-done)?\(", rhs)
            if m and not rhs.startswith("fusion"):
                if m.group(1) == "-done":
                    break  # counted at -start
                # result type(s) appear before the op name
                type_part = rhs[: m.start()]
                b = _shape_bytes(type_part)
                out[kind]["count"] += 1
                out[kind]["bytes"] += b
                break
    return out


def count_params(shape_tree: Any) -> int:
    return sum(int(np.prod(l.shape)) for l in jax.tree.leaves(shape_tree))


def count_active_params(params_shape: Any, cfg) -> int:
    """Matmul-active params per token for the 6ND / 2ND estimate:
    * expert stacks scaled by top_k / n_experts (MoE active fraction),
    * the input embedding table is a gather (0 matmul FLOPs) unless tied,
      in which case it is counted once for the unembed matmul."""
    total = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(params_shape)[0]:
        name = "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)
        n = int(np.prod(leaf.shape))
        if cfg.n_experts and "moe" in name and (
            "w_gate" in name or "w_up" in name or "w_down" in name
        ):
            n = n * cfg.top_k // cfg.n_experts
        if "embed" in name and "emb" in name and not cfg.tie_embeddings:
            n = 0  # pure gather
        total += n
    return total


def _with_shardings(shape_tree: Any, sharding_tree: Any) -> Any:
    return jax.tree.map(
        lambda l, s: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=s),
        shape_tree,
        sharding_tree,
    )


def input_specs(cfg, shape, mesh, *, mode: str) -> dict[str, Any]:
    """ShapeDtypeStruct stand-ins for every model input (weak-type-correct,
    shardable, no device allocation)."""
    from jax.sharding import NamedSharding

    a = mesh_n_agents(mesh)
    # ceil-divide: when the global batch can't split across agents (e.g.
    # long_500k batch=1 on 2 pods) each pod serves its own replica of the
    # request; the effective global batch is a * b.
    b = max(1, -(-shape.global_batch // a))
    s = shape.seq_len

    def sds(shp, dtype):
        spec = batch_pspec(mesh, shp)
        return jax.ShapeDtypeStruct(shp, dtype, sharding=NamedSharding(mesh, spec))

    out: dict[str, Any] = {}
    if mode == "train":
        n_text = s
        if cfg.frontend == "vision_stub":
            n_text = s - cfg.n_patches
            out["patches"] = sds((a, b, cfg.n_patches, cfg.d_model), jnp.float32)
        if cfg.frontend == "audio_stub":
            out["frames"] = sds((a, b, cfg.encoder_seq, cfg.d_model), jnp.float32)
        out["tokens"] = sds((a, b, n_text), jnp.int32)
        out["targets"] = sds((a, b, s if cfg.frontend == "vision_stub" else n_text), jnp.int32)
        # vlm targets cover the full (patch+text) logit range
        if cfg.frontend == "vision_stub":
            out["targets"] = sds((a, b, s), jnp.int32)
    elif mode == "prefill":
        n_text = s - (cfg.n_patches if cfg.frontend == "vision_stub" else 0)
        out["tokens"] = sds((a, b, n_text), jnp.int32)
        if cfg.frontend == "vision_stub":
            out["patches"] = sds((a, b, cfg.n_patches, cfg.d_model), jnp.float32)
        if cfg.frontend == "audio_stub":
            out["frames"] = sds((a, b, cfg.encoder_seq, cfg.d_model), jnp.float32)
    elif mode == "decode":
        out["tokens"] = sds((a, b, 1), jnp.int32)
        if cfg.frontend == "audio_stub":
            out["frames"] = sds((a, b, cfg.encoder_seq, cfg.d_model), jnp.float32)
    return out


def long_context_window_override(cfg, shape) -> int | None:
    """Dense/full-attention archs run long_500k only via the SWA variant."""
    if shape.name != "long_500k":
        return None
    if cfg.family in ("ssm", "hybrid"):
        return None  # native sub-quadratic
    return cfg.long_context_window


def dryrun_one(
    arch: str,
    shape_name: str,
    multi_pod: bool,
    *,
    kv_quant: bool = False,
    no_remat: bool = False,
    consensus_impl: str = "einsum",
    consensus_wire_dtype: str = "",
    mesh_shape: tuple[int, int] | None = None,
    variant: str = "",
) -> dict[str, Any]:
    cfg = get_config(arch)
    shape = INPUT_SHAPES[shape_name]
    t0 = time.time()

    if shape.name == "long_500k" and not cfg.long_context_ok:
        return {
            "arch": arch,
            "shape": shape_name,
            "mesh": "multi" if multi_pod else "single",
            "status": "skipped",
            "reason": "full-attention enc-dec; long_500k out of family scope "
                      "(DESIGN.md §5)",
        }

    if mesh_shape is not None:
        mesh = jax.make_mesh(mesh_shape, ("data", "model"))
    else:
        mesh = make_production_mesh(multi_pod=multi_pod)
    a = mesh_n_agents(mesh)
    chips = mesh_n_chips(mesh)
    window = long_context_window_override(cfg, shape)
    wire_dtype = {"": None, "f32": None, "bf16": jnp.bfloat16}[consensus_wire_dtype]
    cache_dtype = jnp.int8 if kv_quant else jnp.bfloat16

    from repro.models import init_params

    params_shape = jax.eval_shape(
        lambda k: jax.vmap(lambda kk: init_params(cfg, kk))(jax.random.split(k, a)),
        jax.random.key(0),
    )

    with mesh:
        if shape.kind == "train":
            opt = adam()
            W = jnp.full((a, a), 1.0 / a)
            state_shape = jax.eval_shape(
                lambda k: init_train_state(k, cfg, a, opt), jax.random.key(0)
            )
            state_shard = param_shardings(state_shape, mesh, agent_leading=True)
            step = make_train_round_step(
                cfg, W, opt=opt, remat=not no_remat,
                consensus_impl=consensus_impl,
                consensus_wire_dtype=wire_dtype,
                mesh=mesh,
                posterior_shardings=state_shard.posterior
                if consensus_impl == "ppermute" else None,
            )
            state_sds = _with_shardings(state_shape, state_shard)
            batch_sds = input_specs(cfg, shape, mesh, mode="train")
            key_sds = jax.ShapeDtypeStruct(
                jax.eval_shape(lambda: jax.random.key(0)).shape,
                jax.eval_shape(lambda: jax.random.key(0)).dtype,
                sharding=replicated(mesh),
            )
            lowered = jax.jit(step).lower(state_sds, batch_sds, key_sds)
            n_active = count_active_params(params_shape, cfg) // a
            flops_factor = 6.0
            tokens = shape.global_batch * shape.seq_len
        else:
            # serving paths use posterior-mean bf16 weights
            serve_shape = jax.tree.map(
                lambda l: jax.ShapeDtypeStruct(l.shape, jnp.bfloat16 if l.dtype == jnp.float32 else l.dtype),
                params_shape,
            )
            serve_shard = param_shardings(serve_shape, mesh, agent_leading=True)
            serve_sds = _with_shardings(serve_shape, serve_shard)
            b_local = max(1, -(-shape.global_batch // a))
            capacity = shape.seq_len
            if window:
                capacity = min(capacity, window)
            cache_shape = jax.eval_shape(
                lambda: make_agent_cache(cfg, a, b_local, capacity, dtype=cache_dtype)
            )
            cache_shard = cache_shardings(cache_shape, mesh, agent_leading=True)
            cache_sds = _with_shardings(cache_shape, cache_shard)
            batch_sds = input_specs(cfg, shape, mesh, mode=shape.kind)
            if shape.kind == "prefill":
                step = make_prefill_step(cfg, window_override=window)
                lowered = jax.jit(step).lower(serve_sds, batch_sds, cache_sds)
                flops_factor = 2.0
                tokens = shape.global_batch * shape.seq_len
            else:  # decode
                step = make_decode_step(cfg, window_override=window)
                pos_sds = jax.ShapeDtypeStruct((), jnp.int32, sharding=replicated(mesh))
                frames_sds = batch_sds.get("frames")
                lowered = jax.jit(step, static_argnames=()).lower(
                    serve_sds, batch_sds["tokens"], pos_sds, cache_sds, frames_sds
                )
                flops_factor = 2.0
                tokens = shape.global_batch  # one token per sequence
            n_active = count_active_params(params_shape, cfg) // a

        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    # ---- analyses ----
    try:
        mem = compiled.memory_analysis()
        mem_info = {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "peak_bytes": getattr(mem, "peak_memory_in_bytes", None),
        }
    except Exception as e:  # pragma: no cover
        mem_info = {"error": str(e)}
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    flops_dev = float(cost.get("flops", 0.0))
    bytes_dev = float(cost.get("bytes accessed", 0.0))
    hlo = compiled.as_text()
    coll = parse_collectives(hlo)
    coll_bytes_dev = sum(v["bytes"] for v in coll.values())

    flops_global = flops_dev * chips
    bytes_global = bytes_dev * chips
    coll_bytes_global = coll_bytes_dev * chips
    # RAW HLO terms.  CAVEAT (validated, see costmodel.py docstring): XLA
    # cost_analysis counts while-loop bodies ONCE, so these undercount
    # anything inside the layer/chunk scans by the trip counts.  They remain
    # exact for ops outside the scans (consensus collectives, embed/unembed)
    # and for relative comparisons of same-structure programs.
    t_compute = flops_global / (chips * PEAK_FLOPS_BF16)
    t_memory = bytes_global / (chips * HBM_BW)
    t_coll = coll_bytes_global / (chips * ICI_BW)
    terms = {"compute": t_compute, "memory": t_memory, "collective": t_coll}

    # ANALYTIC terms (trip-count-correct): the §Roofline table's source.
    from repro.launch.costmodel import analytic_costs

    analytic = analytic_costs(
        cfg,
        mode=shape.kind,
        batch_global=(max(1, -(-shape.global_batch // a))) * a,
        seq_len=shape.seq_len,
        n_agents=a,
        data_shards=mesh.shape["data"],
        model_shards=mesh.shape["model"],
        n_matmul_params=n_active,
        n_total_params=count_params(params_shape) // a,
        window=window,
        kv_bytes=1.0 + 4.0 / cfg.hd if kv_quant else 2.0,
    )
    dominant = analytic["dominant"]

    model_flops = flops_factor * n_active * tokens
    useful_ratio = model_flops / flops_global if flops_global else 0.0

    return {
        "arch": arch,
        "shape": shape_name,
        "mesh": "multi" if multi_pod else "single",
        "variant": variant,
        "mesh_shape": dict(mesh.shape),
        "kv_quant": kv_quant,
        "consensus_impl": consensus_impl,
        "consensus_wire_dtype": consensus_wire_dtype or "f32",
        "status": "ok",
        "n_agents": a,
        "chips": chips,
        "window_override": window,
        "params_per_agent": count_params(params_shape) // a,
        "active_params_per_agent": n_active,
        "tokens_per_step": tokens,
        "flops_per_device": flops_dev,
        "bytes_per_device": bytes_dev,
        "collectives": coll,
        "collective_bytes_per_device": coll_bytes_dev,
        "hlo_roofline_seconds": terms,  # raw HLO (scan-undercounted, see caveat)
        "roofline_seconds": analytic["roofline_seconds"],  # analytic, primary
        "analytic": analytic,
        "dominant": dominant,
        "model_flops": model_flops,
        "hlo_flops_global": flops_global,
        "useful_flops_ratio": useful_ratio,
        "memory_analysis": mem_info,
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(INPUT_SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out-dir", default="benchmarks/results")
    # §Perf variant knobs
    ap.add_argument("--variant", default="", help="tag for the output filename")
    ap.add_argument("--kv-quant", action="store_true", help="int8 KV cache")
    ap.add_argument("--no-remat", action="store_true", help="disable activation rematerialization")
    ap.add_argument("--consensus-impl", default="einsum", choices=["einsum", "ppermute", "none"])
    ap.add_argument("--consensus-dtype", default="", choices=["", "f32", "bf16"])
    ap.add_argument("--mesh-shape", default="", help="DxM single-pod override, e.g. 32x8")
    args = ap.parse_args()
    mesh_shape = None
    if args.mesh_shape:
        d_, m_ = args.mesh_shape.split("x")
        mesh_shape = (int(d_), int(m_))

    combos = []
    if args.all:
        for arch in list_archs():
            if arch == "repro-100m":
                continue
            for shp in INPUT_SHAPES:
                combos.append((arch, shp))
    else:
        if not args.arch or not args.shape:
            ap.error("need --arch and --shape (or --all)")
        combos = [(args.arch, args.shape)]

    os.makedirs(args.out_dir, exist_ok=True)
    failures = 0
    for arch, shp in combos:
        tag = f"{arch}_{shp}_{'multi' if args.multi_pod else 'single'}"
        if args.variant:
            tag += f"_{args.variant}"
        try:
            res = dryrun_one(
                arch, shp, args.multi_pod,
                kv_quant=args.kv_quant,
                no_remat=args.no_remat,
                consensus_impl=args.consensus_impl,
                consensus_wire_dtype=args.consensus_dtype,
                mesh_shape=mesh_shape,
                variant=args.variant,
            )
        except Exception as e:  # noqa: BLE001
            res = {
                "arch": arch, "shape": shp,
                "mesh": "multi" if args.multi_pod else "single",
                "status": "error", "error": f"{type(e).__name__}: {e}",
            }
            failures += 1
        path = os.path.join(args.out_dir, f"dryrun_{tag}.json")
        with open(path, "w") as f:
            json.dump(res, f, indent=1)
        dom = res.get("dominant", "-")
        print(
            f"[{res['status']:7s}] {arch:26s} {shp:12s} "
            f"mesh={res['mesh']:6s} dominant={dom} "
            f"compile={res.get('compile_s', '-')}s",
            flush=True,
        )
        if res["status"] == "error":
            print("   ", res["error"], flush=True)
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    # 512 placeholder CPU devices for the production meshes.  Set here, not
    # at import: importing this module must not change another program's
    # XLA flags (importing jax does not start the backend, so this is early
    # enough).
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    main()
