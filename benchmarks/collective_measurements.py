"""Standalone collective A/B measurements used by EXPERIMENTS.md §Perf.

NOT part of ``benchmarks.run`` (needs 512 placeholder devices — run it as a
fresh process):

    PYTHONPATH=src python -m benchmarks.collective_measurements

Measurements (exact — all ops are scan-exterior):
  1. MoE layer: GSPMD-inferred dispatch vs explicit expert-parallel
     all_to_all (launch/expert_parallel.py) at olmoe train_4k shard sizes.
  2. 16-agent ring consensus: dense einsum (GSPMD) vs hand-written
     shard_map ring ppermute, f32 and bf16 wire.
Outputs JSON next to the other dry-run results.
"""
import json
import os

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_config
from repro.core.graphs import bidirectional_ring_w
from repro.core.posterior import GaussianPosterior, consensus_all_agents
from repro.launch.consensus_opt import consensus_ppermute_ring
from repro.launch.dryrun import parse_collectives
from repro.launch.expert_parallel import moe_ffn_expert_parallel
from repro.models.moe import moe_ffn, moe_init

OUT = os.path.join(os.path.dirname(__file__), "results")


def _total(c):
    return sum(v["bytes"] for v in c.values())


def measure_moe() -> dict:
    mesh = jax.make_mesh((16, 16), ("data", "model"))
    cfg = get_config("olmoe-1b-7b")
    p_shape = jax.eval_shape(lambda k: moe_init(k, cfg), jax.random.key(0))
    psh_base = {
        "router": NamedSharding(mesh, P(None, None)),
        "w_gate": NamedSharding(mesh, P("model", "data", None)),
        "w_up": NamedSharding(mesh, P("model", "data", None)),
        "w_down": NamedSharding(mesh, P("model", "data", None)),
    }
    psh_ep = {
        "router": NamedSharding(mesh, P(None, None)),
        "w_gate": NamedSharding(mesh, P("model", None, None)),
        "w_up": NamedSharding(mesh, P("model", None, None)),
        "w_down": NamedSharding(mesh, P("model", None, None)),
    }
    x_sds = jax.ShapeDtypeStruct(
        (256, 4096, 2048), jnp.bfloat16, sharding=NamedSharding(mesh, P("data", None, None))
    )
    res = {}
    with mesh:
        p_sds = {k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=psh_base[k])
                 for k, v in p_shape.items()}
        low = jax.jit(lambda p, x: moe_ffn(p, x, cfg)).lower(p_sds, x_sds)
        res["gspmd_baseline"] = parse_collectives(low.compile().as_text())
        p_sds2 = {k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=psh_ep[k])
                  for k, v in p_shape.items()}
        low2 = jax.jit(
            lambda p, x: moe_ffn_expert_parallel(p, x, cfg, mesh)
        ).lower(p_sds2, x_sds)
        res["expert_parallel"] = parse_collectives(low2.compile().as_text())
    return res


def measure_ring_consensus() -> dict:
    mesh = jax.make_mesh((16, 16), ("data", "model"))
    a, pn = 16, 16 * 1024 * 1024
    sh = NamedSharding(mesh, P("data", "model"))
    sds = jax.ShapeDtypeStruct((a, pn), jnp.float32, sharding=sh)
    posts = GaussianPosterior(mean={"w": sds}, rho={"w": sds})
    W = jnp.asarray(bidirectional_ring_w(a), jnp.float32)
    res = {}
    with mesh:
        low = jax.jit(lambda q: consensus_all_agents(q, W)).lower(posts)
        res["dense_einsum_ring_W"] = parse_collectives(low.compile().as_text())
        for name, dt in (("sparse_ppermute_f32", jnp.float32),
                         ("sparse_ppermute_bf16", jnp.bfloat16)):
            low2 = jax.jit(
                lambda q, dt=dt: consensus_ppermute_ring(q, mesh, "data", wire_dtype=dt)
            ).lower(posts)
            res[name] = parse_collectives(low2.compile().as_text())
    return res


def main() -> None:
    moe = measure_moe()
    ring = measure_ring_consensus()
    for group, res in (("moe_ep", moe), ("ring_consensus", ring)):
        for name, c in res.items():
            print(f"{group}/{name},{_total(c):.1f},bytes_per_device")
        with open(os.path.join(OUT, f"{group}_collectives.json"), "w") as f:
            json.dump(res, f, indent=1)


if __name__ == "__main__":
    # placeholder devices, set before the backend starts (not at import)
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    main()
