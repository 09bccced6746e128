"""What every driver shares: the translation of a configuration and a
traffic mix into the program's ``ExperimentSpec``, per-leaf norms of a
posterior, and the profiler switch.

``build_spec`` passes each section of the configuration through to the
program's spec and reads every key it is given: a key that no program
path reads is an error, never a silent default.
"""
from __future__ import annotations

import re
from pathlib import Path

import jax
import jax.numpy as jnp

TRACE_SECONDS = 4.0  # longest stretch of a traced run that is profiled
_LEAF = re.compile(r"\['(\w+)'\]")

# keys of a configuration that describe it and drive no program path
DESCRIPTIVE = ("name", "source", "reference", "deployment", "precision",
               "reduced", "assumed", "cut")
SECTIONS = ("model", "data", "inference", "topology", "n_agents")
MODEL_KEYS = ("name", "hidden", "depth",
              # read by the reference and the counts; the driver checks
              # that the program's parameter count is ``n_params``
              "input_dim", "n_classes", "n_params")
DATA_KEYS = ("dataset", "dataset_params", "partition", "partition_params",
             "batch_size", "local_updates")
TOPOLOGY_KEYS = ("graph", "params", "edge_native")
SPEC_TRAFFIC_KEYS = ("clock", "faults", "fault_policy")


def only_keys(doc: dict, keys, where: str) -> dict:
    extra = sorted(set(doc) - set(keys))
    if extra:
        raise ValueError(f"{where}: no program path reads {extra}")
    return doc


def build_spec(cfg: dict, traffic: dict, seed: int, *, obs: bool = False):
    """The program's spec for one configuration under one traffic mix.

    The configuration names the dataset and its partition, the model and
    its widths, every inference option that differs from the program's
    default, and the graph: ``topology.graph`` with its ``params``, edge
    native (``TopologySpec.sparse``) or dense.  The traffic mix names the
    gossip clock (none: synchronous rounds) and the fault model and policy.
    Every seeded stream takes the run's seed."""
    from repro.api import (DataSpec, ExperimentSpec, InferenceSpec, ObsSpec,
                           RunSpec, TopologySpec)

    only_keys(cfg, DESCRIPTIVE + SECTIONS, "configuration")
    model = only_keys(cfg["model"], MODEL_KEYS, "configuration.model")
    data = only_keys(cfg["data"], DATA_KEYS, "configuration.data")
    topo = only_keys(cfg["topology"], TOPOLOGY_KEYS, "configuration.topology")
    clock = traffic.get("clock")
    if clock is not None:
        clock = {**clock, "seed": seed}
        if traffic.get("faults"):
            clock["faults"] = {**traffic["faults"], "seed": seed}
    elif traffic.get("faults"):
        raise ValueError("a fault model needs a gossip clock to draw it")
    graph, params = topo["graph"], dict(topo["params"])
    if topo["edge_native"]:
        topology = TopologySpec.sparse(graph, clock=clock, **params)
    elif clock is None:
        topology = TopologySpec(kind=graph, params=params)
    else:
        topology = TopologySpec.gossip(graph, params, clock)
    inference = InferenceSpec(
        model=model["name"], hidden=model["hidden"], depth=model["depth"],
        fault_policy=traffic.get("fault_policy", "strict"),
        **cfg["inference"])
    return ExperimentSpec(
        topology=topology,
        data=DataSpec(dataset=data["dataset"],
                      dataset_params={**data["dataset_params"], "seed": seed},
                      partition=data["partition"],
                      partition_params={**data["partition_params"],
                                        "n_agents": cfg["n_agents"],
                                        "seed": seed},
                      batch_size=data["batch_size"],
                      local_updates=data["local_updates"]),
        inference=inference,
        run=RunSpec(n_rounds=1, seed=seed),
        obs=ObsSpec(enabled=obs, trace=True, convergence=False),
    )


def leaf_norms(post, row0=None) -> dict:
    """Per-leaf norms over all agents of a FlatPosterior-shaped pair
    (``mean``/``rho``), less ``row0`` (one agent's initial mean and rho,
    broadcast) when given.  Keys ``mean.<leaf>``/``rho.<leaf>``."""
    out = {}
    for kind in ("mean", "rho"):
        arr = getattr(post, kind)
        if row0 is not None:
            arr = arr - row0[kind][None, :]
        for spec in post.layout.specs:
            name = _LEAF.search(spec.path).group(1)
            sl = arr[:, spec.offset:spec.offset + spec.size]
            out[f"{kind}.{name}"] = float(jnp.sqrt(jnp.sum(jnp.square(sl))))
    return out


def profile(trace_dir: Path):
    """Start the profiler without its Python-function tracer, so the traced
    run's host runs at the speed of an untraced one."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
