"""DataSpec -> concrete data: dataset, per-agent shards, round sampler,
held-out test set.  One builder per dataset family; every builder enforces
the spec/topology agent-count agreement eagerly."""
from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.api.spec import DataSpec
from repro.data import linreg as linreg_mod
from repro.data import partition as partition_mod
from repro.data import synthetic
from repro.data.pipeline import (
    AgentDataset,
    make_lm_batch_sampler,
    make_round_batches,
)

_DATASETS = {
    "synthetic_classification": synthetic.make_synthetic_classification,
    "mnist_like": synthetic.mnist_like,
    "fmnist_like": synthetic.fmnist_like,
}


@dataclasses.dataclass
class DataBundle:
    """Concrete data behind a Session: sampler(key, round) -> batches pytree
    with leading [N, u, B] axes, plus the test set for ``evaluate``."""

    kind: str  # "classification" | "linreg" | "tokens"
    n_agents: int
    sampler: Callable[[jax.Array, int], Any]
    x_test: np.ndarray | None = None
    y_test: np.ndarray | None = None
    dim: int = 0
    n_classes: int = 0
    dataset: Any = None  # the underlying SyntheticClassification / LinRegTask
    test_phi: np.ndarray | None = None  # linreg global test features
    test_y: np.ndarray | None = None


def _partition(spec: DataSpec, ds) -> list:
    params = dict(spec.partition_params)
    if spec.partition == "iid":
        return partition_mod.partition_iid(ds.x_train, ds.y_train, **params)
    if spec.partition == "by_label":
        return partition_mod.partition_by_label(ds.x_train, ds.y_train, **params)
    if spec.partition == "star":
        return partition_mod.star_partition(ds.x_train, ds.y_train, **params)
    if spec.partition == "grid":
        return partition_mod.grid_partition(ds.x_train, ds.y_train, **params)
    raise ValueError(f"unknown partition {spec.partition!r}")


def build_data(spec: DataSpec, n_agents: int) -> DataBundle:
    if spec.dataset == "linreg":
        return _build_linreg(spec, n_agents)
    if spec.dataset == "zipf_tokens":
        return _build_tokens(spec, n_agents)
    ds = _DATASETS[spec.dataset](**dict(spec.dataset_params))
    shards = _partition(spec, ds)
    if len(shards) != n_agents:
        raise ValueError(
            f"partition {spec.partition!r} produced {len(shards)} agent "
            f"shards but the topology has {n_agents} agents"
        )
    data = AgentDataset.from_shards(
        [(x.astype(np.float32), y.astype(np.int32)) for x, y in shards]
    )
    sampler = make_round_batches(data, spec.batch_size, spec.local_updates)
    return DataBundle(
        kind="classification",
        n_agents=n_agents,
        sampler=sampler,
        x_test=ds.x_test,
        y_test=ds.y_test,
        dim=ds.dim,
        n_classes=ds.n_classes,
        dataset=ds,
    )


def _build_linreg(spec: DataSpec, n_agents: int) -> DataBundle:
    params = dict(spec.dataset_params)
    params.setdefault("n_agents", n_agents)
    task = linreg_mod.make_linreg_task(**params)
    if task.n_agents != n_agents:
        raise ValueError(
            f"linreg task has {task.n_agents} agents but the topology has {n_agents}"
        )
    b = spec.batch_size

    def sampler(key: jax.Array, round_idx: int):
        # np-backed task sampling, deterministically keyed per round
        seed = int(jax.random.randint(key, (), 0, np.iinfo(np.int32).max))
        rng = np.random.default_rng(seed)
        phis, ys = [], []
        for i in range(n_agents):
            phi, y = task.sample_local(rng, i, b)
            phis.append(phi)
            ys.append(y)
        return {
            "phi": jnp.asarray(np.stack(phis), jnp.float32),
            "y": jnp.asarray(np.stack(ys), jnp.float32),
        }

    rng_test = np.random.default_rng(10_000)
    phi_t, y_t = task.sample_global(rng_test, 4000)
    return DataBundle(
        kind="linreg",
        n_agents=n_agents,
        sampler=sampler,
        dim=task.d,
        dataset=task,
        test_phi=phi_t,
        test_y=y_t,
    )


def _build_tokens(spec: DataSpec, n_agents: int) -> DataBundle:
    """Token text drawn on the device every round: [N, u, B, seq_len]
    tokens and next-token targets, iid Zipf ids per agent and step."""
    p = dict(spec.dataset_params)
    sampler = make_lm_batch_sampler(
        p["vocab_size"], spec.batch_size, p["seq_len"], n_agents=n_agents,
        local_updates=spec.local_updates, exponent=p.get("exponent", 1.2),
    )
    return DataBundle(kind="tokens", n_agents=n_agents, sampler=sampler,
                      dim=p["seq_len"], n_classes=p["vocab_size"])
