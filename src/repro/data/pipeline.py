"""Batching pipeline.

The paper equalizes the number of local updates per communication round:
u = floor(n_edge / B) * E for every agent, so the (larger) central agent
trains each round on a RANDOM SUBSET of its local data (supplementary
1.4.1).  ``make_round_batches`` implements exactly that: every agent
contributes u minibatches of size B per round, stacked to [N, u, B, ...].

For the production LM runtime, ``make_lm_batch_sampler`` yields synthetic
token batches (the container is offline; real corpora plug in behind the
same interface).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass
class AgentDataset:
    """Per-agent local shards, padded to a common backing size for vmap."""

    x: jnp.ndarray  # [N, max_n, ...]
    y: jnp.ndarray  # [N, max_n]
    n: jnp.ndarray  # [N] true (unpadded) shard sizes

    @property
    def n_agents(self) -> int:
        return int(self.x.shape[0])

    @staticmethod
    def from_shards(shards: list[tuple[np.ndarray, np.ndarray]]) -> "AgentDataset":
        max_n = max(len(y) for _, y in shards)
        xs, ys, ns = [], [], []
        for x, y in shards:
            pad = max_n - len(y)
            # pad by repeating from the start (padded rows are never sampled:
            # sampling indices are taken modulo the true size n)
            reps = int(np.ceil(max_n / max(len(y), 1)))
            xs.append(np.concatenate([x] * reps)[:max_n])
            ys.append(np.concatenate([y] * reps)[:max_n])
            ns.append(len(y))
            del pad
        return AgentDataset(
            x=jnp.asarray(np.stack(xs)),
            y=jnp.asarray(np.stack(ys)),
            n=jnp.asarray(ns, jnp.int32),
        )


def make_round_batches(
    data: AgentDataset, batch_size: int, n_local_updates: int
):
    """Returns sampler(key, round) -> dict(x=[N,u,B,...], y=[N,u,B]).

    Each agent draws u*B sample indices uniformly from its true shard
    (with replacement across rounds, without within a round when possible) —
    the paper's random-subset-per-round behaviour for the big agent.
    """
    n_agents = data.n_agents
    u, b = n_local_updates, batch_size

    # the shards are ARGUMENTS, not closed-over constants: a closure embeds
    # the whole dataset in the executable (0.5 GB at 60,000 MNIST-sized
    # rows), which compiles slowly and is too large for the persistent cache
    @jax.jit
    def sampler_impl(key, x, y, n):
        keys = jax.random.split(key, n_agents)

        def per_agent(k, x_a, y_a, n_a):
            idx = jax.random.randint(k, (u * b,), 0, n_a)
            return x_a[idx].reshape((u, b) + x_a.shape[1:]), y_a[idx].reshape(u, b)

        xs, ys = jax.vmap(per_agent)(keys, x, y, n)
        return {"x": xs, "y": ys}

    def sampler(key, round_idx: int):
        del round_idx
        return sampler_impl(key, data.x, data.y, data.n)

    return sampler


def zipf_cdf(vocab_size: int, exponent: float = 1.2) -> np.ndarray:
    """Cumulative Zipf weights k^-exponent over ids 0..V-1 (id k has rank
    k + 1), normalized in float64 and stored float32 [V]."""
    w = 1.0 / (np.arange(1, vocab_size + 1, dtype=np.float64) ** exponent)
    return (np.cumsum(w) / w.sum()).astype(np.float32)


def zipf_ids(key, cdf: jax.Array, shape) -> jax.Array:
    """Zipf token ids by inverse CDF: one uniform per id and a
    ``searchsorted`` over the [V] cumulative weights, so nothing of size
    [..., V] is built per token."""
    u = jax.random.uniform(key, shape, jnp.float32)
    ids = jnp.searchsorted(cdf, u, side="right")
    return jnp.minimum(ids, cdf.shape[0] - 1).astype(jnp.int32)


def make_lm_batch_sampler(
    vocab_size: int, batch_size: int, seq_len: int, n_agents: int = 0,
    distribution: str = "zipf", local_updates: int = 0,
    exponent: float = 1.2,
):
    """Synthetic LM token pipeline: sampler(key, round) -> dict with
    ``tokens`` [(N,) (u,) B, S] and ``targets`` (next-token shift), the
    agent and local-step axes present when ``n_agents`` / ``local_updates``
    are given.  Used by the production train driver, the ~100M end-to-end
    example and the ``zipf_tokens`` dataset of ``repro.api``.

    ``distribution``: "zipf" (ids iid with P(k) proportional to
    (k + 1)^-exponent: learnable unigram structure, entropy below log V —
    training visibly reduces NLL) or "uniform"."""

    shape = tuple(d for d in (n_agents, local_updates) if d) + (
        batch_size, seq_len + 1)
    if distribution == "zipf":
        cdf = jnp.asarray(zipf_cdf(vocab_size, exponent))
        draw = lambda key: zipf_ids(key, cdf, shape)
    elif distribution == "uniform":
        draw = lambda key: jax.random.randint(key, shape, 0, vocab_size,
                                              jnp.int32)
    else:
        raise ValueError(distribution)

    @jax.jit
    def sampler_impl(key):
        toks = draw(key)
        return {"tokens": toks[..., :-1], "targets": toks[..., 1:]}

    def sampler(key, round_idx: int):
        del round_idx
        return sampler_impl(key)

    return sampler
