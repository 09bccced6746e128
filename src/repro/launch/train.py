"""Production training driver: decentralized Bayesian training on a
(pod, data, model) mesh, agents = pods.

Runs the paper's full round structure: u local Bayes-by-Backprop steps per
communication round against the round's consensus prior, then the eq.-(6)
consensus over the pod axis.  Supports the deterministic (non-Bayesian
decentralized-FedAvg) baseline via --no-bayesian.

On this CPU container use small archs / --steps; the same entry point is the
real-TPU launcher (device count and mesh come from the runtime).

Example:
  PYTHONPATH=src python -m repro.launch.train --arch repro-100m \
      --batch 8 --seq 256 --rounds 10 --local-steps 4 --agents 2
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.configs import get_config
from repro.core.graphs import complete_w
from repro.data.pipeline import make_lm_batch_sampler
from repro.launch.steps import (
    init_train_state,
    make_consensus_step,
    make_local_step,
    make_train_round_step,
)
from repro.optim import adam
from repro.optim.schedules import exponential_decay


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="repro-100m")
    ap.add_argument("--reduced", action="store_true", help="use the smoke config")
    ap.add_argument("--agents", type=int, default=2)
    ap.add_argument("--batch", type=int, default=8, help="per-agent batch")
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--rounds", type=int, default=10)
    ap.add_argument("--local-steps", type=int, default=4, help="u per round")
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--lr-decay", type=float, default=0.99, help="per round (paper)")
    ap.add_argument("--kl-scale", type=float, default=1e-4)
    ap.add_argument("--no-bayesian", action="store_true")
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    a = args.agents
    opt = adam()
    # paper: lr decays per communication round
    sched = exponential_decay(args.lr, args.lr_decay ** (1.0 / max(args.local_steps, 1)))
    W = jnp.asarray(complete_w(a))

    key = jax.random.key(args.seed)
    key, k_init = jax.random.split(key)
    state = init_train_state(k_init, cfg, a, opt)
    n_params = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(state.posterior.mean))
    print(f"arch={cfg.name} agents={a} posterior params={n_params:,}")

    sampler = make_lm_batch_sampler(cfg.vocab_size, args.batch, args.seq, n_agents=a)
    local_step = jax.jit(
        make_local_step(cfg, opt, sched, kl_scale=args.kl_scale, remat=False)
    )
    consensus = jax.jit(make_consensus_step(cfg, W))
    round_step = jax.jit(
        make_train_round_step(
            cfg, W, opt=opt, lr_schedule=sched, kl_scale=args.kl_scale,
            remat=False, bayesian=not args.no_bayesian,
        )
    )

    ckpt = CheckpointManager(args.ckpt_dir) if args.ckpt_dir else None
    t0 = time.time()
    for r in range(args.rounds):
        key, k_round = jax.random.split(key)
        if args.local_steps <= 1 or args.no_bayesian:
            batch = sampler(k_round, r)
            state, metrics = round_step(state, batch, k_round)
            loss = float(jnp.mean(metrics["loss"]))
        else:
            prior = consensus(state.posterior)
            state = jax.tree.map(lambda x: x, state)
            state.posterior = prior
            losses = []
            for u in range(args.local_steps):
                key, k_u = jax.random.split(key)
                batch = sampler(k_u, r * args.local_steps + u)
                state, loss_u = local_step(state, prior, batch, k_u)
                losses.append(float(loss_u))
            loss = float(np.mean(losses))
        dt = time.time() - t0
        print(f"round {r + 1:4d}/{args.rounds}  loss {loss:8.4f}  ({dt:6.1f}s)", flush=True)
        if ckpt and (r + 1) % 10 == 0:
            ckpt.save(r + 1, state)
    if ckpt:
        ckpt.save(args.rounds, state)
        print(f"checkpoint saved to {args.ckpt_dir}")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
