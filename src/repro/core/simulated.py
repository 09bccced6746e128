"""Simulated multi-agent runtime: the whole network lives on one host and
agents are a leading pytree axis stepped under ``vmap``.  This is the exact
execution model for the paper's CPU-scale experiments (4-agent linear
regression, 9-agent star/grid Bayesian NNs, 26/101-agent time-varying
networks) and the reference semantics against which the production
collective runtime (launch/) is tested.

One communication round at every agent i (Sec 2.1):
  1. draw a local batch (the data pipeline pre-slices u minibatches),
  2+3. u local Bayes-by-Backprop steps against the prior q_i^{(n-1)}
       (Remark 1 merges the Bayesian update and the projection),
  4+5. consensus: precision-weighted averaging with row W_i (eq. 6).
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp

from repro.core.flat import (
    FlatLayout,
    FlatPosterior,
    flat_posterior_from_pytree,
    make_flat_nll,
)
from repro.core.posterior import (
    GaussianPosterior,
    consensus_all_agents,
    consensus_mean_only,
)
from repro.optim import Optimizer
from repro.optim.schedules import Schedule
from repro.vi.bayes_by_backprop import NllFn, local_vi_steps

PyTree = Any


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class NetworkState:
    """State of the whole N-agent network (leading axis N on every leaf)."""

    posterior: GaussianPosterior  # stacked over agents
    opt_state: Any
    step: jax.Array  # per-agent local step counter [N]
    round: jax.Array  # scalar communication-round counter


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class SharedBatches:
    """A round's batches plus a pytree that every agent's nll reads, such as
    a frozen trunk under per-agent adapters.  ``per_agent`` leaves carry the
    leading [N, u] axes; ``shared`` is unbatched and reaches the model as
    the nll's third argument, ``nll(theta, batch, shared)``: one copy in the
    round program's arguments, never one per agent, never a constant of
    the program.  ``per_agent`` is the first field, so the first leaf of a
    ``SharedBatches`` is a per-agent batch leaf."""

    per_agent: Any
    shared: Any


def with_shared(batches, shared):
    """The round's batches, with a model's frozen part (if it has one)
    riding along as one unbatched argument of the round program."""
    return batches if shared is None else SharedBatches(batches, shared)


def init_network(
    key: jax.Array,
    n_agents: int,
    init_params_fn: Callable[[jax.Array], PyTree],
    opt: Optimizer,
    init_sigma: float = 0.05,
    shared_init: bool = True,
    flat: bool = True,
) -> NetworkState:
    """Paper Remark 7: agents use a SHARED initialization the first time the
    local models are trained (but never re-synchronize afterwards).  Set
    ``shared_init=False`` to study the divergent-initialization failure mode.

    The posterior is stored as a ``core.flat.FlatPosterior`` (contiguous
    [N, P] buffers) — the canonical runtime format: consensus runs as ONE
    fused network-wide pass and the optimizer state collapses to flat
    buffers too.  ``make_round_fn`` picks the layout up from the state
    automatically, so ``nll_fn`` keeps its pytree signature either way.

    ``flat=False`` keeps the legacy pytree ``GaussianPosterior`` network
    state (deprecated; the leaf-loop consensus reference stays reachable
    through ``consensus_all_agents`` on pytree posteriors).
    """
    from repro.core.posterior import init_posterior

    if not flat:
        warnings.warn(
            "init_network(flat=False) builds the deprecated pytree network "
            "state; the flat [N, P] posterior is the canonical runtime "
            "format since PR 1 (pytrees remain the model-apply boundary).",
            DeprecationWarning,
            stacklevel=2,
        )

    if shared_init:
        params = init_params_fn(key)
        stack = jax.tree.map(
            lambda p: jnp.broadcast_to(p, (n_agents,) + p.shape), params
        )
    else:
        keys = jax.random.split(key, n_agents)
        stack = jax.vmap(init_params_fn)(keys)
    post = init_posterior(stack, init_sigma=init_sigma)
    if flat:
        post = flat_posterior_from_pytree(post, leading_axes=1)
    opt_state = opt.init(post)
    return NetworkState(
        posterior=post,
        opt_state=opt_state,
        step=jnp.zeros((n_agents,), jnp.int32),
        round=jnp.asarray(0, jnp.int32),
    )


def network_local_steps(
    posterior,
    prior,
    opt: Optimizer,
    opt_state,
    nll,
    batches,
    key: jax.Array,
    lr,
    step: jax.Array,
    n_samples: int = 1,
    kl_scale: float = 1.0,
):
    """The network-wide local phase: per-agent key split + ``local_vi_steps``
    under ``vmap`` — SHARED by the synchronous round (``make_round_fn``) and
    the gossip event window (``repro.gossip.engine``).  The two runtimes'
    bit-identity in the all-edges-active case hangs on sharing this exact
    key/step derivation, so extend it here rather than copying it.

    ``batches`` may be a ``SharedBatches``: its shared part is closed over
    by every agent's nll, unbatched.  Returns (posterior', opt_state',
    per-agent mean losses [N], the nll's aux summed over the steps, leaves
    [N, ...]).  Its device work carries the ``local_phase`` named scope
    (the profiler's per-layer reading).
    """
    if isinstance(batches, SharedBatches):
        shared, batches, agent_nll = batches.shared, batches.per_agent, nll
        nll = lambda theta, batch: agent_nll(theta, batch, shared)

    def local(post_i, prior_i, opt_i, batches_i, key_i, step_i):
        return local_vi_steps(
            post_i,
            prior_i,
            opt,
            opt_i,
            nll,
            batches_i,
            key_i,
            lr,
            step_i,
            n_samples=n_samples,
            kl_scale=kl_scale,
        )

    with jax.named_scope("local_phase"):
        keys = jax.random.split(key, step.shape[0])
        return jax.vmap(local)(posterior, prior, opt_state, batches, keys,
                               step)


def make_round_fn(
    nll_fn: NllFn,
    opt: Optimizer,
    lr_schedule: Schedule,
    n_mc_samples: int = 1,
    kl_scale: float = 1.0,
    consensus: str = "gaussian",
    param_layout: FlatLayout | None = None,
    wire_dtype=None,
):
    """Build the jittable per-round transition.

    round_fn(state, batches, W, key) -> (state', mean_loss_per_agent, aux)
      batches: pytree, leaves [N, u, ...] — u local minibatches per agent
      W: [N, N] row-stochastic (may differ per round: time-varying networks)

    ``nll_fn`` keeps its pytree signature; when the network state holds a
    ``FlatPosterior`` the layout is read off the state and the nll is wrapped
    so the flat theta sample crosses to a pytree only at the model-apply
    boundary.  ``param_layout`` pre-binds that layout at build time (skips
    the per-trace wrap; required only when the state type is not known yet).
    ``wire_dtype`` compresses the gaussian consensus exchange
    (``consensus_all_agents``); f32/None is bitwise uncompressed.  ``aux``
    is the nll's aux per agent, summed over the local steps (``()`` for an
    nll that returns its value alone; ``network_local_steps``).
    """
    if consensus not in ("gaussian", "mean_only", "none"):
        raise ValueError(f"unknown consensus mode {consensus!r}")
    if param_layout is not None:
        nll_fn = make_flat_nll(nll_fn, param_layout)

    def round_fn(state: NetworkState, batches: Any, W: jax.Array, key: jax.Array):
        nll = nll_fn
        if param_layout is None and isinstance(state.posterior, FlatPosterior):
            nll = make_flat_nll(nll_fn, state.posterior.layout)
        lr = lr_schedule(state.round)
        prior = state.posterior  # q_i^{(n-1)}: consensus result of last round
        post, opt_state, losses, aux = network_local_steps(
            state.posterior, prior, opt, state.opt_state, nll, batches, key,
            lr, state.step, n_samples=n_mc_samples, kl_scale=kl_scale,
        )
        u = jax.tree.leaves(batches)[0].shape[1]
        with jax.named_scope("consensus"):
            if consensus == "gaussian":
                post = consensus_all_agents(post, W, wire_dtype=wire_dtype)
            elif consensus == "mean_only":
                # dataclasses.replace keeps the posterior's own type (and,
                # for a FlatPosterior, its static layout)
                post = dataclasses.replace(
                    post,
                    mean=consensus_mean_only(post.mean, W),
                    rho=consensus_mean_only(post.rho, W),
                )
        # consensus == "none": isolated learning (paper Fig 1b baseline)
        new_state = NetworkState(
            posterior=post,
            opt_state=opt_state,
            step=state.step + u,
            round=state.round + 1,
        )
        return new_state, losses, aux

    return round_fn


def as_w_schedule(
    w_schedule: Sequence[jax.Array] | jax.Array | Callable[[int], jax.Array],
) -> Callable[[int], jax.Array]:
    """Normalize the three accepted topology-schedule forms — a static W, a
    list cycled over rounds, or a round-indexed callable — to one
    ``Callable[[int], W]``.  Shared by ``run_rounds`` and ``api.Session``."""
    if callable(w_schedule):
        return w_schedule
    if isinstance(w_schedule, (list, tuple)):
        ws = list(w_schedule)
        if not ws:
            raise ValueError("empty W schedule")
        return lambda r: ws[r % len(ws)]
    return lambda r: w_schedule


def run_rounds(
    round_fn,
    state: NetworkState,
    batch_sampler: Callable[[jax.Array, int], Any],
    w_schedule: Sequence[jax.Array] | jax.Array | Callable[[int], jax.Array],
    n_rounds: int,
    key: jax.Array,
    eval_fn: Callable[[NetworkState], dict] | None = None,
    eval_every: int = 0,
    jit: bool = True,
) -> tuple[NetworkState, list[dict]]:
    """Python-level driver (rounds may have data-dependent W / eval hooks).

    batch_sampler(key, round_idx) -> batches pytree [N, u, ...]
    w_schedule: a single W, a list cycled over rounds, or a round-indexed
    ``Callable[[int], W]`` (first-class time-varying topologies).
    """
    fn = jax.jit(round_fn) if jit else round_fn
    history: list[dict] = []
    w_for_round = as_w_schedule(w_schedule)
    for r in range(n_rounds):
        key, k_batch, k_round = jax.random.split(key, 3)
        batches = batch_sampler(k_batch, r)
        state, losses, _ = fn(state, batches, jnp.asarray(w_for_round(r)),
                              k_round)
        if eval_every and ((r + 1) % eval_every == 0 or r == n_rounds - 1):
            rec = {"round": r + 1, "loss": float(jnp.mean(losses))}
            if eval_fn is not None:
                rec.update(eval_fn(state))
            history.append(rec)
    return state, history
