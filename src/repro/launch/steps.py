"""Production step functions: decentralized-Bayesian train round, prefill,
and decode, all vmapped over the agent (pod) axis.

train_round_step — ONE communication round of the paper's rule fused into a
single jitted step (the dry-run target):
  1. consensus (eq. 6) over the agent axis  ->  prior q_i^{(n-1)}
  2. one Bayes-by-Backprop step from that prior (eq. 5): reparameterized
     sample, NLL + KL(q || prior), Adam update on (mu, rho)
The production driver (train.py) runs u local steps per consensus by calling
``local_step`` u-1 additional times against the stored prior — identical
semantics to the paper's u local epochs (supplementary Tables 1-3).

Serving uses the posterior MEAN as the weights (the L=1 fast path of the
paper's MC-predictive serving; --mc-samples exposes L>1).

Posterior format: since PR 2 the launch hot loop runs on the FLAT posterior
(``core.flat.FlatPosterior``, contiguous [A, P] fp32 buffers) end-to-end —
consensus dispatches to the single fused network-wide pass and the model
pytree appears only at the apply boundary (``layout.unflatten`` around
``nll_loss``/``forward``).  Every step function still accepts the legacy
pytree ``GaussianPosterior`` state (``init_train_state(flat=False)``).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.core.flat import FlatPosterior, flat_posterior_from_pytree
from repro.core.posterior import (
    GaussianPosterior,
    consensus_all_agents,
    init_posterior,
    kl_gaussian,
)
from repro.models import forward, init_cache, init_params, nll_loss
from repro.optim import Optimizer, adam, apply_updates
from repro.optim.schedules import Schedule, exponential_decay

PyTree = Any


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class BayesTrainState:
    posterior: GaussianPosterior  # FlatPosterior [A, P] (default) or pytree
    opt_state: Any
    step: jax.Array  # scalar int32


def _unflattener(posterior) -> Callable[[jax.Array], PyTree]:
    """Model-apply-boundary conversion: flat theta [*, P] -> parameter pytree
    (identity for pytree posteriors, whose samples already ARE pytrees)."""
    if isinstance(posterior, FlatPosterior):
        return posterior.layout.unflatten
    return lambda theta: theta


def _n_agents(posterior) -> int:
    return jax.tree.leaves(posterior.mean)[0].shape[0]


def init_train_state(
    key: jax.Array,
    cfg,
    n_agents: int,
    opt: Optimizer,
    init_sigma: float = 0.02,
    flat: bool = True,
) -> BayesTrainState:
    params = init_params(cfg, key)
    stacked = jax.tree.map(
        lambda p: jnp.broadcast_to(p, (n_agents,) + p.shape), params
    )
    post = init_posterior(stacked, init_sigma=init_sigma)
    if flat:
        post = flat_posterior_from_pytree(post, leading_axes=1)
    return BayesTrainState(
        posterior=post,
        opt_state=opt.init(post),
        step=jnp.asarray(0, jnp.int32),
    )


def make_train_round_step(
    cfg,
    W: jax.Array,  # [A, A] row-stochastic agent interaction matrix
    opt: Optimizer | None = None,
    lr_schedule: Schedule | None = None,
    kl_scale: float = 1e-4,
    remat: bool = True,
    bayesian: bool = True,
    consensus_impl: str = "einsum",  # einsum | ppermute | none (§Perf A/B)
    consensus_wire_dtype=None,  # e.g. jnp.bfloat16: §Perf wire compression
    mesh=None,  # required for consensus_impl="ppermute"
    posterior_shardings=None,  # required for consensus_impl="ppermute"
) -> Callable:
    """Build the fused per-round train step (see module docstring).

    ``bayesian=False`` degrades to the deterministic baseline: plain NLL on
    the posterior mean + W-weighted parameter averaging (decentralized
    FedAvg) — the non-Bayesian comparison point.
    """
    opt = opt or adam()
    lr_schedule = lr_schedule or exponential_decay(1e-3, 0.9999)

    def step_fn(state: BayesTrainState, batch: PyTree, key: jax.Array):
        a = W.shape[0]
        lr = lr_schedule(state.step)
        unflatten = _unflattener(state.posterior)
        is_flat = isinstance(state.posterior, FlatPosterior)
        # ---- consensus (eq. 6): the paper's model-aggregation operator ----
        if consensus_impl == "none":
            prior = state.posterior  # pure local step (u>1 rounds / A-B test)
        elif consensus_impl == "ppermute":
            if is_flat:
                # flat posterior: ONE shard_map over the two [A, P] buffers
                # (ROADMAP item closed by ISSUE 3) instead of the leaf-wise
                # pod ppermute; the shard's W row supplies the ring weights
                from repro.launch.consensus_opt import consensus_ppermute_ring_flat

                mean_sh = getattr(posterior_shardings, "mean", None)
                spec0 = getattr(mean_sh, "spec", None)
                axis = (spec0[0] if spec0 and spec0[0] is not None else "pod")
                prior = consensus_ppermute_ring_flat(
                    state.posterior, mesh, axis,
                    wire_dtype=consensus_wire_dtype or jnp.bfloat16,
                    W=W,
                )
            else:
                from repro.launch.consensus_opt import consensus_ppermute_pod

                prior = consensus_ppermute_pod(
                    state.posterior, W, mesh, posterior_shardings,
                    wire_dtype=consensus_wire_dtype or jnp.bfloat16,
                )
        elif consensus_wire_dtype is not None:
            from repro.launch.consensus_opt import (
                consensus_einsum,
                consensus_einsum_flat,
            )

            prior = (
                consensus_einsum_flat(
                    state.posterior, W, wire_dtype=consensus_wire_dtype
                )
                if is_flat
                else consensus_einsum(
                    state.posterior, W, wire_dtype=consensus_wire_dtype
                )
            )
        else:
            prior = consensus_all_agents(state.posterior, W)
        keys = jax.random.split(key, a)

        def loss_fn(post: GaussianPosterior):
            def per_agent(post_a, prior_a, batch_a, key_a):
                if bayesian:
                    theta = post_a.sample(key_a)
                    kl = kl_gaussian(post_a, prior_a)
                else:
                    theta, kl = post_a.mean, jnp.asarray(0.0)
                nll, aux = nll_loss(unflatten(theta), cfg, batch_a, remat=remat)
                ntok = jnp.asarray(batch_a["targets"].size, jnp.float32)
                loss = (nll + cfg.router_aux_weight * aux * ntok) / ntok
                return loss + kl_scale * kl / ntok, (nll / ntok, kl)

            prior_b = jax.lax.stop_gradient(prior)
            losses, metrics = jax.vmap(per_agent)(post, prior_b, batch, keys)
            return jnp.mean(losses), metrics

        (loss, (nll, kl)), grads = jax.value_and_grad(loss_fn, has_aux=True)(prior)
        updates, opt_state = opt.update(grads, state.opt_state, state.step, lr)
        new_post = apply_updates(prior, updates)
        new_state = BayesTrainState(
            posterior=new_post, opt_state=opt_state, step=state.step + 1
        )
        return new_state, {"loss": loss, "nll": nll, "kl": kl}

    return step_fn


def make_local_step(
    cfg,
    opt,
    lr_schedule,
    kl_scale: float = 1e-4,
    remat: bool = True,
    *,
    nll_fn: Callable[[PyTree, Any], jax.Array] | None = None,
    n_mc_samples: int = 1,
):
    """One local VI step against an explicit prior (u>1 rounds in train.py).

    Default (``nll_fn=None``): the LM objective — ``models.nll_loss`` on
    ``cfg``, per-token normalized, averaged over agents.

    ``nll_fn`` (the ``repro.api`` / ``LaunchEngine`` path): an arbitrary
    per-agent pytree NLL.  The loss becomes the paper's un-normalized free
    energy ``kl_scale * KL(q||prior) + E_q[nll]`` (eq. 5, estimated with
    ``n_mc_samples`` MC samples exactly like ``vi.free_energy``), summed over
    agents so each agent's gradient equals its OWN free-energy gradient; the
    returned loss is the per-agent [A] vector.  ``key`` may then be a
    pre-split [A] key array, giving bit-identical RNG to the simulated
    runtime's per-agent key derivation.

    Either way a ``FlatPosterior`` state runs flat end-to-end: sampling, KL,
    the optimizer, and consensus all stay on the [A, P] buffers; the pytree
    appears only inside the model apply (``layout.unflatten``).
    """

    @jax.named_scope("local_phase")
    def step_fn(state: BayesTrainState, prior: GaussianPosterior, batch, key):
        a = _n_agents(state.posterior)
        lr = lr_schedule(state.step)
        unflatten = _unflattener(state.posterior)
        # a 1-D array of TYPED keys is a pre-split per-agent batch; anything
        # else (typed scalar, legacy uint32 [2] key) is one key to split
        is_key_batch = (
            jnp.ndim(key) == 1
            and jax.dtypes.issubdtype(key.dtype, jax.dtypes.prng_key)
        )
        keys = key if is_key_batch else jax.random.split(key, a)

        def loss_fn(post):
            def per_agent(post_a, prior_a, batch_a, key_a):
                if nll_fn is not None:
                    from repro.vi.bayes_by_backprop import free_energy

                    value, _ = free_energy(
                        post_a,
                        prior_a,
                        lambda theta, b: nll_fn(unflatten(theta), b),
                        batch_a,
                        key_a,
                        n_samples=n_mc_samples,
                        kl_scale=kl_scale,
                    )
                    return value
                theta = post_a.sample(key_a)
                kl = kl_gaussian(post_a, prior_a)
                nll, aux = nll_loss(unflatten(theta), cfg, batch_a, remat=remat)
                ntok = jnp.asarray(batch_a["targets"].size, jnp.float32)
                return (nll + cfg.router_aux_weight * aux * ntok) / ntok + kl_scale * kl / ntok

            losses = jax.vmap(per_agent)(
                post, jax.lax.stop_gradient(prior), batch, keys
            )
            # sum: d(sum)/d(post_a) = each agent's own gradient (the agents
            # are independent); mean would scale every lr by 1/A
            agg = jnp.sum(losses) if nll_fn is not None else jnp.mean(losses)
            return agg, losses

        (_, losses), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            state.posterior
        )
        with jax.named_scope("optimizer"):
            updates, opt_state = opt.update(grads, state.opt_state,
                                            state.step, lr)
            new_post = apply_updates(state.posterior, updates)
        loss = losses if nll_fn is not None else jnp.mean(losses)
        return (
            BayesTrainState(posterior=new_post, opt_state=opt_state, step=state.step + 1),
            loss,
        )

    return step_fn


def make_consensus_step(cfg, W: jax.Array, wire_dtype=None):
    """Standalone consensus (eq. 6) over the agent axis — the communication
    phase of a round, applied every u local steps by train.py.  Dispatches on
    the posterior type: a ``FlatPosterior`` runs the single fused
    network-wide pass (Pallas kernel on TPU).  ``wire_dtype`` compresses
    the exchanged (prec, prec*mu) — f32/None is bitwise uncompressed."""
    del cfg  # consensus is model-independent

    @jax.named_scope("consensus")
    def step_fn(posterior: GaussianPosterior) -> GaussianPosterior:
        return consensus_all_agents(posterior, W, wire_dtype=wire_dtype)

    return step_fn


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------


def serve_params(posterior: GaussianPosterior, dtype=jnp.bfloat16) -> PyTree:
    """Posterior-mean weights cast for serving (paper's L=1 predictive path).
    A flat posterior is unflattened here — serving consumes the model pytree."""
    mean = posterior.mean
    if isinstance(posterior, FlatPosterior):
        mean = posterior.layout.unflatten(mean)
    return jax.tree.map(lambda m: m.astype(dtype), mean)


def make_prefill_step(cfg, window_override: int | None = None):
    """(params [A,...], batch) -> (next-token logits [A,B,1,V], cache)."""

    def step_fn(params: PyTree, batch: PyTree, cache: PyTree):
        def per_agent(p, tokens, frames, patches, cache_a):
            logits, new_cache, _ = forward(
                p,
                cfg,
                tokens,
                cache=cache_a,
                frames=frames,
                patches=patches,
                logits_tail=1,
                window_override=window_override,
            )
            return logits, new_cache

        return jax.vmap(per_agent)(
            params,
            batch["tokens"],
            batch.get("frames"),
            batch.get("patches"),
            cache,
        )

    return step_fn


def make_decode_step(cfg, window_override: int | None = None):
    """(params [A,...], token [A,B,1], position, cache) -> (logits, cache)."""

    def step_fn(params: PyTree, token: jax.Array, position: jax.Array, cache: PyTree,
                frames: jax.Array | None = None):
        def per_agent(p, tok_a, cache_a, frames_a):
            positions = position[None]
            logits, new_cache, _ = forward(
                p,
                cfg,
                tok_a,
                positions=positions,
                cache=cache_a,
                frames=frames_a,
                window_override=window_override,
            )
            return logits, new_cache

        return jax.vmap(per_agent, in_axes=(0, 0, 0, 0 if frames is not None else None))(
            params, token, cache, frames
        )

    return step_fn


def make_agent_cache(cfg, n_agents: int, batch_per_agent: int, capacity: int,
                     dtype=jnp.bfloat16):
    """Agent-stacked decode cache [A, ...]."""
    one = init_cache(cfg, batch_per_agent, capacity, dtype)
    return jax.tree.map(lambda x: jnp.broadcast_to(x, (n_agents,) + x.shape).copy(), one)
