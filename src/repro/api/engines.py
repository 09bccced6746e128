"""Engine implementations behind ``api.Session``.

An Engine owns the state layout and the per-round transition; the Session
owns the loop, the data, and the RNG stream.  All engines run the SAME
paper round (u local updates against the round prior, then eq.-(6)
consensus) on the SAME key derivation, so their posteriors agree (the
synchronous engine and all-edges gossip bitwise, by test):

* ``SimulatedEngine`` — the ``core.simulated`` flat runtime: one jitted
  ``round_fn`` (vmap over agents, scan over local steps), consensus as the
  single fused network-wide pass.  The default.
* ``ConjugateLinregEngine`` — paper Example 1: exact conjugate
  full-covariance updates + eq.-(6) full-covariance consensus.
* ``repro.gossip.engine.GossipEngine`` — the event-driven asynchronous
  runtime (selected by ``TopologySpec(kind="gossip")``): one event window
  per round, masked active-edge consensus, staleness telemetry.  An engine
  may additionally expose ``telemetry(state) -> dict``; ``Session.evaluate``
  merges it into its result.
"""
from __future__ import annotations

from typing import Any, Protocol

import jax
import jax.numpy as jnp

from repro.api.data import DataBundle
from repro.api.models import ModelFns
from repro.api.spec import ExperimentSpec
from repro.core.flat import FlatPosterior
from repro.core.posterior import (
    FullCovGaussian,
    consensus_full_cov,
    linreg_bayes_update,
)
from repro.core.simulated import init_network, make_round_fn, with_shared
from repro.optim import Optimizer, adam, sgd
from repro.optim.schedules import Schedule, constant_schedule, exponential_decay

PyTree = Any


class Engine(Protocol):
    """Contract between ``Session`` and a runtime.

    ``init(key) -> state``; ``run_round(state, batches, W, key) ->
    (state, per_agent_losses)``; ``posterior(state)`` -> the network
    posterior (``FlatPosterior`` for the BbB engines).  State must be a
    pytree (it is checkpointed leaf-wise with the spec doc riding along).
    """

    name: str

    def init(self, key: jax.Array) -> Any: ...

    def run_round(
        self, state: Any, batches: Any, W: jax.Array, key: jax.Array
    ) -> tuple[Any, jax.Array]: ...

    def posterior(self, state: Any) -> Any: ...


def build_optimizer(name: str) -> Optimizer:
    return {"adam": adam, "sgd": sgd}[name]()


def build_schedule(lr: float, decay: float) -> Schedule:
    if decay == 1.0:
        return constant_schedule(lr)
    return exponential_decay(lr, decay)


class SimulatedEngine:
    """``core.simulated`` flat runtime behind the Engine protocol."""

    name = "simulated"

    def __init__(self, spec: ExperimentSpec, model: ModelFns, n_agents: int):
        inf = spec.inference
        self.n_agents = n_agents
        self.model = model
        self.opt = build_optimizer(inf.optimizer)
        self.init_sigma = inf.init_sigma
        self.shared_init = inf.shared_init
        round_fn = make_round_fn(
            model.nll_fn,
            self.opt,
            build_schedule(inf.lr, inf.lr_decay),
            n_mc_samples=inf.n_mc_samples,
            kl_scale=inf.kl_scale,
            consensus=inf.consensus,
            wire_dtype=inf.wire_dtype,
        )
        self._round = jax.jit(round_fn) if spec.run.jit else round_fn

    def init(self, key: jax.Array):
        return init_network(
            key,
            self.n_agents,
            self.model.init_fn,
            self.opt,
            init_sigma=self.init_sigma,
            shared_init=self.shared_init,
        )

    def run_round(self, state, batches, W, key):
        """One round; the nll's aux (the model's counters per agent) is
        kept as ``last_aux`` for ``Session.round``'s telemetry."""
        state, losses, self.last_aux = self._round(
            state, with_shared(batches, self.model.shared), jnp.asarray(W),
            key)
        return state, losses

    def posterior(self, state) -> FlatPosterior:
        return state.posterior


class ConjugateLinregEngine:
    """Paper Example 1: exact conjugate Bayesian linear regression (eq. 2)
    with full-covariance consensus (eq. 6)."""

    name = "conjugate_linreg"

    def __init__(self, spec: ExperimentSpec, data: DataBundle):
        self.n_agents = data.n_agents
        self.d = data.dim
        self.noise_var = float(data.dataset.noise_std) ** 2
        self.prior_var = spec.inference.prior_var
        self.consensus_mode = spec.inference.consensus

        def round_fn(posts: FullCovGaussian, batches, W):
            upd = jax.vmap(
                lambda m, p, phi, y: linreg_bayes_update(
                    FullCovGaussian(m, p), phi, y, self.noise_var
                )
            )(posts.mean, posts.prec, batches["phi"], batches["y"])
            if self.consensus_mode != "none":
                upd = consensus_full_cov(upd, W)
            err = jnp.einsum("nbd,nd->nb", batches["phi"], upd.mean) - batches["y"]
            return upd, jnp.mean(jnp.square(err), axis=-1)

        self._round = jax.jit(round_fn) if spec.run.jit else round_fn

    def init(self, key: jax.Array) -> FullCovGaussian:
        del key  # the conjugate prior is deterministic
        n, d = self.n_agents, self.d
        return FullCovGaussian(
            mean=jnp.zeros((n, d)),
            prec=jnp.broadcast_to(jnp.eye(d) / self.prior_var, (n, d, d)),
        )

    def run_round(self, state, batches, W, key):
        del key
        return self._round(state, batches, jnp.asarray(W))

    def posterior(self, state) -> FullCovGaussian:
        return state
