"""Bayes-by-Backprop variational inference (Blundell et al. [10]), the
computational realization of the paper's steps 2+3 (Remark 1, eq. 5):

    b_i^{(n)} = argmin_{pi in Q}  KL(pi || q_i^{(n-1)})
                                  + E_pi[ -log l_i(Y | . , X) ]

The KL term is closed-form between mean-field Gaussians; the expected
negative log-likelihood is estimated with simple Monte Carlo through the
reparameterization trick.  The *prior* of round n is the consensus posterior
q_i^{(n-1)} — this is exactly how the paper injects the network's global
information into local training (Remark 7).

Posterior-representation contract: everything here is polymorphic over the
posterior type.  ``post``/``prior`` may be a ``GaussianPosterior`` (pytree
mean/rho; ``post.sample`` returns a parameter pytree) or a
``core.flat.FlatPosterior`` (contiguous [P] fp32 buffers; ``post.sample``
returns a FLAT theta vector).  In the flat case ``nll_fn``/``logits_fn``
must accept the flat theta — wrap a pytree model once with
``core.flat.make_flat_nll`` (or apply ``layout.unflatten`` yourself) so the
flat->pytree conversion happens only at the model-apply boundary.  KL,
gradients, and the optimizer all run directly on the flat buffers.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.core.posterior import GaussianPosterior, kl_gaussian
from repro.optim import Optimizer, apply_updates

PyTree = Any
# nll_fn(params, batch) -> scalar total negative log-likelihood over the
# batch, or (that scalar, aux): aux is a pytree of the model's counters
# (the LM's tokens per expert), reported and never differentiated
NllFn = Callable[[PyTree, Any], Any]


def free_energy(
    post: GaussianPosterior,
    prior: GaussianPosterior,
    nll_fn: NllFn,
    batch: Any,
    key: jax.Array,
    n_samples: int = 1,
    kl_scale: float = 1.0,
) -> tuple[jax.Array, PyTree]:
    """Variational free energy (eq. 5): KL(q||prior) + E_q[-log lik], and
    the nll's aux summed over the MC samples (``()`` for an nll that
    returns its value alone).

    ``kl_scale`` implements minibatch KL reweighting (1/num_batches in [10])
    so that one epoch of minibatch steps applies the KL once in expectation.
    """
    with jax.named_scope("kl"):
        kl = kl_gaussian(post, prior)

    def one(k):
        with jax.named_scope("sample"):
            theta = post.sample(k)
        with jax.named_scope("nll"):
            out = nll_fn(theta, batch)
        return out if isinstance(out, tuple) else (out, ())

    keys = jax.random.split(key, n_samples)
    values, aux = jax.vmap(one)(keys)
    enll = jnp.mean(values)
    return kl_scale * kl + enll, jax.tree.map(lambda a: jnp.sum(a, axis=0),
                                              aux)


def free_energy_and_grad(
    post: GaussianPosterior,
    prior: GaussianPosterior,
    nll_fn: NllFn,
    batch: Any,
    key: jax.Array,
    n_samples: int = 1,
    kl_scale: float = 1.0,
) -> tuple[tuple[jax.Array, PyTree], GaussianPosterior]:
    """((free energy, aux), the free energy's gradient in ``post``)."""
    return jax.value_and_grad(free_energy, has_aux=True)(
        post, prior, nll_fn, batch, key, n_samples, kl_scale
    )


def local_vi_steps(
    post: GaussianPosterior,
    prior: GaussianPosterior,
    opt: Optimizer,
    opt_state: Any,
    nll_fn: NllFn,
    batches: Any,
    key: jax.Array,
    lr: jax.Array,
    step0: jax.Array,
    n_samples: int = 1,
    kl_scale: float = 1.0,
) -> tuple[GaussianPosterior, Any, jax.Array, PyTree]:
    """Run u local VI (Bayes-by-Backprop) steps — the paper's ``u`` local
    updates per communication round (supplementary Tables 1-3).

    ``batches``: pytree whose leaves carry a leading axis of length u (one
    slice per local step).  Returns (new_post, new_opt_state, mean_loss,
    the nll's aux summed over the steps).
    """
    u = jax.tree.leaves(batches)[0].shape[0]
    keys = jax.random.split(key, u)

    def body(carry, xs):
        post, opt_state, step = carry
        batch, k = xs
        loss_aux, grads = free_energy_and_grad(
            post, prior, nll_fn, batch, k, n_samples, kl_scale
        )
        with jax.named_scope("optimizer"):
            updates, opt_state = opt.update(grads, opt_state, step, lr)
            post = apply_updates(post, updates)
        return (post, opt_state, step + 1), loss_aux

    (post, opt_state, _), (losses, aux) = jax.lax.scan(
        body, (post, opt_state, step0), (batches, keys)
    )
    return (post, opt_state, jnp.mean(losses),
            jax.tree.map(lambda a: jnp.sum(a, axis=0), aux))


def mc_predict(
    post: GaussianPosterior,
    logits_fn: Callable[[PyTree, jax.Array], jax.Array],
    x: jax.Array,
    key: jax.Array,
    n_mc: int = 8,
) -> jax.Array:
    """Paper Sec 4.2: Monte-Carlo predictive distribution
    P(y) = (1/L) sum_k Softmax(y, f_{theta_k}(x)), theta_k ~ b_i^{(n)}.

    Returns the averaged class-probability array [..., n_classes].
    ``logits_fn`` takes a parameter PYTREE; a ``FlatPosterior`` is sampled
    through its layout (``sample_pytree``) so callers never see flat theta.
    """
    keys = jax.random.split(key, n_mc)
    sample = getattr(post, "sample_pytree", post.sample)

    def one(k):
        theta = sample(k)
        return jax.nn.softmax(logits_fn(theta, x), axis=-1)

    return jnp.mean(jax.vmap(one)(keys), axis=0)


def predictive_confidence(probs: jax.Array) -> tuple[jax.Array, jax.Array]:
    """(argmax prediction, confidence = posterior predictive probability)."""
    pred = jnp.argmax(probs, axis=-1)
    conf = jnp.max(probs, axis=-1)
    return pred, conf
