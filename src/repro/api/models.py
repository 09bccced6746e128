"""Model registry for the declarative API.

The paper's NN experiments all use a small ReLU MLP trained with
Bayes-by-Backprop (Sec 4.2: 2 hidden layers, 200 units on MNIST).  The
registry maps ``InferenceSpec.model`` names to a ``ModelFns`` triple; the
input/output dimensions always come from the ``DataSpec`` at
``build_session`` time, so spec and dataset cannot disagree on shapes.

Everything here keeps the PYTREE parameter signature — the flat runtime
wraps ``nll_fn`` through ``FlatLayout.unflatten`` at the model-apply
boundary (``core.flat.make_flat_nll``), never the other way around.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

PyTree = Any


@dataclasses.dataclass(frozen=True)
class ModelFns:
    """(init, logits, nll) for one model family at fixed dimensions.

    A family whose posterior covers part of the model (``shared`` is not
    None: the LM family's frozen trunk) takes the rest as the nll's third
    argument, ``nll_fn(theta, batch, shared)``; the engines pass it into
    the round program as one unbatched argument
    (``core.simulated.SharedBatches``).  An nll may return (nll, aux),
    aux a dict of the model's counters (the LM's ``expert_tokens``).  A
    family without a ``logits_fn`` (the LM) has no class predictive."""

    init_fn: Callable[[jax.Array], PyTree]
    logits_fn: Callable[[PyTree, jax.Array], jax.Array] | None
    nll_fn: Callable[..., Any]
    shared: PyTree | None = None


def mlp_init(dim: int, hidden: int, n_classes: int, depth: int = 2):
    """``depth``-hidden-layer ReLU MLP, 1/sqrt(fan_in) init (the paper's
    architecture; ``depth=2`` matches Sec 4.2 / the benchmark drivers)."""

    sizes = [dim] + [hidden] * depth + [n_classes]

    def init(key):
        ks = jax.random.split(key, len(sizes) - 1)
        params = {}
        for i, (k, fan_in, fan_out) in enumerate(zip(ks, sizes[:-1], sizes[1:]), 1):
            params[f"w{i}"] = jax.random.normal(k, (fan_in, fan_out)) / np.sqrt(fan_in)
            params[f"b{i}"] = jnp.zeros((fan_out,))
        return params

    return init


def mlp_logits(theta: PyTree, x: jax.Array) -> jax.Array:
    n_layers = len(theta) // 2
    h = x
    for i in range(1, n_layers):
        h = jax.nn.relu(h @ theta[f"w{i}"] + theta[f"b{i}"])
    return h @ theta[f"w{n_layers}"] + theta[f"b{n_layers}"]


def mlp_nll(theta: PyTree, batch: dict) -> jax.Array:
    """Total (summed) softmax cross-entropy over the batch."""
    logits = mlp_logits(theta, batch["x"])
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, batch["y"][..., None], axis=-1)[..., 0]
    return jnp.sum(logz - gold)


def _build_mlp(dim: int, n_classes: int, hidden: int, depth: int) -> ModelFns:
    return ModelFns(
        init_fn=mlp_init(dim, hidden, n_classes, depth=depth),
        logits_fn=mlp_logits,
        nll_fn=mlp_nll,
    )


# ---------------------------------------------------------------------------
# LM family: a frozen seeded trunk, a mean-field posterior over LoRA adapters
# ---------------------------------------------------------------------------

LM_LOSS_CHUNK = 256  # tokens per block of the chunked next-token loss
TRUNK_DTYPE = jnp.bfloat16


def lm_config(arch: str, n_layers: int | None = None):
    """The registry architecture, cut to its first ``n_layers`` layers."""
    from repro.configs import get_config

    cfg = get_config(arch)
    if n_layers is not None:
        cfg = dataclasses.replace(cfg, n_layers=n_layers)
        cfg.validate()
    return cfg


def init_trunk(cfg, seed: int) -> PyTree:
    """``models.init_params`` from ``fold_in(key(seed), 1)`` (the session's
    own stream splits ``key(seed)``), stored bfloat16 (the published
    checkpoint's dtype): one jitted program, so the float32 draw of a leaf
    never outlives its cast."""
    from repro.models import init_params

    @jax.jit
    def init(key):
        return jax.tree.map(lambda a: a.astype(TRUNK_DTYPE),
                            init_params(cfg, key))

    return init(jax.random.fold_in(jax.random.key(seed), 1))


def lora_init(cfg, rank: int):
    """Adapter means: A ~ N(0, 1/d_in), B = 0 on each MLA projection of
    every layer, so the sampled model starts at the trunk.  Stacks ``lead``
    (the leading dense layers) and ``moe`` (the MoE layers), one key per
    (stack, projection) in the order stacks x ``MLA_PROJECTIONS``."""
    from repro.models.attention import MLA_PROJECTIONS, mla_dims

    dims = mla_dims(cfg)
    stacks = {k: n for k, n in (("lead", cfg.first_k_dense),
                                ("moe", cfg.n_periods)) if n}

    def init(key):
        keys = iter(jax.random.split(key, len(stacks) * len(MLA_PROJECTIONS)))
        out = {}
        for stack, n in stacks.items():
            out[stack] = {}
            for proj in MLA_PROJECTIONS:
                d_in, d_out = dims[proj]
                a = jax.random.normal(next(keys), (n, d_in, rank))
                out[stack][proj] = {"a": a / np.sqrt(d_in),
                                    "b": jnp.zeros((n, rank, d_out))}
        return out

    return init


def chunked_xent(h: jax.Array, targets: jax.Array, w: jax.Array,
                 vocab_size: int, chunk: int = LM_LOSS_CHUNK) -> jax.Array:
    """Summed next-token cross-entropy of hidden states h [T, D] against
    targets [T] through the head w [D, V'], ``chunk`` tokens at a time (the
    [chunk, V'] logits rematerialized in the backward pass); columns at
    and past ``vocab_size`` (padding) are masked out."""
    t, d = h.shape
    chunk = min(chunk, t)
    if t % chunk:
        raise ValueError(f"{t} tokens are not a multiple of the loss chunk "
                         f"{chunk}")

    @jax.checkpoint
    def block(args):
        h_c, y_c = args
        logits = jnp.matmul(h_c, w, preferred_element_type=jnp.float32)
        if w.shape[1] != vocab_size:
            logits = jnp.where(jnp.arange(w.shape[1]) < vocab_size, logits,
                               -jnp.inf)
        gold = jnp.take_along_axis(logits, y_c[:, None], axis=-1)[:, 0]
        return jnp.sum(jax.nn.logsumexp(logits, axis=-1) - gold)

    return jnp.sum(jax.lax.map(block, (h.reshape(-1, chunk, d),
                                       targets.reshape(-1, chunk))))


def lm_model_fns(cfg, inference) -> ModelFns:
    """The LM family at ``cfg`` without its trunk (``shared`` None): the
    posterior over LoRA adapters of the MLA projections; nll = summed
    next-token cross-entropy over the batch's tokens (chunked over the
    vocabulary head), aux ``expert_tokens``: tokens per routed expert of
    each MoE layer [n_moe, E]."""
    from repro.models.transformer import mla_hidden

    if not cfg.is_mla:
        raise ValueError(
            f"model='lm' puts its adapters on latent-attention "
            f"projections; arch {inference.arch!r} has none")
    scale = inference.lora_alpha / inference.lora_rank

    def nll(adapters, batch, trunk):
        tokens, targets = batch["tokens"], batch["targets"]
        h, counts = mla_hidden(trunk, cfg, tokens, adapters=adapters,
                               lora_scale=scale, remat=True)
        with jax.named_scope("lm_head"):
            total = chunked_xent(h.reshape(-1, cfg.d_model),
                                 targets.reshape(-1),
                                 trunk["lm_head"]["w"], cfg.vocab_size)
        return total, {"expert_tokens": counts}

    return ModelFns(init_fn=lora_init(cfg, inference.lora_rank),
                    logits_fn=None, nll_fn=nll)


def _build_lm(inference, seed: int) -> ModelFns:
    """The LM family: ``inference.arch`` (a latent-attention registry
    architecture, cut to ``inference.n_layers``) as a frozen trunk seeded
    from the run's seed, under the adapters of ``lm_model_fns``."""
    cfg = lm_config(inference.arch, inference.n_layers)
    return dataclasses.replace(lm_model_fns(cfg, inference),
                               shared=init_trunk(cfg, seed))


MODELS: dict[str, Callable[..., ModelFns]] = {
    # (inference spec, data bundle, run seed) -> ModelFns
    "mlp": lambda inf, data, seed: _build_mlp(
        data.dim, data.n_classes, hidden=inf.hidden, depth=inf.depth),
    "lm": lambda inf, data, seed: _build_lm(inf, seed),
}


def build_model(inference, data, seed: int) -> ModelFns:
    """The ``InferenceSpec``'s model family: the MLP takes its input and
    output sizes from the data; the LM everything from the spec, and its
    frozen trunk's stream from the run's ``seed``."""
    if inference.model not in MODELS:
        raise ValueError(f"unknown model {inference.model!r}; known: "
                         f"{sorted(MODELS)}")
    return MODELS[inference.model](inference, data, seed)
