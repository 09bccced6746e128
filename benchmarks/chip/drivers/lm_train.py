"""Language-model training traffic: synchronous rounds back to back through
``Session.round()`` on the LM family (a frozen trunk under a mean-field
posterior over LoRA adapters).

A mix for this driver (``traffic/<mix>.json`` with ``"driver":
"lm_train"``) names no clock (synchronous rounds) and how many first rounds
the correctness check follows (``check_rounds``); ``about`` says what it is
for.  The configuration (``configs/<config>.json``) holds the published
architecture under its own keys, the depth run here (``n_layers``) and the
program's sections: ``model`` (``name`` "lm", the registry ``arch``, the
adapter count ``n_params``), ``data`` (``zipf_tokens``), ``inference``,
``topology`` (a bidirectional ring) and ``n_agents``.  The program's
registry architecture must agree with the published keys.

Set-up builds the session, drives its first ``check_rounds`` rounds
through ``Session.round()`` (the first compiles; their readings are kept
for the correctness check).  The window reports ``train_samples_per_s``: a
sample is one sequence (``n_trained`` x u x B per round).  A traced window
also reads, after the profiler stops, the device time under the model's
own scopes (``chipbench.lm_scopes``) and, per round, the registry gauge
``model.expert_load_max``: they reach the readers through
``layer_context``.
"""
from __future__ import annotations

import gc
import re
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import correct, lm_scopes, program
from chipbench.trace import WINDOW_ANNOTATION, find_xplane

_KEY = re.compile(r"\['(\w+)'\]")
TRAFFIC_KEYS = ("driver", "about", "check_rounds", "clock")
CONFIG_KEYS = ("model", "data", "inference", "topology", "n_agents",
               "n_layers")
MODEL_KEYS = ("name", "arch", "n_params")
DATA_KEYS = ("dataset", "dataset_params", "partition", "partition_params",
             "batch_size", "local_updates")
TOPOLOGY_KEYS = ("graph", "params")
# the faults the control run plants in the reference: two of the step, two
# of the architecture
FAULTS = ("half_batch", "no_exchange", "renorm", "no_mscale")
# the published keys the program's registry architecture must match
ARCH_KEYS = {"hidden_size": "d_model", "num_attention_heads": "n_heads",
             "intermediate_size": "d_ff", "vocab_size": "vocab_size",
             "kv_lora_rank": "kv_lora_rank",
             "qk_nope_head_dim": "qk_nope_head_dim",
             "qk_rope_head_dim": "qk_rope_head_dim",
             "v_head_dim": "v_head_dim", "n_routed_experts": "n_experts",
             "num_experts_per_tok": "top_k",
             "moe_intermediate_size": "moe_d_ff",
             "n_shared_experts": "n_shared_experts",
             "norm_topk_prob": "norm_topk_prob",
             "routed_scaling_factor": "routed_scaling_factor",
             "first_k_dense_replace": "first_k_dense",
             "rms_norm_eps": "norm_eps", "rope_theta": "rope_theta",
             "num_hidden_layers": None}


def build_spec(cfg: dict, traffic: dict, seed: int, *, obs: bool = False):
    from repro.api import (DataSpec, ExperimentSpec, InferenceSpec, ObsSpec,
                           RunSpec, TopologySpec)

    program.only_keys(traffic, TRAFFIC_KEYS, "traffic")
    if traffic.get("clock") is not None:
        raise ValueError("lm_train runs synchronous rounds (clock null)")
    unread = sorted(set(cfg) - set(program.DESCRIPTIVE) - set(CONFIG_KEYS)
                    - set(ARCH_KEYS) - set(PUBLISHED_ONLY))
    if unread:
        raise ValueError(f"configuration: no program path reads {unread}")
    model = program.only_keys(cfg["model"], MODEL_KEYS, "configuration.model")
    data = program.only_keys(cfg["data"], DATA_KEYS, "configuration.data")
    topo = program.only_keys(cfg["topology"], TOPOLOGY_KEYS,
                             "configuration.topology")
    return ExperimentSpec(
        topology=TopologySpec(kind=topo["graph"], params=dict(topo["params"])),
        data=DataSpec(dataset=data["dataset"],
                      dataset_params=dict(data["dataset_params"]),
                      partition=data["partition"],
                      partition_params=dict(data["partition_params"]),
                      batch_size=data["batch_size"],
                      local_updates=data["local_updates"]),
        inference=InferenceSpec(model=model["name"], arch=model["arch"],
                                n_layers=cfg["n_layers"], **cfg["inference"]),
        run=RunSpec(n_rounds=1, seed=seed),
        obs=ObsSpec(enabled=obs, trace=True, convergence=False),
    )


# published keys that state the architecture but that no program path
# reads: checked against the registry where they name a choice the program
# makes (``check_arch``), otherwise descriptive
PUBLISHED_ONLY = ("attention_bias", "hidden_act", "max_position_embeddings",
                  "model_type", "moe_layer_freq", "n_group",
                  "num_key_value_heads", "q_lora_rank", "rope_scaling",
                  "scoring_func", "seq_aux", "tie_word_embeddings",
                  "topk_group", "topk_method")


def check_arch(cfg: dict, arch) -> None:
    """The registry architecture the program runs states the published
    numbers of the configuration file (and the depth cut)."""
    for key, field in ARCH_KEYS.items():
        if field is not None and getattr(arch, field) != cfg[key]:
            raise ValueError(f"the program's {arch.name} has {field} = "
                             f"{getattr(arch, field)!r}; the configuration "
                             f"states {key} = {cfg[key]!r}")
    if arch.n_layers != cfg["n_layers"]:
        raise ValueError(f"the program runs {arch.n_layers} layers, the "
                         f"configuration {cfg['n_layers']}")
    rs, ys = cfg["rope_scaling"], arch.rope_scaling
    if ys is None or (rs["factor"], rs["original_max_position_embeddings"],
                      rs["beta_fast"], rs["beta_slow"], rs["mscale"],
                      rs["mscale_all_dim"]) != (
            ys.factor, ys.original_max_position_embeddings, ys.beta_fast,
            ys.beta_slow, ys.mscale, ys.mscale_all_dim):
        raise ValueError("the program's YaRN scaling differs from the "
                         "configuration's rope_scaling")
    if (cfg["q_lora_rank"], cfg["scoring_func"], cfg["topk_method"],
            cfg["tie_word_embeddings"], cfg["attention_bias"]) != (
            None, "softmax", "greedy", False, False):
        raise ValueError("the program implements no query compression, "
                         "softmax scoring, greedy top-k, untied embeddings "
                         "and no attention bias")


def leaf_norms(post, row0=None) -> dict:
    """Per-leaf norms over all agents of a FlatPosterior-shaped pair, each
    leaf named by its full path (``mean.moe.kv_a.a``), less ``row0`` (one
    agent's initial mean and rho) when given."""
    out = {}
    for kind in ("mean", "rho"):
        arr = getattr(post, kind)
        if row0 is not None:
            arr = arr - row0[kind][None, :]
        for spec in post.layout.specs:
            name = ".".join(_KEY.findall(spec.path))
            sl = arr[:, spec.offset:spec.offset + spec.size]
            out[f"{kind}.{name}"] = float(jnp.sqrt(jnp.sum(jnp.square(sl))))
    return out


class Driver:
    def __init__(self, cfg: dict, traffic: dict, seed: int, *,
                 obs: bool = False):
        from repro.api import build_session
        from repro.api.models import lm_config

        spec = build_spec(cfg, traffic, seed, obs=obs)
        check_arch(cfg, lm_config(spec.inference.arch, spec.inference.n_layers))
        self.cfg, self.traffic, self.seed = cfg, traffic, seed
        data = cfg["data"]
        self.samples_per_agent = data["local_updates"] * data["batch_size"]
        self.session = build_session(spec)
        p = int(self.session.posterior().mean.shape[1])
        if p != cfg["model"]["n_params"]:
            raise ValueError(f"the program's adapters have {p} parameters; "
                             f"the configuration states "
                             f"{cfg['model']['n_params']}")
        self._layers = {}

    def setup(self, seconds: float) -> None:
        """The first rounds through the window's own call; their readings."""
        s = self.session
        post0 = s.posterior()
        row0 = {"mean": post0.mean[0], "rho": post0.rho[0]}
        losses = [s.round()["loss"]]
        grad = leaf_norms(s.state.opt_state.mu)
        for _ in range(self.traffic["check_rounds"] - 1):
            losses.append(s.round()["loss"])
        change = leaf_norms(s.posterior(), row0)
        self.readings = {"loss": losses, "grad": grad, "change": change}

    def window(self, seconds: float, trace_dir: Path | None = None) -> dict:
        s = self.session
        limit = min(seconds, program.TRACE_SECONDS) if trace_dir else seconds
        if trace_dir:
            program.profile(trace_dir)
        rounds = failed = samples = 0
        load = []
        with jax.profiler.TraceAnnotation(WINDOW_ANNOTATION):
            t0 = time.perf_counter()
            while True:
                with jax.profiler.TraceAnnotation("bench.round"):
                    rec = s.round()
                rounds += 1
                samples += rec["n_trained"] * self.samples_per_agent
                if rec["loss"] is None or not np.isfinite(rec["loss"]):
                    failed += 1
                if s.obs is not None:
                    load.append(s.obs.registry.gauge(
                        "model.expert_load_max").value())
                t = time.perf_counter() - t0
                if t >= limit:
                    break
        if trace_dir:
            jax.profiler.stop_trace()
            self._layers = lm_scopes.per_round(
                lm_scopes.scope_seconds(find_xplane(trace_dir)), rounds)
        if load:
            self._layers["expert_load_max"] = max(load)
        return {"attempted": rounds, "failed": failed, "rounds": rounds,
                "elapsed_s": t, "samples_per_s": samples / t,
                "end_to_end": {"train_samples_per_s": samples / t}}

    def layer_context(self) -> dict:
        obs = self.session.obs
        return {"spans": list(obs.tracer.spans) if obs is not None else [],
                "lm": dict(self._layers)}

    def free(self) -> None:
        del self.session
        gc.collect()

    def check(self, ref_mod, win: dict) -> dict:
        ref = ref_mod.train_readings(self.cfg, self.traffic, self.seed,
                                     self.traffic["check_rounds"])
        return correct.train_numbers(self.readings, ref)


def control(cell, seed: int, only, say) -> None:
    """The readings the limits are set from, for one seed (``control.py``):
    the program against the float32 reference; the reference with its
    trunk matmuls on float8 inputs, in the program's place; the reference
    with each fault planted."""
    from chipbench import cells

    ref_mod = cells.reference_module(cell.config)
    rounds = cell.traffic["check_rounds"]

    def ref(**kw):
        return ref_mod.train_readings(cell.config, cell.traffic, seed,
                                      rounds, **kw)

    # the program first, as in a run: the reference's state would otherwise
    # hold device memory that the program's window needs
    prog = None
    if only in (None, "program"):
        drv = Driver(cell.config, cell.traffic, seed)
        drv.setup(0.0)
        prog = drv.readings
        drv.free()
        gc.collect()
    ref32 = ref()
    if prog is not None:
        say("program", seed, correct.train_numbers(prog, ref32))
    if only in (None, "control"):
        say("control", seed, correct.train_numbers(
            ref(matmul_dtype=jnp.float8_e4m3fn), ref32))
    if only in (None, "faults"):
        for f in FAULTS:
            say(f"fault:{f}", seed, correct.train_numbers(ref(fault=f), ref32))
