"""Training traffic: rounds back to back through ``Session.round()``.

A mix for this driver (``traffic/<mix>.json`` with ``"driver": "train"``)
carries no arrivals.  It names the gossip clock (``clock``, null for
synchronous rounds), the fault model and policy (``faults``,
``fault_policy``), and how many first rounds the correctness check follows
(``check_rounds``); ``about`` says what it is for.

Set-up builds the cell's session once, drives its first ``check_rounds``
rounds through ``Session.round()`` (the first compiles; the readings of
those rounds are kept for the correctness check), and hands the same
session to the window.  The window reports ``train_samples_per_s``: the
samples of every completed round (``n_trained`` x u x B: a crashed agent
trains nothing) over the time from the window's start to the end of its
last round.
"""
from __future__ import annotations

import gc
import time
from pathlib import Path

import jax
import numpy as np

from chipbench import correct, program
from chipbench.trace import WINDOW_ANNOTATION

TRAFFIC_KEYS = ("driver", "about", "check_rounds") + program.SPEC_TRAFFIC_KEYS
# faults that the control run plants in the reference; a step that returns
# its state unchanged reads 1 by construction and needs no run
FAULTS = ("half_batch", "no_exchange")


class Driver:
    def __init__(self, cfg: dict, traffic: dict, seed: int, *,
                 obs: bool = False):
        from repro.api import build_session

        program.only_keys(traffic, TRAFFIC_KEYS, "traffic")
        self.cfg, self.traffic, self.seed = cfg, traffic, seed
        data = cfg["data"]
        self.samples_per_agent = data["local_updates"] * data["batch_size"]
        self.session = build_session(program.build_spec(cfg, traffic, seed,
                                                        obs=obs))
        p = int(self.session.posterior().mean.shape[1])
        if p != cfg["model"]["n_params"]:
            raise ValueError(f"the program's model has {p} parameters; the "
                             f"configuration states {cfg['model']['n_params']}")

    def setup(self, seconds: float) -> None:
        """The first rounds through the window's own call; their readings."""
        s = self.session
        post0 = s.posterior()
        row0 = {"mean": post0.mean[0], "rho": post0.rho[0]}
        losses = [s.round()["loss"]]
        grad = program.leaf_norms(s.state.opt_state.mu)
        for _ in range(self.traffic["check_rounds"] - 1):
            losses.append(s.round()["loss"])
        change = program.leaf_norms(s.posterior(), row0)
        self.readings = {"loss": losses, "grad": grad, "change": change}
        n_q = getattr(s.state, "n_quarantined", None)
        if n_q is not None:
            self.readings["quarantined"] = int(np.asarray(n_q).sum())

    def window(self, seconds: float, trace_dir: Path | None = None) -> dict:
        s = self.session
        limit = min(seconds, program.TRACE_SECONDS) if trace_dir else seconds
        if trace_dir:
            program.profile(trace_dir)
        rounds = failed = samples = 0
        with jax.profiler.TraceAnnotation(WINDOW_ANNOTATION):
            t0 = time.perf_counter()
            while True:
                with jax.profiler.TraceAnnotation("bench.round"):
                    rec = s.round()
                rounds += 1
                samples += rec["n_trained"] * self.samples_per_agent
                if rec["loss"] is None or not np.isfinite(rec["loss"]):
                    failed += 1
                t = time.perf_counter() - t0
                if t >= limit:
                    break
        if trace_dir:
            jax.profiler.stop_trace()
        return {"attempted": rounds, "failed": failed, "rounds": rounds,
                "elapsed_s": t, "samples_per_s": samples / t,
                "end_to_end": {"train_samples_per_s": samples / t}}

    def layer_context(self) -> dict:
        obs = self.session.obs
        return {"spans": list(obs.tracer.spans) if obs is not None else []}

    def free(self) -> None:
        del self.session
        gc.collect()

    def check(self, ref_mod, win: dict) -> dict:
        ref = ref_mod.train_readings(self.cfg, self.traffic, self.seed,
                                     self.traffic["check_rounds"])
        return correct.train_numbers(self.readings, ref)


def control(cell, seed: int, only, say) -> None:
    """The readings the limits are set from, for one seed (``control.py``):
    the program against the float32 reference; the reference in bfloat16
    in the program's place; the reference with each fault planted."""
    import jax.numpy as jnp

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
        say("control", seed, correct.train_numbers(ref(dtype=jnp.bfloat16),
                                                   ref32))
    if only in (None, "faults"):
        for f in FAULTS:
            say(f"fault:{f}", seed,
                correct.train_numbers(ref(fault=f), ref32))
