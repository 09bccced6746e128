"""Device time of the language-model step by its named scopes.

``chipbench.layers`` gives each device operation to the outermost of the
round-level scopes (``local_phase``, ``consensus``, ...).  Inside the local
phase the LM family opens scopes of its own (``repro.models``,
``repro.api.models``): ``mla`` (latent attention), ``moe`` with
``router``, ``experts`` and ``shared_experts`` inside it, and ``lm_head``
(the chunked loss).  This module gives each device operation to the
INNERMOST of those on its scope path (``chipbench.layers``'
``scope_components``, wrappers peeled), and sums self times.

XLA lowers each grouped matmul (``jax.lax.ragged_dot``) to custom calls
named ``ragged-dot-*`` whose metadata keeps no scope path; only the routed
experts run grouped matmuls, so those operations belong to ``experts``.
"""
from __future__ import annotations

from pathlib import Path

from chipbench import layers
from chipbench import trace as tr

SCOPES = ("mla", "moe", "router", "experts", "shared_experts", "lm_head")
MOE_PARTS = ("moe", "router", "experts", "shared_experts")
GROUPED_MATMUL = "ragged-dot"


def scope_of(op_name: str, path: str) -> str | None:
    if op_name.startswith(GROUPED_MATMUL) or path.startswith(GROUPED_MATMUL):
        return "experts"
    comps = [c for c in layers.scope_components(path) if c in SCOPES]
    return comps[-1] if comps else None


def scope_seconds(xplane: Path) -> dict:
    """Self seconds per innermost LM scope inside the benchmark's window,
    summed over chips; ``None`` for device work under none of them."""
    trace = layers.read(xplane)
    lo, hi = trace.window
    out: dict = {}
    for ops in trace.devices:
        ops = [o for o in ops if o.end > lo and o.start < hi]
        clipped = [tr.Event(str(k), max(o.start, lo), min(o.end, hi))
                   for k, o in enumerate(ops)]
        selfs = tr.self_times(clipped)
        for k, o in enumerate(ops):
            s = scope_of(o.name, o.scope)
            out[s] = out.get(s, 0.0) + selfs[str(k)] * 1e-9
    return out


def per_round(seconds: dict, rounds: int) -> dict:
    """``mla_s``, ``moe_s`` (router, experts and shared experts included)
    and ``experts_s`` per round, or {} when the trace holds none of them
    (a program without the scopes)."""
    if not any(seconds.get(s) for s in SCOPES):
        return {}
    return {"mla_s": seconds.get("mla", 0.0) / rounds,
            "moe_s": sum(seconds.get(s, 0.0) for s in MOE_PARTS) / rounds,
            "experts_s": seconds.get("experts", 0.0) / rounds}
