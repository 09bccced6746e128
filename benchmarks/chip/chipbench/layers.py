"""Device time by the program's named scopes, and device idle time by the
program's host spans, from one profiler trace.

Each device operation carries the scope path of the JAX code that made it
(the ``tf_op`` stat of its metadata, e.g.
``jit(window_fn)/local_phase/vmap()/while/body/closed_call/optimizer/mul:``).
A scope is a whole path component; JAX's ``vmap(...)``, ``jvp(...)`` and
``transpose(...)`` wrappers around it are ignored, so
``transpose(jvp(vmap(nll)))`` is ``nll``.  A fusion carries the path of
the one operation XLA kept as its metadata.

An operation's *layer* is the outermost of ``LAYERS`` on its path (the
validity probe, ``consensus/.../fault_guard``, is consensus work); the
layers therefore split the device's self time without overlap, and what
lies under none of them is listed by operation.  The local phase is split
further by the first of ``LOCAL_PARTS`` on the path.

The program's spans reach the trace as host annotations of their bare
names (``repro.obs.trace``).  Device idle time inside a ``session.round``
annotation is attributed to the innermost program span open over it.
Times follow ``chipbench.trace``: self times summed over chips, idle time
on chip 0, all clipped to the benchmark's window annotation.
"""
from __future__ import annotations

import dataclasses
import re
from pathlib import Path

from chipbench import trace as tr
from chipbench import xplane

LAYERS = ("local_phase", "consensus", "agent_select", "fault_guard")
LOCAL_PARTS = ("optimizer", "sample", "nll", "kl")
ROUND_SPAN = "session.round"
SCOPE_STAT = "tf_op"
_WRAPPED = re.compile(r"^(?:vmap|jvp|transpose)\((.*)\)$")


@dataclasses.dataclass
class Op(tr.Event):
    scope: str = ""  # the operation's scope path


def scope_components(path: str) -> list[str]:
    """``jit(f)/vmap(local_phase)/transpose(jvp(nll))/mul:`` ->
    ``["jit(f)", "local_phase", "nll", "mul"]`` (the ``:<op type>``
    suffix of the stat dropped, wrappers peeled)."""
    path = path.rsplit(":", 1)[0]
    out = []
    for comp in path.split("/"):
        m = _WRAPPED.match(comp)
        while m:
            comp = m.group(1)
            m = _WRAPPED.match(comp)
        out.append(comp)
    return out


def layer_of(path: str) -> str | None:
    return next((c for c in scope_components(path) if c in LAYERS), None)


def local_part_of(path: str) -> str | None:
    return next((c for c in scope_components(path) if c in LOCAL_PARTS),
                None)


def read(path: Path) -> tr.Trace:
    """The trace as ``chipbench.trace.read_trace`` gives it (times in whole
    nanoseconds, as ``ProfileData`` rounds them), each device operation an
    ``Op`` with its scope path."""
    devices, host = [], []
    for plane in xplane.read_planes(path):
        if tr.DEVICE_PLANE.match(plane.name):
            ops = []
            for line in plane.lines:
                if line.name != tr.OPS_LINE:
                    continue
                for mid, off, dur in line.events:
                    start = line.timestamp_ns + off // 1000
                    ops.append(Op(tr.op_name(plane.event_names.get(mid, "")),
                                  start, start + dur // 1000,
                                  plane.event_stats.get(mid, {})
                                  .get(SCOPE_STAT, "")))
            devices.append(ops)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for mid, off, dur in line.events:
                    start = line.timestamp_ns + off // 1000
                    host.append(tr.Event(plane.event_names.get(mid, ""),
                                         start, start + dur // 1000))
    return tr.Trace(devices=devices, host=host)


@dataclasses.dataclass
class Layers:
    window_s: float
    busy_s: float  # mean over chips of the union of op intervals
    layer_s: dict  # layer -> self seconds, summed over chips
    part_s: dict  # part of the local phase -> self seconds
    unscoped_s: dict  # op name -> self seconds of ops under no layer
    idle_by_span: dict  # innermost program span (None: none) -> idle s
    round_idle_s: float | None  # chip-0 idle inside session.round
    coverage_pct: float  # share of device busy time under a layer

    def summary(self, rounds: int, top: int = 10) -> dict:
        """Per-round milliseconds, for a result line."""
        def ms(d):
            return {k or "(no program span)": 1e3 * v / rounds
                    for k, v in sorted(d.items(), key=lambda kv: -kv[1])}

        unscoped = sorted(self.unscoped_s.items(), key=lambda kv: -kv[1])
        return {"layer_ms": ms(self.layer_s),
                "local_part_ms": ms(self.part_s),
                "unscoped_ms": ms(dict(unscoped[:top])),
                "unscoped_total_ms": 1e3 * sum(self.unscoped_s.values())
                / rounds,
                "idle_by_span_ms": ms(self.idle_by_span),
                "coverage_pct": self.coverage_pct}


def _clip(ops, lo, hi):
    return [dataclasses.replace(e, start=max(e.start, lo), end=min(e.end, hi))
            for e in ops if e.end > lo and e.start < hi]


def _span_segments(spans, lo, hi):
    """[(a, b, innermost span name or None, inside session.round)] over
    [lo, hi]; program spans nest, so the innermost open span is the one
    opened last."""
    spans = sorted(spans, key=lambda e: (e.start, -(e.end - e.start)))
    pts = sorted({lo, hi, *(min(max(x, lo), hi) for e in spans
                            for x in (e.start, e.end))})
    out, open_, i = [], [], 0
    for a, b in zip(pts, pts[1:]):
        while i < len(spans) and spans[i].start <= a:
            open_.append(spans[i])
            i += 1
        open_ = [e for e in open_ if e.end > a]
        inner = max(open_, key=lambda e: (e.start, -(e.end - e.start)),
                    default=None)
        out.append((a, b, inner.name if inner else None,
                    any(e.name == ROUND_SPAN for e in open_)))
    return out


def _idle_by_span(gaps, segments):
    """Overlap of idle gaps and span segments, both ascending and
    disjoint: (seconds by innermost span, seconds inside session.round)."""
    by_span, in_round = {}, 0.0
    i = j = 0
    while i < len(gaps) and j < len(segments):
        (a, b), (c, d, name, rnd) = gaps[i], segments[j]
        ov = min(b, d) - max(a, c)
        if ov > 0:
            by_span[name] = by_span.get(name, 0.0) + ov * 1e-9
            if rnd:
                in_round += ov * 1e-9
        if b <= d:
            i += 1
        else:
            j += 1
    return by_span, in_round


def reduce(trace: tr.Trace, program_spans) -> Layers:
    """``program_spans``: the names of the program's tracer spans (the
    host annotations that are the program's own)."""
    if not trace.devices:
        raise ValueError("the trace has no TPU device plane")
    lo, hi = trace.window
    busy, layer_s, part_s, unscoped = [], {}, {}, {}
    idle_by_span, round_idle = {}, None
    for chip, ops in enumerate(trace.devices):
        ops = _clip(ops, lo, hi)
        cover = tr.union(ops)
        busy.append(sum(b - a for a, b in cover))
        # self time per event: each event under a name of its own
        selfs = tr.self_times([tr.Event(str(k), e.start, e.end)
                               for k, e in enumerate(ops)])
        for k, e in enumerate(ops):
            s = selfs[str(k)] * 1e-9
            layer = layer_of(e.scope)
            if layer is None:
                unscoped[e.name] = unscoped.get(e.name, 0.0) + s
                continue
            layer_s[layer] = layer_s.get(layer, 0.0) + s
            part = local_part_of(e.scope) if layer == "local_phase" else None
            if part is not None:
                part_s[part] = part_s.get(part, 0.0) + s
        if chip == 0:
            edges = [lo] + [x for iv in cover for x in iv] + [hi]
            gaps = [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]
            spans = [e for e in trace.host if e.name in program_spans]
            idle_by_span, in_round = _idle_by_span(
                gaps, _span_segments(spans, lo, hi))
            if any(e.name == ROUND_SPAN for e in spans):
                round_idle = in_round
    return Layers(window_s=(hi - lo) * 1e-9,
                  busy_s=sum(busy) / len(busy) * 1e-9, layer_s=layer_s,
                  part_s=part_s, unscoped_s=unscoped,
                  idle_by_span=idle_by_span, round_idle_s=round_idle,
                  coverage_pct=100.0 * sum(layer_s.values())
                  / (sum(busy) * 1e-9))


def per_round_ms(ctx, seconds: float | None) -> float | None:
    return None if seconds is None else 1e3 * seconds / ctx["window"]["rounds"]


def layer_ms(ctx, layer: str) -> float | None:
    """Per-round device milliseconds of one layer, or None when the trace
    holds no operation under it (or the run kept no layers)."""
    lay = ctx.get("layers")
    return None if lay is None else per_round_ms(ctx, lay.layer_s.get(layer))
