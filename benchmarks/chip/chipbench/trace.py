"""Reduce a profiler trace (``.xplane.pb``) to device busy and idle time,
per-operation device time, and idle gaps named by what the host was doing.

Layout of a TPU trace as JAX writes it: one plane per chip named
``/device:TPU:<i>`` whose line ``XLA Ops`` holds one event per executed HLO
operation (the event name is the instruction's text, ``%name.N = ...``;
a Pallas kernel is a ``custom-call`` named after its kernel), and host
planes (``/host:CPU``) whose lines hold runtime events and the
``TraceAnnotation`` spans of the benchmark.  Device and host events share
one time base, in nanoseconds from the start of the profile.

Control-flow operations (``while``, ``conditional``, ``call``) enclose the
operations of their bodies on the same line; per-operation times are
therefore *self* times: an event's duration less that of the events nested
in it.  Busy time is the union of all operation intervals.
"""
from __future__ import annotations

import dataclasses
import heapq
import re
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
WINDOW_ANNOTATION = "bench.window"
_OP_NAME = re.compile(r"^%?([^\s=]+)")


@dataclasses.dataclass
class Event:
    name: str
    start: float  # ns
    end: float  # ns


@dataclasses.dataclass
class Trace:
    devices: list  # one list of op Events per chip
    host: list  # host Events (runtime and annotations)

    @property
    def window(self) -> tuple[float, float]:
        """[start, end] of the benchmark's window annotation (ns)."""
        spans = [e for e in self.host if e.name == WINDOW_ANNOTATION]
        if not spans:
            raise ValueError(f"no {WINDOW_ANNOTATION!r} span in the trace")
        return min(e.start for e in spans), max(e.end for e in spans)


def op_name(text: str) -> str:
    """``%fusion.154 = (f32[...]) fusion(...)`` -> ``fusion.154``."""
    m = _OP_NAME.match(text.strip())
    return m.group(1) if m else text[:64]


def find_xplane(trace_dir: Path) -> Path:
    files = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def read_trace(path: Path) -> Trace:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    devices, host = [], []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            ops = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops += [Event(op_name(e.name), e.start_ns,
                                  e.start_ns + e.duration_ns)
                            for e in line.events]
            devices.append(ops)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [Event(e.name, e.start_ns, e.start_ns + e.duration_ns)
                         for e in line.events]
    return Trace(devices=devices, host=host)


def _clip(events, lo, hi):
    return [Event(e.name, max(e.start, lo), min(e.end, hi))
            for e in events if e.end > lo and e.start < hi]


def union(events) -> list[tuple[float, float]]:
    """Merged [start, end] intervals covered by any event."""
    out: list[list[float]] = []
    for e in sorted(events, key=lambda e: e.start):
        if out and e.start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e.end)
        else:
            out.append([e.start, e.end])
    return [(a, b) for a, b in out]


def self_times(events) -> dict[str, float]:
    """Per-name self time (ns): each event's duration less that of the
    events it encloses directly."""
    total: dict[str, float] = {}
    stack: list[list] = []  # [event, child_time]

    def close(item):
        ev, child = item
        total[ev.name] = total.get(ev.name, 0.0) + (ev.end - ev.start) - child
        if stack:
            stack[-1][1] += ev.end - ev.start

    for e in sorted(events, key=lambda e: (e.start, -(e.end - e.start))):
        while stack and stack[-1][0].end <= e.start:
            close(stack.pop())
        stack.append([e, 0.0])
    while stack:
        close(stack.pop())
    return total


def gap_causes(gaps, host) -> list[str]:
    """For each gap (ascending, disjoint), what the host was doing: the
    shortest host event that covers at least half of the gap, else the one
    that overlaps it most.  A sweep that keeps only the host events open
    across the gap."""
    host = sorted((e for e in host if e.name != WINDOW_ANNOTATION),
                  key=lambda e: e.start)
    open_: list = []  # heap of (end, seq, event)
    out, i = [], 0
    for a, b in gaps:
        while i < len(host) and host[i].start < b:
            heapq.heappush(open_, (host[i].end, i, host[i]))
            i += 1
        while open_ and open_[0][0] <= a:
            heapq.heappop(open_)
        best, best_key = "host: no traced event", (False, 0.0, 0.0)
        for _, _, e in open_:
            ov = min(e.end, b) - max(e.start, a)
            dur = e.end - e.start
            covers = 2 * ov >= b - a
            key = (covers, -dur, ov) if covers else (False, ov, -dur)
            if ov > 0 and key > best_key:
                best, best_key = e.name, key
        out.append(best)
    return out


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float  # mean over chips of the union of op intervals
    op_self_s: dict  # name -> seconds, summed over chips
    idle_gaps: dict  # host cause -> seconds of device idle (chip 0)

    @property
    def idle_pct(self) -> float:
        return 100.0 * (1.0 - self.busy_s / self.window_s)

    def kernel_s(self, prefixes) -> float | None:
        """Self seconds of operations whose name starts with a prefix, or
        None when no such operation ran."""
        hits = [s for n, s in self.op_self_s.items()
                if any(n.startswith(p) for p in prefixes)]
        return sum(hits) if hits else None

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.op_self_s.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.idle_gaps.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[n, s] for n, s in ops],
                "idle_gaps": [[n, s] for n, s in gaps]}


def reduce(trace: Trace) -> Reduced:
    lo, hi = trace.window
    busy, selfs, gaps = [], {}, {}
    for i, ops in enumerate(trace.devices):
        ops = _clip(ops, lo, hi)
        cover = union(ops)
        busy.append(sum(b - a for a, b in cover))
        for n, t in self_times(ops).items():
            selfs[n] = selfs.get(n, 0.0) + t * 1e-9
        if i == 0:
            edges = [lo] + [x for iv in cover for x in iv] + [hi]
            idle = [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]
            for (a, b), cause in zip(idle, gap_causes(idle, trace.host)):
                gaps[cause] = gaps.get(cause, 0.0) + (b - a) * 1e-9
    if not trace.devices:
        raise ValueError("the trace has no TPU device plane")
    return Reduced(window_s=(hi - lo) * 1e-9,
                   busy_s=sum(busy) / len(busy) * 1e-9,
                   op_self_s=selfs, idle_gaps=gaps)
