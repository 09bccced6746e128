"""Nested wall-clock spans with explicit compile-vs-warm attribution.

The gossip/serve benches all reinvented the same timing discipline by hand:
run the first call separately (it pays the jit trace + XLA compile), THEN
start the timer, and report ``compile_us`` next to the warm median — because
a mean over calls that includes the compile is off by orders of magnitude.
This module promotes that discipline into a reusable API:

* ``Tracer`` — nested ``span(name, **attrs)`` context managers recording
  wall-clock intervals into an in-process buffer.  Spans carry arbitrary
  attributes; the ``compile=True`` attribute marks a span as
  compile-attributed, and ``summary()`` splits every aggregate into
  ``compile`` / ``warm`` groups so steady-state numbers are never polluted.
  A DISABLED tracer's ``span`` is a reusable no-op context manager — the
  instrumented hot path pays one attribute check and an empty
  ``with`` (asserted ~0 by ``benchmarks/bench_obs.py``).
* ``CompileWarmTimer`` — the two-phase bench pattern as an object: time
  the compiling call under ``with t.compile():``, the steady-state run
  under ``with t.warm():``.
* ``median_us(fn, *args)`` — median-of-warm-calls microbenchmark helper
  (blocks on jax values so device work is actually counted).

Spans measure HOST wall-clock at the dispatch boundary.  Calls that return
before the device finishes (jax async dispatch) are only fully counted
when something downstream synchronizes — ``Session.round`` does
(``np.asarray(losses)``, under its ``session.sync`` span), so the
``session.round`` span is end-to-end accurate; inner engine spans are
dispatch-side and documented as such.

An enabled tracer's span also opens a ``jax.profiler.TraceAnnotation``
under the span's bare name, so a profiler trace shows the program's spans
on the same clock as the device's operations (the attributes stay in the
span's own record: an annotation carries them in its name).
"""
from __future__ import annotations

import contextlib
import dataclasses
import json
import time
from typing import Any, Callable

from jax.profiler import TraceAnnotation


@dataclasses.dataclass
class Span:
    """One recorded interval (microseconds since the tracer epoch)."""

    name: str
    t0_us: float
    dur_us: float
    depth: int
    attrs: dict

    def to_event(self) -> dict:
        ev = {"kind": "span", "name": self.name, "t0_us": round(self.t0_us, 3),
              "dur_us": round(self.dur_us, 3), "depth": self.depth}
        if self.attrs:
            ev["attrs"] = {k: _plain(v) for k, v in self.attrs.items()}
        return ev


def _plain(v):
    return v if isinstance(v, (bool, int, float, str, type(None))) else str(v)


class _NullSpan:
    """Reusable no-op context manager (the disabled-tracer fast path)."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()


class _LiveSpan:
    """An open span: closes itself into the tracer buffer on ``__exit__``,
    inside a profiler annotation of the same (bare) name."""

    __slots__ = ("tracer", "name", "attrs", "t0", "annotation")

    def __init__(self, tracer: "Tracer", name: str, attrs: dict):
        self.tracer = tracer
        self.name = name
        self.attrs = attrs

    def __enter__(self):
        self.annotation = TraceAnnotation(self.name)
        self.annotation.__enter__()
        self.tracer._depth += 1
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        tr = self.tracer
        tr._depth -= 1
        tr.spans.append(Span(
            name=self.name,
            t0_us=(self.t0 - tr._epoch) * 1e6,
            dur_us=(t1 - self.t0) * 1e6,
            depth=tr._depth,
            attrs=self.attrs,
        ))
        self.annotation.__exit__(*exc)
        return False


class Tracer:
    """In-process span recorder; disabled by default and free when so."""

    def __init__(self, enabled: bool = True, sink=None):
        self.enabled = enabled
        self.spans: list[Span] = []
        self.sink = sink  # optional metrics.JsonlSink; spans land as events
        self._depth = 0
        self._flushed = 0
        self._epoch = time.perf_counter()

    def span(self, name: str, **attrs):
        """``with tracer.span("gossip.window", impl="masked"): ...`` —
        records nothing (and allocates nothing) when the tracer is off."""
        if not self.enabled:
            return _NULL_SPAN
        return _LiveSpan(self, name, attrs)

    # -- aggregation ---------------------------------------------------------

    def summary(self) -> dict:
        """Per span name: count / total / mean / p50 / max (us), split into
        ``warm`` and ``compile`` groups by the ``compile`` attribute."""
        grouped: dict[str, dict[str, list[float]]] = {}
        for s in self.spans:
            mode = "compile" if s.attrs.get("compile") else "warm"
            grouped.setdefault(s.name, {}).setdefault(mode, []).append(s.dur_us)
        out: dict[str, dict] = {}
        for name, modes in sorted(grouped.items()):
            out[name] = {}
            for mode, durs in modes.items():
                durs = sorted(durs)
                n = len(durs)
                out[name][mode] = {
                    "n": n,
                    "total_us": sum(durs),
                    "mean_us": sum(durs) / n,
                    "p50_us": durs[n // 2],
                    "max_us": durs[-1],
                }
        return out

    def flush(self) -> int:
        """Push buffered spans to the JSONL sink (if any); returns the
        number of spans written this call."""
        if self.sink is None:
            return 0
        n = 0
        for s in self.spans[self._flushed:]:
            self.sink.emit(s.to_event())
            n += 1
        self._flushed = len(self.spans)
        return n

    def to_jsonl(self, path: str) -> int:
        """Write every recorded span to ``path`` (one JSON object per
        line); returns the span count."""
        with open(path, "w") as fh:
            for s in self.spans:
                fh.write(json.dumps(s.to_event(), sort_keys=True) + "\n")
        return len(self.spans)


class CompileWarmTimer:
    """The bench_gossip ad-hoc split as a reusable object.

        t = CompileWarmTimer()
        with t.compile():
            session.round()          # pays trace + XLA compile
        with t.warm():
            session.run(n_rounds=m)  # steady state
        t.compile_us, t.warm_us, t.warm_us_per(m)

    Multiple ``compile()``/``warm()`` blocks accumulate (re-traces under
    distinct shapes all belong to the compile bucket)."""

    def __init__(self):
        self.compile_us = 0.0
        self.warm_us = 0.0

    @contextlib.contextmanager
    def compile(self):
        t0 = time.perf_counter()
        try:
            yield self
        finally:
            self.compile_us += (time.perf_counter() - t0) * 1e6

    @contextlib.contextmanager
    def warm(self):
        t0 = time.perf_counter()
        try:
            yield self
        finally:
            self.warm_us += (time.perf_counter() - t0) * 1e6

    def warm_us_per(self, n_calls: int) -> float:
        return self.warm_us / max(1, n_calls)

    def as_dict(self) -> dict:
        return {"compile_us": self.compile_us, "warm_us": self.warm_us}


def _block(x) -> None:
    try:
        import jax

        jax.block_until_ready(x)
    except (ImportError, TypeError):
        pass


def median_us(fn: Callable[..., Any], *args, iters: int = 5) -> float:
    """Median warm wall-clock of ``fn(*args)`` in microseconds.  The caller
    is responsible for warming ``fn`` first (or use ``compile_warm_split``);
    jax return values are blocked on so device time is counted."""
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        _block(fn(*args))
        times.append((time.perf_counter() - t0) * 1e6)
    return sorted(times)[len(times) // 2]


def compile_warm_split(
    fn: Callable[..., Any], *args, iters: int = 5
) -> dict:
    """Time ``fn(*args)``'s first call (compile) apart from its warm
    median: ``{"compile_us", "warm_us_median"}``."""
    t0 = time.perf_counter()
    _block(fn(*args))
    compile_us = (time.perf_counter() - t0) * 1e6
    return {
        "compile_us": compile_us,
        "warm_us_median": median_us(fn, *args, iters=iters),
    }
