"""``repro.obs`` — the pure-observer observability layer (ROADMAP
"Observability").

Three pillars, one contract:

* ``obs.metrics`` — the unified ``MetricsRegistry`` (counters / gauges /
  histograms with labels, JSONL event sink, Prometheus-style exporter);
* ``obs.trace`` — nested wall-clock spans with compile-vs-warm
  attribution, each also a profiler annotation when enabled (plus the
  ``CompileWarmTimer`` / ``median_us`` bench helpers the benchmarks build
  on);
* ``obs.convergence`` — theory-vs-measured: live network disagreement /
  KL against ``core.theory``'s predicted decay.

The contract: observability is READ-ONLY and OFF by default.  With
``ObsSpec`` unset a run is bitwise identical to an uninstrumented build
(same trajectories, same jit trace counts, same checkpoint leaves); with
it enabled the training math is still bit-identical — the instruments only
ever observe already-materialized host values.  ``tests/test_obs.py`` pins
both directions.

Front door: ``ExperimentSpec(obs=ObsSpec(enabled=True))`` →
``session.obs`` (an ``Observability``) → ``session.dashboard()``.
"""
from __future__ import annotations

from repro.obs.convergence import ConvergenceTracker, network_stats
from repro.obs.metrics import (
    Counter,
    Gauge,
    Histogram,
    JsonlSink,
    MetricsRegistry,
)
from repro.obs.trace import (
    CompileWarmTimer,
    Tracer,
    compile_warm_split,
    median_us,
)

__all__ = [
    "ConvergenceTracker",
    "network_stats",
    "Counter",
    "Gauge",
    "Histogram",
    "JsonlSink",
    "MetricsRegistry",
    "CompileWarmTimer",
    "Tracer",
    "compile_warm_split",
    "median_us",
    "Observability",
]


class Observability:
    """One session's observability bundle: registry + tracer (+ optional
    convergence tracker), wired to a shared JSONL sink.

    Built by ``api.session.build_session`` when ``spec.obs.enabled``; the
    session and the engines talk to THIS object (never to the spec), and
    everything on it is a pure observer of already-computed host values.
    """

    def __init__(self, obs_spec, static_w=None):
        self.spec = obs_spec
        self.sink = (
            JsonlSink(obs_spec.jsonl_path) if obs_spec.jsonl_path else None
        )
        self.registry = MetricsRegistry(sink=self.sink)
        self.tracer = Tracer(enabled=obs_spec.trace, sink=self.sink)
        self.convergence = (
            ConvergenceTracker(W=static_w) if obs_spec.convergence else None
        )

    @classmethod
    def from_spec(cls, spec) -> "Observability | None":
        """``None`` unless ``spec.obs.enabled``.  For the convergence
        tracker's theory overlay, a STATIC topology (named builder /
        explicit / single-matrix schedule) contributes its W; scheduled,
        callable, and gossip topologies track measured decay only (their
        per-round W varies, so the spectral rate is not a constant)."""
        if not spec.obs.enabled:
            return None
        static_w = None
        if spec.obs.convergence:
            try:
                mats = spec.topology._static_list()
            except ValueError:
                mats = None
            if mats is not None and len(mats) == 1:
                static_w = mats[0]
        return cls(spec.obs, static_w=static_w)

    def flush(self) -> None:
        """Push buffered spans/events to the JSONL sink, if one is set."""
        self.tracer.flush()
        if self.sink is not None:
            self.sink.flush()
