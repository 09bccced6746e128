"""Observability layer (ROADMAP "Observability"; ``repro.obs``).

Pins the layer's two-sided contract plus the unit behavior of each pillar:

* **Zero perturbation** — with ``ObsSpec`` unset nothing is recorded and
  nothing changes; with it enabled the TRAINING MATH is still bitwise
  identical (posteriors, trace counts) because every instrument observes
  already-materialized host values.
* **Namespaced telemetry** — ``evaluate()`` puts engine telemetry under
  ``out["engine"]``; a telemetry key can never clobber a metric key
  (regression for the pre-obs ``out.update(...)`` merge).
* Registry / exporter / tracer / convergence-tracker units, and the
  ``ObsSpec`` doc + checkpoint round trip.
* **One clock** — an enabled tracer's spans are profiler annotations too,
  and every round program carries the layer scopes a device trace is read
  by (``local_phase``, ``optimizer``, ``consensus``, ``fault_guard``).
"""
import dataclasses
import json
import math
import re

import jax
import numpy as np
import pytest

from repro.core.numerics import softplus_inv
from repro.obs.convergence import ConvergenceTracker, network_stats
from repro.obs.metrics import (
    JsonlSink,
    MetricsRegistry,
    escape_label_value,
    sanitize_name,
)
from repro.obs import trace as trace_mod
from repro.obs.trace import CompileWarmTimer, Tracer

# ---------------------------------------------------------------------------
# metrics registry


def test_counter_gauge_histogram_basics():
    reg = MetricsRegistry()
    c = reg.counter("c")
    c.inc()
    c.inc(2.5)
    assert c.value() == 3.5
    with pytest.raises(ValueError):
        c.inc(-1)
    g = reg.gauge("g")
    g.set(1.0)
    g.set(4.0)
    assert g.value() == 4.0
    h = reg.histogram("h", buckets=(1.0, 10.0))
    for v in (0.5, 5.0, 50.0):
        h.observe(v)
    s = h.summary()
    assert s["count"] == 3 and s["min"] == 0.5 and s["max"] == 50.0
    assert s["sum"] == pytest.approx(55.5)
    assert reg.histogram("h").summary(mc="8") == {"count": 0}


def test_labels_are_independent_series():
    reg = MetricsRegistry()
    c = reg.counter("req")
    c.inc(1, mc="1")
    c.inc(5, mc="8")
    assert c.value(mc="1") == 1 and c.value(mc="8") == 5
    assert c.value() == 0  # unlabeled series untouched


def test_instrument_kind_collision_raises():
    reg = MetricsRegistry()
    reg.counter("x")
    assert reg.counter("x") is reg.counter("x")  # idempotent
    with pytest.raises(ValueError, match="already registered"):
        reg.gauge("x")


def test_ingest_flattens_telemetry_vocabulary():
    reg = MetricsRegistry()
    reg.ingest("engine", {
        "staleness": {"p50": 1.0, "p90": 3},
        "per_agent": [2, 4],
        "wire_dtype": "bf16",
        "ok": True,
        "skipped": None,
    })
    got = reg.collect()
    assert got["engine.staleness.p50"] == 1.0
    assert got["engine.staleness.p90"] == 3.0
    assert got["engine.per_agent.0"] == 2.0
    assert got["engine.per_agent.1"] == 4.0
    assert got["engine.ok"] == 1.0
    assert got["engine.wire_dtype"] == "bf16"  # info entry
    assert "engine.skipped" not in got


def test_prometheus_export_deterministic_and_sane():
    def build(order):
        reg = MetricsRegistry()
        for name in order:
            reg.counter(name).inc(1)
        reg.gauge("z.gauge").set(2.5)
        return reg.to_prometheus()

    a = build(["b.n", "a.n"])
    b = build(["a.n", "b.n"])  # insertion order must not matter
    assert a == b
    assert "a_n_total 1\n" in a and "z_gauge 2.5\n" in a


def test_sanitize_name():
    assert sanitize_name("gossip.window-time") == "gossip_window_time"
    assert sanitize_name("0bad") == "_0bad"


def test_escape_label_value():
    assert escape_label_value('plain') == 'plain'
    assert escape_label_value('a"b') == 'a\\"b'
    assert escape_label_value('a\\b') == 'a\\\\b'
    assert escape_label_value('a\nb') == 'a\\nb'
    # backslash first, so the escapes it INTRODUCES are not re-escaped
    assert escape_label_value('\\"') == '\\\\\\"'


def test_prometheus_label_values_escaped():
    reg = MetricsRegistry()
    reg.counter("req").inc(3, path='say "hi"\n@C:\\tmp')
    reg.ingest("build", {"flags": 'x="1"\\y'})
    text = reg.to_prometheus()
    # every emitted line stays one line: raw newlines never leak into the
    # exposition body
    assert all(line.count('"') % 2 == 0 or "\\" in line
               for line in text.splitlines())
    assert 'req_total{path="say \\"hi\\"\\n@C:\\\\tmp"} 3\n' in text
    assert 'build_flags_info{value="x=\\"1\\"\\\\y"} 1\n' in text
    assert "\nsay" not in text  # the label newline was escaped, not emitted


def test_jsonl_sink_records_events(tmp_path):
    path = str(tmp_path / "events.jsonl")
    sink = JsonlSink(path)
    reg = MetricsRegistry(sink=sink)
    reg.counter("c").inc(2, mc="8")
    reg.gauge("g").set(1.5)
    sink.close()
    lines = [json.loads(l) for l in open(path)]
    assert sink.n_events == len(lines) == 2
    assert lines[0] == {"kind": "counter", "name": "c",
                        "labels": {"mc": "8"}, "value": 2}


# ---------------------------------------------------------------------------
# tracer


def test_disabled_tracer_records_nothing_and_reuses_null_span():
    tr = Tracer(enabled=False)
    s1, s2 = tr.span("a"), tr.span("b", k=1)
    assert s1 is s2  # the shared no-op context: zero allocation per span
    with s1:
        pass
    assert tr.spans == [] and tr.summary() == {}


def test_tracer_nesting_depth_and_order():
    tr = Tracer(enabled=True)
    with tr.span("outer"):
        with tr.span("inner"):
            pass
    # inner closes first; depth is relative to the enclosing span
    assert [(s.name, s.depth) for s in tr.spans] == [("inner", 1), ("outer", 0)]
    assert tr.spans[1].dur_us >= tr.spans[0].dur_us


def test_tracer_summary_splits_compile_from_warm():
    tr = Tracer(enabled=True)
    with tr.span("round", compile=True):
        pass
    for _ in range(3):
        with tr.span("round"):
            pass
    summ = tr.summary()["round"]
    assert summ["compile"]["n"] == 1 and summ["warm"]["n"] == 3
    assert summ["warm"]["p50_us"] <= summ["warm"]["max_us"]


def test_tracer_flush_is_incremental(tmp_path):
    sink = JsonlSink(str(tmp_path / "t.jsonl"))
    tr = Tracer(enabled=True, sink=sink)
    with tr.span("a"):
        pass
    assert tr.flush() == 1
    assert tr.flush() == 0  # already flushed
    with tr.span("b"):
        pass
    assert tr.flush() == 1


class _StubAnnotation:
    """Stands in for ``jax.profiler.TraceAnnotation``: logs enter/exit."""

    log: list = []

    def __init__(self, name, **kw):
        self.name, self.kw = name, kw

    def __enter__(self):
        self.log.append(("enter", self.name, self.kw))

    def __exit__(self, *exc):
        self.log.append(("exit", self.name, self.kw))


@pytest.fixture
def annotations(monkeypatch):
    monkeypatch.setattr(_StubAnnotation, "log", [])
    monkeypatch.setattr(trace_mod, "TraceAnnotation", _StubAnnotation)
    return _StubAnnotation.log


def test_enabled_tracer_spans_are_annotations_with_bare_names(annotations):
    tr = Tracer(enabled=True)
    with tr.span("outer", round=3):
        with tr.span("inner", impl="masked"):
            pass
    # attributes stay in the span record; the annotation name is constant
    assert annotations == [("enter", "outer", {}), ("enter", "inner", {}),
                           ("exit", "inner", {}), ("exit", "outer", {})]
    assert [s.attrs for s in tr.spans] == [{"impl": "masked"}, {"round": 3}]


def test_disabled_tracer_opens_no_annotation(annotations):
    tr = Tracer(enabled=False)
    with tr.span("outer", round=3):
        with tr.span("inner"):
            pass
    assert annotations == [] and tr.spans == []


def test_compile_warm_timer_accumulates():
    t = CompileWarmTimer()
    with t.compile():
        pass
    with t.warm():
        pass
    with t.warm():
        pass
    assert t.compile_us > 0 and t.warm_us > 0
    assert t.warm_us_per(4) == pytest.approx(t.warm_us / 4)
    assert set(t.as_dict()) == {"compile_us", "warm_us"}


# ---------------------------------------------------------------------------
# convergence tracking


def test_network_stats_hand_computed():
    # two agents, one param: means +/-1, both sigmas = 1
    mean = np.array([[1.0], [-1.0]], np.float32)
    rho = np.full((2, 1), float(softplus_inv(1.0)), np.float32)
    got = network_stats(mean, rho)
    assert got["disagreement"] == pytest.approx(1.0, rel=1e-6)
    assert got["rho_disagreement"] == pytest.approx(0.0, abs=1e-7)
    # KL(q_i || q_bar): var ratio 1 -> 0.5 * dev^2 / var_bar = 0.5 each
    assert got["kl_to_mean"] == pytest.approx(0.5, rel=1e-5)
    # mean-only posterior: disagreement only
    assert set(network_stats(mean)) == {"disagreement"}


def test_tracker_measures_synthetic_decay_rate():
    class _P:
        def __init__(self, d):
            self.mean = np.array([[d], [-d]], np.float32)

    tracker = ConvergenceTracker(K=0.7)
    for r in range(8):
        tracker.update(_P(math.exp(-0.7 * r)), r)
    rep = tracker.report()
    assert rep["measured_rate"] == pytest.approx(0.7, rel=1e-2)
    assert rep["rate_attainment"] == pytest.approx(1.0, rel=1e-2)
    # overlay is anchored at the first measured point
    first = rep["overlay"][0]
    assert first["predicted"] == pytest.approx(first["measured"])
    assert len(rep["overlay"]) == rep["n_rounds"] == 8


def test_tracker_explicit_K_wins_over_W():
    W = np.full((3, 3), 1.0 / 3.0)
    assert ConvergenceTracker(W=W, K=2.0).theory_rate == 2.0
    assert ConvergenceTracker().theory_rate is None
    assert ConvergenceTracker().measured_rate() is None  # no points


def test_tracker_series_columns():
    tracker = ConvergenceTracker()
    tracker.update(np.zeros((2, 3), np.float32))
    cols = tracker.series()
    assert cols["round"] == [0]
    assert cols["disagreement"] == [0.0]


# ---------------------------------------------------------------------------
# ObsSpec: validation, doc round trip


def _tiny_spec(obs=None, n_rounds=3):
    from repro.api import (
        DataSpec, ExperimentSpec, InferenceSpec, ObsSpec, RunSpec,
        TopologySpec,
    )

    kw = {} if obs is None else {"obs": obs}
    return ExperimentSpec(
        topology=TopologySpec(kind="bidirectional_ring", params={"n": 4}),
        data=DataSpec(
            dataset_params=dict(n_classes=3, dim=8, n_train_per_class=20),
            partition="iid", partition_params=dict(n_agents=4),
            batch_size=4, local_updates=1,
        ),
        inference=InferenceSpec(hidden=4, depth=1, lr=1e-2),
        run=RunSpec(n_rounds=n_rounds, seed=0),
        **kw,
    )


def test_obs_spec_validation():
    from repro.api import ObsSpec

    ObsSpec().validate()
    with pytest.raises(ValueError, match="convergence_every"):
        ObsSpec(convergence_every=0).validate()
    with pytest.raises(ValueError, match="jsonl_path"):
        ObsSpec(jsonl_path=7).validate()


def test_obs_spec_doc_round_trip(tmp_path):
    from repro.api import ExperimentSpec, ObsSpec

    spec = _tiny_spec(obs=ObsSpec(enabled=True, convergence_every=2,
                                  jsonl_path=str(tmp_path / "t.jsonl")))
    back = ExperimentSpec.from_doc(spec.to_doc())
    assert back.obs == spec.obs
    # docs written before the obs field existed still load (default ObsSpec)
    doc = spec.to_doc()
    doc.pop("obs")
    assert ExperimentSpec.from_doc(doc).obs == ObsSpec()


# ---------------------------------------------------------------------------
# session integration: zero perturbation, namespacing, checkpoint, dashboard


def test_obs_enabled_is_bitwise_identical():
    from repro.api import ObsSpec, build_session

    posts = {}
    for enabled in (False, True):
        obs = ObsSpec(enabled=True) if enabled else None
        s = build_session(_tiny_spec(obs=obs))
        s.run()
        posts[enabled] = s.posterior()
    np.testing.assert_array_equal(
        np.asarray(posts[False].mean), np.asarray(posts[True].mean)
    )
    np.testing.assert_array_equal(
        np.asarray(posts[False].rho), np.asarray(posts[True].rho)
    )


def test_obs_disabled_session_records_nothing():
    from repro.api import build_session

    s = build_session(_tiny_spec())
    s.run()
    assert s.obs is None
    assert "observability disabled" in s.dashboard()


def test_obs_session_counters_convergence_and_dashboard():
    from repro.api import ObsSpec, build_session

    s = build_session(_tiny_spec(obs=ObsSpec(enabled=True)))
    s.run()
    reg = s.obs.registry
    assert reg.counter("session.rounds").value() == 3
    # static named topology -> spectral theory rate on the tracker
    rep = s.obs.convergence.report()
    assert rep["n_rounds"] == 3
    assert rep["theory_rate"] is not None and rep["theory_rate"] > 0
    names = {sp.name for sp in s.obs.tracer.spans}
    assert {"session.run", "session.round"} <= names
    # first round is compile-attributed, the rest warm
    summ = s.obs.tracer.summary()["session.round"]
    assert summ["compile"]["n"] == 1 and summ["warm"]["n"] == 2
    dash = s.dashboard()
    assert "convergence:" in dash and "span session.round" in dash


def test_obs_gossip_engine_counters_and_spans():
    from repro.api import (
        DataSpec, ExperimentSpec, InferenceSpec, ObsSpec, RunSpec,
        TopologySpec, build_session,
    )

    spec = ExperimentSpec(
        topology=TopologySpec.gossip(
            "bidirectional_ring", {"n": 4},
            clock={"kind": "poisson", "rate": 0.8, "seed": 0},
        ),
        data=DataSpec(
            dataset_params=dict(n_classes=3, dim=8, n_train_per_class=20),
            partition="iid", partition_params=dict(n_agents=4),
            batch_size=4, local_updates=1,
        ),
        inference=InferenceSpec(hidden=4, depth=1, lr=1e-2),
        run=RunSpec(n_rounds=3, seed=0),
        obs=ObsSpec(enabled=True),
    )
    s = build_session(spec)
    s.run()
    reg = s.obs.registry
    assert reg.counter("gossip.windows").value() == 3
    assert reg.gauge("gossip.jit_traces").value() == s.engine.n_traces == 1
    names = {sp.name for sp in s.obs.tracer.spans}
    assert "gossip.window" in names


@pytest.mark.parametrize("path", ["segment_sum", "row_gather"])
def test_obs_segments_consensus_path_counter(path, monkeypatch):
    """``gossip.consensus_path`` counts the execution each edge-native
    window's consensus ran: the XLA segment sum on the CPU, the row gather
    where the dispatch picks it (steered here to the Pallas interpreter),
    whose ``gossip.gather_slot_fill`` is the tables' share of real
    entries."""
    import dataclasses

    from repro.api import ObsSpec, RunSpec, build_session
    from repro.gossip import engine as engine_mod

    if path == "row_gather":
        monkeypatch.setattr(engine_mod, "segments_mode",
                            lambda n, slots, wire=None: "interpret")
    spec = dataclasses.replace(_program_spec("segments"),
                               run=RunSpec(n_rounds=3, seed=0),
                               obs=ObsSpec(enabled=True))
    s = build_session(spec)
    s.run()
    reg = s.obs.registry
    counts = reg.counter("gossip.consensus_path")
    other = "segment_sum" if path == "row_gather" else "row_gather"
    assert counts.value(path=path) == reg.counter("gossip.windows").value()
    assert counts.value(path=path) == 3
    assert counts.value(path=other) == 0
    fill = reg.collect().get("gossip.gather_slot_fill")
    if path == "row_gather":
        assert 0.0 < fill <= 1.0
    else:
        assert fill is None


def test_round_spans_nest_inside_session_round():
    """The host's round: schedule, batches and the wait for the losses
    (``session.sync``) are spans inside ``session.round``."""
    from repro.api import ObsSpec, build_session

    s = build_session(_tiny_spec(obs=ObsSpec(enabled=True)))
    s.round()  # the benchmark's path: the spec's schedule, built per round
    s.round()
    spans = s.obs.tracer.spans
    rounds = [sp for sp in spans if sp.name == "session.round"]
    assert len(rounds) == 2
    for name in ("session.w_build", "session.batches", "session.sync"):
        inner = [sp for sp in spans if sp.name == name]
        assert len(inner) == 2, name
        for sp, rd in zip(inner, rounds):
            assert sp.depth == rd.depth + 1
            assert rd.t0_us <= sp.t0_us
            assert sp.t0_us + sp.dur_us <= rd.t0_us + rd.dur_us
            assert sp.attrs["round"] == rd.attrs["round"]


_FAULTS = {"crash_rate": 0.3, "recover_rate": 0.5, "corrupt_rate": 0.3,
           "seed": 0}
_CLOCK = {"kind": "poisson", "rate": 0.8, "seed": 0}


def _program_spec(kind):
    from repro.api import (
        DataSpec, ExperimentSpec, InferenceSpec, RunSpec, TopologySpec,
    )

    inference = {}
    if kind == "sync":
        topo = TopologySpec(kind="bidirectional_ring", params={"n": 4})
    elif kind == "masked":
        topo = TopologySpec.gossip("bidirectional_ring", {"n": 4}, _CLOCK)
    elif kind == "segments":
        topo = TopologySpec.sparse("ring", clock=_CLOCK, n=4)
    else:
        topo = TopologySpec.gossip("bidirectional_ring", {"n": 4},
                                   {**_CLOCK, "faults": _FAULTS})
        inference = {"fault_policy": "quarantine"}
    return ExperimentSpec(
        topology=topo,
        data=DataSpec(
            dataset_params=dict(n_classes=3, dim=8, n_train_per_class=20),
            partition="iid", partition_params=dict(n_agents=4),
            batch_size=4, local_updates=2,
        ),
        inference=InferenceSpec(hidden=4, depth=1, lr=1e-2, **inference),
        run=RunSpec(n_rounds=1, seed=0),
    )


def _round_program_op_names(spec) -> list:
    """The op_name metadata of the compiled program one round runs."""
    from repro.api import build_session

    s = build_session(spec)
    attr = "_round" if hasattr(s.engine, "_round") else "_window"
    program, calls = getattr(s.engine, attr), []

    def record(*args):
        calls.append(args)
        return program(*args)

    setattr(s.engine, attr, record)
    s.round()
    text = program.lower(*calls[0]).compile().as_text()
    return re.findall(r'op_name="([^"]*)"', text)


def _has_scope(op_name: str, scope: str) -> bool:
    # a scope is a whole path component, possibly wrapped: vmap(nll)
    return re.search(rf"(^|[/(]){scope}($|[/)])", op_name) is not None


@pytest.mark.parametrize("kind", ["sync", "masked", "segments",
                                  "quarantine"])
def test_round_program_carries_layer_scopes(kind):
    names = _round_program_op_names(_program_spec(kind))
    expected = ["local_phase", "optimizer", "sample", "nll", "kl",
                "consensus"]
    if kind == "quarantine":
        expected += ["fault_guard", "agent_select"]
    for scope in expected:
        assert any(_has_scope(n, scope) for n in names), scope
    # the optimizer and the MC sample are parts of the local phase
    for scope in ("optimizer", "sample"):
        assert all(_has_scope(n, "local_phase") for n in names
                   if _has_scope(n, scope)), scope


def test_evaluate_namespaces_engine_telemetry():
    """Regression: engine telemetry used to be update()-splatted into the
    metrics dict, so a telemetry key named like a metric clobbered it."""
    from repro.api import build_session

    s = build_session(_tiny_spec())
    s.run()
    s.engine.telemetry = lambda state: {"acc": "CLOBBER", "avg_acc": -1.0}
    out = s.evaluate(n_mc=1)
    assert isinstance(out["acc"], list) and out["avg_acc"] >= 0.0
    assert out["engine"] == {"acc": "CLOBBER", "avg_acc": -1.0}


def test_obs_checkpoint_round_trip(tmp_path):
    from repro.api import ObsSpec, Session, build_session

    path = str(tmp_path / "obs.ckpt")
    s = build_session(_tiny_spec(obs=ObsSpec(enabled=True)))
    plain = build_session(_tiny_spec())
    # observability adds NO state leaves: identical checkpoint structure
    assert (jax.tree.structure(s.state) == jax.tree.structure(plain.state))
    s.run()
    s.save(path)
    back = Session.load(path)
    assert back.spec.obs.enabled and back.obs is not None
    np.testing.assert_array_equal(
        np.asarray(back.posterior().mean), np.asarray(s.posterior().mean)
    )
    assert back.round_idx == s.round_idx


def test_obs_jsonl_path_writes_trace(tmp_path):
    from repro.api import ObsSpec, build_session

    path = str(tmp_path / "trace.jsonl")
    s = build_session(_tiny_spec(obs=ObsSpec(enabled=True,
                                             jsonl_path=path)))
    s.run()
    s.dashboard()  # flushes
    events = [json.loads(l) for l in open(path)]
    kinds = {e["kind"] for e in events}
    assert "span" in kinds and ("counter" in kinds or "gauge" in kinds)
