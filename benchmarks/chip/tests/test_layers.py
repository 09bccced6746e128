"""Device time by named scope and device idle time by program span, on
hand-made traces and on traces recorded on a TPU v5e; the scope readers'
results with and without what they read."""
from pathlib import Path

import pytest

import tiny  # noqa: F401  (puts the benchmark on the path)
from chipbench import cells, layers
from chipbench import trace as tr

DATA = Path(__file__).parent / "data"
SMALL = DATA / "tpu_v5e_small.xplane.pb"
SCOPED = DATA / "tpu_v5e_scoped.xplane.pb"
PROGRAM_SPANS = {"session.round", "session.w_build", "session.batches",
                 "session.sync", "gossip.window_build", "gossip.window"}
SCOPE_METRICS = ("local_phase_ms.train", "optimizer_ms.train",
                 "consensus_ms.train", "host_idle_ms.train")
Op, E = layers.Op, tr.Event


def test_scope_components_peel_wrappers():
    assert layers.scope_components(
        "jit(f)/vmap(local_phase)/transpose(jvp(vmap(nll)))/mul:") == [
            "jit(f)", "local_phase", "nll", "mul"]
    assert layers.scope_components("jit(f)/local_phase/jvp()/tanh") == [
        "jit(f)", "local_phase", "", "tanh"]
    # a scope is a whole component: names that merely contain one are not
    assert layers.layer_of("jit(f)/local_phases/consensus_x/add:") is None
    assert layers.layer_of("jit(f)/jit(consensus_fused_network)/"
                           "pallas_call:") is None
    # the outermost layer wins: the validity probe is consensus work
    assert layers.layer_of(
        "jit(w)/consensus/fault_guard/jit(payload_validity_fused)/"
        "pallas_call:") == "consensus"
    assert layers.local_part_of(
        "jit(w)/local_phase/vmap()/while/body/closed_call/"
        "transpose(jvp(sample))/mul:") == "sample"


def hand_trace():
    ops = [Op("while.1", 0, 100, "jit(w)/local_phase/vmap()/while:"),
           Op("fusion.1", 10, 40,
              "jit(w)/local_phase/vmap()/while/body/closed_call/"
              "optimizer/mul:"),
           Op("fusion.2", 40, 60,
              "jit(w)/vmap(local_phase)/while/body/transpose(jvp(nll))/"
              "dot_general:"),
           Op("consensus_fused_masked.1", 110, 130,
              "jit(w)/consensus/jit(consensus_fused_masked)/pallas_call:"),
           Op("payload_validity_fused.1", 130, 135,
              "jit(w)/consensus/fault_guard/jit(payload_validity_fused)/"
              "pallas_call:"),
           Op("select.3", 135, 140, "jit(w)/agent_select/select_n:"),
           Op("copy.7", 150, 160, "jit(w)/copy:"),
           Op("fusion.9", 250, 260, "jit(w)/local_phase/add:")]  # clipped
    host = [E(tr.WINDOW_ANNOTATION, 0, 200)]
    return tr.Trace(devices=[ops], host=host)


def test_device_time_by_layer():
    lay = layers.reduce(hand_trace(), PROGRAM_SPANS)
    ns = pytest.approx
    assert lay.layer_s == {"local_phase": ns(100e-9),
                           "consensus": ns(25e-9),
                           "agent_select": ns(5e-9)}
    # the while's self time (100 - 30 - 20) stays in the local phase
    assert lay.part_s == {"optimizer": ns(30e-9), "nll": ns(20e-9)}
    assert lay.unscoped_s == {"copy.7": ns(10e-9)}
    assert lay.busy_s == ns(140e-9)
    assert lay.coverage_pct == ns(100 * 130 / 140)
    # no program span in the trace: no idle attribution inside a round
    assert lay.round_idle_s is None
    assert lay.idle_by_span == {None: ns(60e-9)}


def idle_trace():
    """Device busy [0, 20], [50, 60], [90, 100]; the window [0, 130].
    Round 1 [5, 70] holds w_build [10, 30] and sync [55, 70]; round 2
    [75, 125] holds batches [76, 80] and sync [100, 125]; bench.round
    (not a program span) encloses each round."""
    ops = [Op("fusion.1", 0, 20, "jit(w)/local_phase/add:"),
           Op("fusion.2", 50, 60, "jit(w)/local_phase/add:"),
           Op("fusion.3", 90, 100, "jit(w)/consensus/add:")]
    host = [E(tr.WINDOW_ANNOTATION, 0, 130),
            E("bench.round", 4, 72), E("bench.round", 74, 128),
            E("session.round", 5, 70), E("session.w_build", 10, 30),
            E("session.sync", 55, 70),
            E("session.round", 75, 125), E("session.batches", 76, 80),
            E("session.sync", 100, 125),
            E("np.asarray(jax.Array)", 101, 124)]  # runtime, not a span
    return tr.Trace(devices=[ops], host=host)


def test_idle_inside_rounds_by_innermost_span():
    lay = layers.reduce(idle_trace(), PROGRAM_SPANS)
    ns = pytest.approx
    # idle [20, 50]: w_build [20, 30], round [30, 50];
    # [60, 90]: sync [60, 70], none [70, 75], round [75, 76],
    # batches [76, 80], round [80, 90]; [100, 130]: sync [100, 125]
    # (the runtime event inside it does not count), none [125, 130]
    assert lay.idle_by_span == {
        "session.w_build": ns(10e-9), "session.round": ns(31e-9),
        "session.sync": ns(35e-9), "session.batches": ns(4e-9),
        None: ns(10e-9)}
    assert lay.round_idle_s == ns(80e-9)
    total_idle = lay.window_s - lay.busy_s
    assert sum(lay.idle_by_span.values()) == ns(total_idle)


def _context(lay, rounds=2):
    return {"layers": lay, "window": {"rounds": rounds}}


def test_scope_readers():
    ctx = _context(layers.reduce(idle_trace(), PROGRAM_SPANS))
    got = {m: cells.metric_reader(m).reduce(ctx) for m in SCOPE_METRICS}
    assert got == {"local_phase_ms.train": pytest.approx(15e-6),
                   "optimizer_ms.train": None,
                   "consensus_ms.train": pytest.approx(5e-6),
                   "host_idle_ms.train": pytest.approx(40e-6)}


def test_scope_readers_find_nothing_without_scopes_or_spans():
    """A program without named scopes or span annotations (the parent of
    this reading), or a run that kept no layers: no value, no error."""
    bare = tr.Trace(devices=[[Op("fusion.1", 0, 10, "jit(w)/add:")]],
                    host=[E(tr.WINDOW_ANNOTATION, 0, 20),
                          E("bench.round", 0, 20)])
    for ctx in (_context(layers.reduce(bare, PROGRAM_SPANS)),
                {"window": {"rounds": 1}}):
        for m in SCOPE_METRICS:
            assert cells.metric_reader(m).reduce(ctx) is None, m


def test_decoder_reads_the_committed_trace():
    """The decoder and ``jax.profiler.ProfileData`` agree on every event of
    the trace recorded for ``test_trace.py``; its one custom call is the
    network consensus kernel, under that kernel's jit."""
    mine = layers.read(SMALL)
    ref = tr.read_trace(SMALL)
    def events(evs):
        return sorted((e.name, e.start, e.end) for e in evs)

    for got, want in zip([events(ops) for ops in mine.devices] +
                         [events(mine.host)],
                         [events(ops) for ops in ref.devices] +
                         [events(ref.host)]):
        assert [n for n, _, _ in got] == [n for n, _, _ in want]
        assert [t for e in got for t in e[1:]] == pytest.approx(
            [t for e in want for t in e[1:]])
    kernels = [e for e in mine.devices[0]
               if e.name.startswith("consensus_fused_network")]
    assert kernels and {e.scope for e in kernels} == {
        "jit(<lambda>)/jit(consensus_fused_network)/pallas_call:"}
    a, b = tr.reduce(mine), tr.reduce(ref)
    assert a.busy_s == pytest.approx(b.busy_s)
    assert a.op_self_s == pytest.approx(b.op_self_s)


def test_recorded_scoped_window():
    """One window of the quarantine cell cut to 16 agents, recorded on one
    TPU v5e (``record_scoped.py``): its operations land under the program's
    scopes, and its host annotations name the idle time in the round."""
    trace = layers.read(SCOPED)
    ops = trace.devices[0]
    by_kernel = {e.name.split(".")[0]: layers.layer_of(e.scope) for e in ops
                 if e.name.startswith(("consensus_fused", "payload_validity"))}
    assert by_kernel == {"consensus_fused_masked": "consensus",
                         "payload_validity_fused": "consensus"}
    spans = {e.name for e in trace.host} & PROGRAM_SPANS
    assert spans == PROGRAM_SPANS
    lay = layers.reduce(trace, PROGRAM_SPANS)
    assert set(lay.layer_s) == {"local_phase", "consensus", "agent_select",
                                "fault_guard"}
    assert set(lay.part_s) == {"optimizer", "sample", "nll", "kl"}
    assert lay.coverage_pct >= 90.0
    assert 0 < lay.round_idle_s <= lay.window_s - lay.busy_s + 1e-12
    assert set(lay.idle_by_span) - {None} <= PROGRAM_SPANS
