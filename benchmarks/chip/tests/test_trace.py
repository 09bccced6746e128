"""Trace reduction: busy and idle time, self times, kernel time and idle
gaps named by the host, on a hand-made trace and on one recorded on a
TPU v5e."""
from pathlib import Path

import pytest

import tiny  # noqa: F401  (puts the benchmark on the path)
from chipbench import trace as tr

E = tr.Event
RECORDED = Path(__file__).parent / "data" / "tpu_v5e_small.xplane.pb"


def hand_trace():
    ops = [E("while.4", 0, 100), E("fusion.1", 10, 40),
           E("consensus_fused_network.1", 50, 70), E("fusion.2", 150, 180),
           E("fusion.3", 250, 260)]  # outside the window: clipped away
    host = [E(tr.WINDOW_ANNOTATION, 0, 200), E("D2H Dispatch", 100, 140),
            E("bench.round", 95, 200), E("bench.wait", 180, 200)]
    return tr.Trace(devices=[ops], host=host)


def test_busy_idle_and_self_times():
    red = tr.reduce(hand_trace())
    assert red.window_s == pytest.approx(200e-9)
    assert red.busy_s == pytest.approx(130e-9)  # [0, 100] and [150, 180]
    assert red.idle_pct == pytest.approx(35.0)
    assert red.op_self_s["while.4"] == pytest.approx(50e-9)  # 100 - 30 - 20
    assert red.op_self_s["fusion.1"] == pytest.approx(30e-9)
    assert "fusion.3" not in red.op_self_s
    assert red.kernel_s(("consensus_fused",)) == pytest.approx(20e-9)
    assert red.kernel_s(("payload_validity",)) is None


def test_idle_gaps_named_by_host():
    red = tr.reduce(hand_trace())
    # [100, 150]: D2H Dispatch and bench.round both cover half of it or
    # more; the shorter names it.  [180, 200]: bench.wait.
    assert red.idle_gaps == {"D2H Dispatch": pytest.approx(50e-9),
                             "bench.wait": pytest.approx(20e-9)}
    bd = red.breakdown(top=2)
    assert [n for n, _ in bd["device_ops"]] == ["while.4", "fusion.1"]


def test_op_names():
    assert tr.op_name("%fusion.154 = (f32[2]) fusion(f32[2] %x)") == "fusion.154"
    assert tr.op_name("%consensus_fused_network.1 = (f32[256,199808]) "
                      "custom-call(...)") == "consensus_fused_network.1"


def test_union_of_overlapping_events():
    assert tr.union([E("a", 0, 10), E("b", 5, 20), E("c", 30, 40)]) == [
        (0, 20), (30, 40)]


def test_recorded_tpu_trace():
    """A window of three consensus calls and a small matmul, recorded with
    the benchmark's profiler options on one TPU v5e chip."""
    red = tr.reduce(tr.read_trace(RECORDED))
    assert 0 < red.busy_s < red.window_s
    assert 0 < red.idle_pct < 100
    assert red.kernel_s(("consensus_fused_network",)) > 0
    total = sum(red.op_self_s.values())
    assert total <= red.busy_s * len(tr.read_trace(RECORDED).devices) * 1.0001
