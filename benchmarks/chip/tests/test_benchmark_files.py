"""Every name in BENCHMARK.json resolves to its files, and the entries keep
the contract's shape."""
import json
import re

import pytest

import tiny
from chipbench import cells

BENCH = cells.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
WORKLOADS = [w["name"] for w in BENCH["workloads"]]


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["benchmarks/chip"]
    assert BENCH["command"][1] == "benchmarks/chip/run.py"
    assert (tiny.REPO_ROOT / BENCH["command"][1]).is_file()
    assert 1 <= BENCH["run_seconds"] <= 51


def test_names_are_well_formed_and_unique():
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in BENCH[group]]
        assert len(names) == len(set(names)), group
        assert all(NAME.match(n) for n in names), names


@pytest.mark.parametrize("cfg", BENCH["configs"], ids=lambda c: c["name"])
def test_config_file(cfg):
    path = tiny.REPO_ROOT / cfg["file"]
    assert path.is_file() and path.suffix == ".json"
    doc = json.loads(path.read_text())
    assert doc["name"] == cfg["name"]
    assert doc["source"] == cfg["source"]
    assert doc["reduced"] == cfg["reduced"]
    assert (tiny.CHIP_DIR / "configs" / f"{doc['reference']}.py").is_file()
    assert any(w["config"] == cfg["name"] for w in BENCH["workloads"])


@pytest.mark.parametrize("name", WORKLOADS)
def test_workload_resolves(name):
    cell = cells.load_cell(name)
    assert cell.chips in (1, 4)
    driver = cells.driver_module(cell.traffic["driver"])
    assert hasattr(driver, "Driver") and hasattr(driver, "control")
    assert cell.limits, "a cell needs the limits of its comparison"
    e2e = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert cell.per_layer
    for m in cell.per_layer:
        assert m["moves"] in e2e, (m["name"], "moves a metric the cell lacks")
        assert hasattr(cells.metric_reader(m["name"]), "reduce")


def test_metric_entries():
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        assert set(m.get("workloads", [])) <= set(WORKLOADS)
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    layers = {}
    for m in BENCH["per_layer"]:
        layers.setdefault(m["layer"].split(" ")[0], set()).add(m["layer"])
    assert all(len(v) == 1 for v in layers.values()), layers
