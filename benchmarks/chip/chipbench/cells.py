"""Find a cell's files by the names in ``BENCHMARK.json``.

A cell (``workloads`` entry) names a configuration and a traffic mix; each
lives in a file of its own, found by name:

* ``configs/<config>.json``         the configuration's sizes and source;
* ``configs/<reference>.py``        its plain reference (the config names it);
* ``traffic/<traffic>.json``        the traffic mix's parameters, which
                                    name its driver;
* ``drivers/<driver>.py``           set-up, window and correctness check
                                    of one kind of traffic;
* ``limits/<workload>.json``        the limits of the correctness comparison;
* ``layer_metrics/<metric>.py``     one reader per per-layer metric.

Adding a cell, a configuration, a mix or a metric adds files and entries;
no file here changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path

CHIP_DIR = Path(__file__).resolve().parents[1]
REPO_ROOT = CHIP_DIR.parents[1]


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: tuple  # BENCHMARK.json metric entries this cell reports
    per_layer: tuple


def load_benchmark(root: Path = REPO_ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def _reports(metric: dict, workload: str, e2e_names: set | None = None) -> bool:
    if "workloads" in metric:
        return workload in metric["workloads"]
    if e2e_names is not None:  # per-layer metric without a list: every cell
        return metric["moves"] in e2e_names  # that reports what it moves
    return True


def load_json(path: Path) -> dict:
    return json.loads(Path(path).read_text())


def load_cell(name: str, bench: dict | None = None,
              chip_dir: Path = CHIP_DIR) -> Cell:
    bench = load_benchmark() if bench is None else bench
    by_name = {w["name"]: w for w in bench["workloads"]}
    if name not in by_name:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                       f"{sorted(by_name)}")
    return cell_from_entry(by_name[name], bench, chip_dir)


def cell_from_entry(w: dict, bench: dict, chip_dir: Path = CHIP_DIR) -> Cell:
    """The cell a ``workloads`` entry names, with its files."""
    name = w["name"]
    files = {c["name"]: c["file"] for c in bench["configs"]}
    config = load_json(chip_dir.parents[1] / files[w["config"]])
    traffic = load_json(chip_dir / "traffic" / f"{w['traffic']}.json")
    limits = load_json(chip_dir / "limits" / f"{name}.json")
    e2e = tuple(m for m in bench["end_to_end"] if _reports(m, name))
    e2e_names = {m["name"] for m in e2e}
    layer = tuple(m for m in bench["per_layer"]
                  if _reports(m, name, e2e_names))
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, limits=limits, end_to_end=e2e,
                per_layer=layer)


def load_module(path: Path, name: str):
    """Import a file by path (metric readers and references have dots in
    their names, so they are not importable as packages)."""
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise ImportError(f"cannot load {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(metric_name: str, chip_dir: Path = CHIP_DIR):
    return load_module(chip_dir / "layer_metrics" / f"{metric_name}.py",
                       f"layer_metric_{metric_name.replace('.', '_')}")


def driver_module(driver: str, chip_dir: Path = CHIP_DIR):
    """The module that drives one kind of traffic (its ``Driver`` class
    and the ``control`` readings of ``control.py``)."""
    return load_module(chip_dir / "drivers" / f"{driver}.py",
                       f"driver_{driver.replace('.', '_')}")


def reference_module(config: dict, chip_dir: Path = CHIP_DIR):
    ref = config["reference"]
    return load_module(chip_dir / "configs" / f"{ref}.py",
                       f"reference_{ref.replace('.', '_')}")
