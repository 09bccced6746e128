"""Per-layer device and host time of one cell, from one traced window.

    python benchmarks/chip/layer_report.py --workload <cell> --seed <n> \
        [--seconds 4] [--keep <dir>]

From the root of a checkout, on the chips the cell asks for.  Runs the
cell's set-up and a traced window as ``run.py --trace 1`` does, then reads
the trace twice: with ``chipbench.trace`` (the cell's per-layer metrics of
``BENCHMARK.json`` and the breakdown) and with ``chipbench.layers`` (device
time by the program's named scopes, device idle time by the program's host
spans), and prints one JSON line.  The scope metrics are the readers
``layer_metrics/{local_phase,optimizer,consensus,host_idle}_ms.train.py``;
``run.py`` deletes its trace before its readers run, so they are read here.
``--keep`` copies the trace into a directory.  No correctness check.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
from pathlib import Path

CHIP_DIR = Path(__file__).resolve().parent
REPO_ROOT = CHIP_DIR.parents[1]
SCOPE_METRICS = ("local_phase_ms.train", "optimizer_ms.train",
                 "consensus_ms.train", "host_idle_ms.train")


def report(name: str, seed: int, seconds: float, keep: Path | None) -> dict:
    from chipbench import cells, layers, peaks
    from chipbench import trace as trace_mod
    from run import _scratch, enable_cache, require_chips

    cell = cells.load_cell(name)
    devs = require_chips(cell.chips)
    enable_cache()
    driver = cells.driver_module(cell.traffic["driver"])
    drv = driver.Driver(cell.config, cell.traffic, seed, obs=True)
    drv.setup(seconds)
    trace_dir = Path(tempfile.mkdtemp(prefix="trace_", dir=_scratch()))
    win = drv.window(seconds, trace_dir)
    context = {"window": win, "cfg": cell.config, "traffic": cell.traffic,
               "chips": cell.chips, **drv.layer_context()}
    drv.free()
    xp = trace_mod.find_xplane(trace_dir)
    red = trace_mod.reduce(trace_mod.read_trace(xp))
    lay = layers.reduce(layers.read(xp), {s.name for s in context["spans"]})
    if keep is not None:
        keep.mkdir(parents=True, exist_ok=True)
        shutil.copy(xp, keep / f"{name}.{seed}.xplane.pb")
    shutil.rmtree(trace_dir, ignore_errors=True)
    context.update(reduced=red, layers=lay,
                   peaks=peaks.peaks_for(devs[0].device_kind))
    metrics = {}
    for m in [m["name"] for m in cell.per_layer] + list(SCOPE_METRICS):
        metrics[m] = cells.metric_reader(m).reduce(context)
    dev = devs[0]
    return {"workload": name, "seed": seed,
            "window": {k: win[k] for k in ("rounds", "elapsed_s",
                                           "samples_per_s")},
            "device": {"platform": dev.platform, "kind": dev.device_kind,
                       "count": len(devs), "busy_s": red.busy_s,
                       "window_s": red.window_s},
            "metrics": metrics, "breakdown": red.breakdown(),
            "layers": lay.summary(win["rounds"])}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=4.0)
    ap.add_argument("--keep", type=Path, default=None)
    args = ap.parse_args(argv)
    for p in (str(CHIP_DIR), str(REPO_ROOT / "src")):
        sys.path.insert(0, p)
    print(json.dumps(report(args.workload, args.seed, args.seconds,
                            args.keep)), flush=True)
    return 0


if __name__ == "__main__":
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.exit(main())
