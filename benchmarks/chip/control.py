"""Readings that the correctness limits are set from, at a cell's own size.

    python benchmarks/chip/control.py --workload <cell> --seeds 1 2 3 ... \
        [--only program|control|faults]

For each seed, one JSON line per reading of the numbers that decide
``correct`` (``chipbench.correct``), as the cell's driver
(``drivers/<driver>.py``, its ``control``) takes them:

* ``program``: the program as the configuration states it, against the
  float32 reference (the lower readings);
* ``control``: the reference one precision step down, in the program's
  place (the upper readings);
* ``fault:<name>``: the reference with one of the faults the cell can have
  planted.

The benchmark's own runs never run this; it needs the chips the cell asks
for, and runs every reading in this one process.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

CHIP_DIR = Path(__file__).resolve().parent
sys.path.insert(0, str(CHIP_DIR))
sys.path.insert(0, str(CHIP_DIR.parents[1] / "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--only", choices=("program", "control", "faults"))
    args = ap.parse_args(argv)

    import run
    from chipbench import cells

    cell = cells.load_cell(args.workload)
    try:
        run.require_chips(cell.chips)
    except run.NoChip as e:
        print(f"control.py: {e}", file=sys.stderr)
        return 2
    run.enable_cache()
    driver = cells.driver_module(cell.traffic["driver"])
    t0 = time.perf_counter()

    def say(kind, seed, numbers):
        print(json.dumps({"workload": args.workload, "kind": kind,
                          "seed": seed, **numbers,
                          "t_s": round(time.perf_counter() - t0, 1)}),
              flush=True)

    for seed in args.seeds:
        driver.control(cell, seed, args.only, say)
    return 0


if __name__ == "__main__":
    sys.exit(main())
