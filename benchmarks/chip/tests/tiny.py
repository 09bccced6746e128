"""Cut-down copies of the benchmark's cells for the CPU tests: the
published widths (784-200-200-10), a handful of agents and a small data
set, so that a whole run takes seconds without a chip."""
from __future__ import annotations

import copy
import dataclasses
import sys
from pathlib import Path

CHIP_DIR = Path(__file__).resolve().parents[1]
REPO_ROOT = CHIP_DIR.parents[1]
for p in (str(CHIP_DIR), str(REPO_ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from chipbench import cells  # noqa: E402

SMALL_DATA = {"n_train_per_class": 40, "n_test_per_class": 20}



def tiny_cell(name: str) -> cells.Cell:
    cell = cells.load_cell(name)
    cfg = copy.deepcopy(cell.config)
    cfg["data"]["dataset_params"].update(SMALL_DATA)
    params = cfg["topology"]["params"]
    if cfg["topology"]["graph"] == "torus":
        params.update(rows=2, cols=2)
        cfg["n_agents"] = 4
    else:
        params.update(n=8, k=4)
        cfg["n_agents"] = 8
    return dataclasses.replace(cell, config=cfg)
