"""Record ``data/tpu_v5e_scoped.xplane.pb``: one window of a cut-down
quarantine cell (16 agents on a 4x4 torus, the published widths) under the
profiler with the benchmark's options, on one TPU chip.

    python benchmarks/chip/tests/record_scoped.py [<output path>]

The window holds every layer scope of the program (local phase and its
parts, the agent selects, the corrupt-payload fill, the masked consensus
kernel with the validity probe) and the program's host spans.  The
``/host:metadata`` plane (the programs' HLO, most of the file) is left out:
nothing here reads it.
"""
from __future__ import annotations

import copy
import dataclasses
import os
import shutil
import sys
import tempfile
from pathlib import Path

import tiny
from chipbench import cells, xplane
from chipbench.trace import find_xplane

SEED = 2500001301
OUT = Path(__file__).parent / "data" / "tpu_v5e_scoped.xplane.pb"
DROP_PLANE = "/host:metadata"


def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def without_metadata_plane(buf: bytes) -> bytes:
    """The XSpace ``buf`` less its ``/host:metadata`` plane; every other
    field (all of an XSpace's are length-delimited) keeps its bytes."""
    out = bytearray()
    for num, val in xplane.fields(buf):
        if num == 1 and xplane.plane_name(val) == DROP_PLANE:
            continue
        out += _varint(num << 3 | 2) + _varint(len(val)) + val
    return bytes(out)


def scoped_cell() -> cells.Cell:
    cell = cells.load_cell("paper_mlp.torus256.gossip_quarantine")
    cfg = copy.deepcopy(cell.config)
    cfg["data"]["dataset_params"].update(tiny.SMALL_DATA)
    cfg["topology"]["params"].update(rows=4, cols=4)
    cfg["n_agents"] = 16
    return dataclasses.replace(cell, config=cfg)


def main(out: Path) -> None:
    from run import require_chips

    require_chips(1)
    cell = scoped_cell()
    driver = cells.driver_module(cell.traffic["driver"])
    drv = driver.Driver(cell.config, cell.traffic, SEED, obs=True)
    drv.setup(0.0)
    trace_dir = Path(tempfile.mkdtemp(prefix="scoped_"))
    drv.window(0.0, trace_dir)  # a window of one round
    out.write_bytes(without_metadata_plane(find_xplane(trace_dir)
                                          .read_bytes()))
    shutil.rmtree(trace_dir, ignore_errors=True)
    print(f"{out}: {out.stat().st_size} bytes")


if __name__ == "__main__":
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    main(Path(sys.argv[1]) if len(sys.argv) > 1 else OUT)
