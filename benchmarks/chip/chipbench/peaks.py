"""The chip's published peaks, keyed by ``device_kind`` (``peaks.json``).

A device that is not in the table is an error, not a default: a share of
a peak that was taken against another chip's numbers means nothing.
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parents[1] / "peaks.json"


class UnknownDevice(KeyError):
    """The peak table has no entry for this ``device_kind``."""


def peaks_for(device_kind: str, path: Path = PEAKS_FILE) -> dict:
    table = json.loads(Path(path).read_text())["devices"]
    if device_kind not in table:
        raise UnknownDevice(
            f"no peaks for device kind {device_kind!r} in {path.name}; "
            f"known: {sorted(table)}")
    return table[device_kind]
