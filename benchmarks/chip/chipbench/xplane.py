"""A dependency-free reader of the profiler's ``.xplane.pb`` files.

``jax.profiler.ProfileData`` gives each event its name and times but not
the stats of its metadata, where a device operation's scope path lives
(``tf_op``, e.g. ``jit(window_fn)/local_phase/vmap()/while/body/...``).
This module decodes the protobuf wire format directly, and only the fields
that the layer readings need:

* ``XSpace.planes`` = 1
* ``XPlane.name`` = 2, ``lines`` = 3, ``event_metadata`` = 4 (a map),
  ``stat_metadata`` = 5 (a map)
* ``XLine.name`` = 2, ``timestamp_ns`` = 3, ``events`` = 4
* ``XEvent.metadata_id`` = 1, ``offset_ps`` = 2, ``duration_ps`` = 3
* ``XEventMetadata.name`` = 2, ``stats`` = 5
* ``XStat.metadata_id`` = 1, ``str_value`` = 5, ``ref_value`` = 7
* ``XStatMetadata.name`` = 2

A map entry is a message with ``key`` = 1 and ``value`` = 2.  A ``ref_value``
names a stat metadata whose name is the string.
"""
from __future__ import annotations

import dataclasses
from pathlib import Path

_VARINT, _I64, _LEN, _I32 = 0, 1, 2, 5


def _varint(buf: bytes, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def fields(buf: bytes):
    """(field number, value) of each field of one message: an int for
    varint and fixed-width fields, bytes for length-delimited ones."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == _VARINT:
            val, i = _varint(buf, i)
        elif wire == _LEN:
            size, i = _varint(buf, i)
            val, i = buf[i:i + size], i + size
        elif wire == _I64:
            val, i = int.from_bytes(buf[i:i + 8], "little"), i + 8
        elif wire == _I32:
            val, i = int.from_bytes(buf[i:i + 4], "little"), i + 4
        else:
            raise ValueError(f"unsupported wire type {wire} (field {num})")
        yield num, val


def _signed(v: int) -> int:
    return v - (1 << 64) if v >= 1 << 63 else v


@dataclasses.dataclass
class Line:
    name: str
    timestamp_ns: int
    events: list  # (metadata id, offset ps, duration ps)


@dataclasses.dataclass
class Plane:
    name: str
    lines: list
    event_names: dict  # event metadata id -> name
    event_stats: dict  # event metadata id -> {stat name: str or ref name}


def _map_entries(buf: bytes):
    key = val = None
    for num, v in fields(buf):
        if num == 1:
            key = _signed(v)
        elif num == 2:
            val = v
    return key, val


def _line(buf: bytes) -> Line:
    name, ts, events = "", 0, []
    for num, v in fields(buf):
        if num == 2:
            name = bytes(v).decode()
        elif num == 3:
            ts = _signed(v)
        elif num == 4:
            mid = off = dur = 0
            for n2, v2 in fields(v):
                if n2 == 1:
                    mid = _signed(v2)
                elif n2 == 2:
                    off = _signed(v2)
                elif n2 == 3:
                    dur = _signed(v2)
            events.append((mid, off, dur))
    return Line(name=name, timestamp_ns=ts, events=events)


def plane_name(buf: bytes) -> str:
    return next((bytes(v).decode() for num, v in fields(buf) if num == 2), "")


def _plane(buf: bytes) -> Plane:
    name, lines, ev_meta, stat_names = "", [], {}, {}
    for num, v in fields(buf):
        if num == 2:
            name = bytes(v).decode()
        elif num == 3:
            lines.append(_line(v))
        elif num == 4:
            mid, meta = _map_entries(v)
            ev_meta[mid] = meta or b""
        elif num == 5:
            sid, meta = _map_entries(v)
            stat_names[sid] = next((bytes(x).decode() for n2, x
                                    in fields(meta or b"") if n2 == 2), "")
    names, stats = {}, {}
    for mid, meta in ev_meta.items():
        ev_name, st = "", {}
        for num, v in fields(meta):
            if num == 2:
                ev_name = bytes(v).decode(errors="replace")
            elif num == 5:
                sid, value = None, None
                for n2, v2 in fields(v):
                    if n2 == 1:
                        sid = _signed(v2)
                    elif n2 == 5:
                        value = bytes(v2).decode(errors="replace")
                    elif n2 == 7:
                        value = stat_names.get(_signed(v2), "")
                if sid in stat_names and value is not None:
                    st[stat_names[sid]] = value
        names[mid], stats[mid] = ev_name, st
    return Plane(name=name, lines=lines, event_names=names, event_stats=stats)


def read_planes(path: Path) -> list[Plane]:
    buf = Path(path).read_bytes()
    return [_plane(v) for num, v in fields(buf) if num == 1]
