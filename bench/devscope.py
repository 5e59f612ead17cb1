"""Device ops with the HLO scope they were traced in.

devtrace keeps each device op as (name, start_ns, end_ns). A metric that
reads one layer of the program by its `jax.named_scope` needs the op's
HLO op_name as well. The profiler records it with the ops of a device's
`XLA Ops` line as the stat SCOPE_STAT (xprof shows it as `tf_op`), on the
event or on the event's metadata. jax.profiler.ProfileData yields only
the event's own stats, so this module reads the .xplane.pb itself: a
protobuf XSpace (tsl/profiler/protobuf/xplane.proto), of which it decodes
the one device plane it needs.

  load(path)        (name, start_ns, end_ns, scope) of one device's ops
  window_ops()      the same from the newest .xplane.pb under the run's
                    output directory: the file devtrace.load read for the
                    same run
  scoped_ms(...)    device milliseconds per test of the ops whose scope
                    holds a name; None where no op does

A CPU trace has no device plane, so every reading of it is None.
"""

from __future__ import annotations

import glob
import os
import struct
from typing import Dict, Iterable, Iterator, List, Optional, Tuple

ScopedOp = Tuple[str, float, float, str]

SCOPE_STAT = "tf_op"
OPS_LINE = "XLA Ops"


# -- protobuf wire format ----------------------------------------------------

def _varint(buf, pos: int) -> Tuple[int, int]:
    shift = value = 0
    while True:
        b = buf[pos]
        pos += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, pos
        shift += 7


def _fields(buf) -> Iterator[Tuple[int, object]]:
    """(field number, value) of a message: an int for a varint, bytes for
    a length-delimited or fixed-width field."""
    pos, end = 0, len(buf)
    while pos < end:
        key, pos = _varint(buf, pos)
        field, wire = key >> 3, key & 7
        if wire == 0:
            value, pos = _varint(buf, pos)
        elif wire == 2:
            n, pos = _varint(buf, pos)
            value, pos = buf[pos:pos + n], pos + n
        elif wire in (1, 5):
            n = 8 if wire == 1 else 4
            value, pos = buf[pos:pos + n], pos + n
        else:
            raise ValueError(f"protobuf wire type {wire} in an xplane")
        yield field, value


def _text(b) -> str:
    return bytes(b).decode("utf-8", "replace")


def _stats(stats, names: Dict[int, str]) -> Dict[str, object]:
    """XStat messages -> {stat name: value}; a ref_value names another
    stat metadata entry, whose name is the (interned) string."""
    out = {}
    for raw in stats:
        md, value = None, None
        for field, v in _fields(raw):
            if field == 1:
                md = v
            elif field == 2:
                value = struct.unpack("<d", bytes(v))[0]
            elif field == 3:
                value = v
            elif field == 4:                # int64: two's complement
                value = v - (1 << 64) if v >> 63 else v
            elif field in (5, 6):
                value = _text(v)
            elif field == 7:
                value = names.get(v, "")
        out[names.get(md, "")] = value
    return out


def _map_entries(entries) -> Iterator[Tuple[int, object]]:
    for raw in entries:
        key = value = None
        for field, v in _fields(raw):
            if field == 1:
                key = v
            elif field == 2:
                value = v
        yield key, value


def plane_events(path: str, plane_name: str,
                 line_name: str) -> Iterator[Tuple[str, float, float,
                                                   Dict[str, object]]]:
    """(name, start_ns, end_ns, stats) of every event on one line of one
    plane; stats merge the event's metadata's and the event's own."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    for field, plane in _fields(space):
        if field != 1:
            continue
        parts: Dict[int, list] = {}
        for fld, v in _fields(plane):
            parts.setdefault(fld, []).append(v)
        if [_text(n) for n in parts.get(2, [])] != [plane_name]:
            continue
        names = {}
        for key, raw in _map_entries(parts.get(5, [])):
            names[key] = next((_text(v) for fld, v in _fields(raw)
                               if fld == 2), "")
        meta = {}
        for key, raw in _map_entries(parts.get(4, [])):
            md = {"name": "", "stats": []}
            for fld, v in _fields(raw):
                if fld == 2:
                    md["name"] = _text(v)
                elif fld == 5:
                    md["stats"].append(v)
            meta[key] = md
        for raw_line in parts.get(3, []):
            line = {"name": "", "ts": 0, "events": []}
            for fld, v in _fields(raw_line):
                if fld == 2:
                    line["name"] = _text(v)
                elif fld == 3:
                    line["ts"] = v
                elif fld == 4:
                    line["events"].append(v)
            if line["name"] != line_name:
                continue
            for raw_event in line["events"]:
                md_id, offset_ps, duration_ps, own = 0, 0, 0, []
                for fld, v in _fields(raw_event):
                    if fld == 1:
                        md_id = v
                    elif fld == 2:
                        offset_ps = v
                    elif fld == 3:
                        duration_ps = v
                    elif fld == 4:
                        own.append(v)
                md = meta.get(md_id, {"name": "", "stats": []})
                stats = _stats(md["stats"], names)
                stats.update(_stats(own, names))
                start = line["ts"] + offset_ps / 1e3
                yield md["name"], start, start + duration_ps / 1e3, stats


# -- the reading ---------------------------------------------------------------

def newest(log_dir: str) -> Optional[str]:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    return paths[-1] if paths else None


def load(path: str, device: int = 0) -> List[ScopedOp]:
    return [(name, s, e, str(stats.get(SCOPE_STAT) or ""))
            for name, s, e, stats in plane_events(
                path, f"/device:TPU:{device}", OPS_LINE)]


def window_ops(device: int = 0) -> List[ScopedOp]:
    """The ops of the newest trace under bench/run.py's OUT_DIR."""
    from bench import run
    path = newest(run.OUT_DIR)
    return load(path, device) if path else []


def scoped_ms(ops: Iterable[ScopedOp], window: Tuple[float, float],
              tests: int, scope: str) -> Optional[float]:
    """Device ms per test, inside `window`, of the ops whose scope holds
    `scope`; None where no op in the window does."""
    lo, hi = window
    total, found = 0.0, False
    for _, s, e, sc in ops:
        if scope not in sc:
            continue
        s, e = max(s, lo), min(e, hi)
        if e > s:
            total += e - s
            found = True
    if not found:
        return None
    return total / 1e6 / max(tests, 1)
