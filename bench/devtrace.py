"""Device trace: capture with jax.profiler, reduce to intervals, read.

The reduction works on plain intervals, so the tests can check it on a
small recorded trace without a chip:

  ops[device]  (name, start_ns, end_ns) of every device operation
  spans        (name, start_ns, end_ns) of host annotations: the
               benchmark's `bench.window` and `bench.test`, and the
               program's obs spans (engine.*, fusedk.*, stage1.*, ...)

Busy time is the union of a device's op intervals inside the window;
idle is the rest. A gap between ops is labelled by the innermost host
span open at its midpoint.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Sequence, Tuple

Interval = Tuple[str, float, float]

WINDOW = "bench.window"
TEST = "bench.test"
# host annotations worth naming a gap after: the benchmark's own and the
# program's obs spans
SPAN_PREFIXES = ("bench.", "engine.", "fusedk.", "fused.", "stage1.",
                 "stream.", "bridge.", "pipeline.", "serve.")
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"


@dataclasses.dataclass
class Trace:
    ops: Dict[int, List[Interval]]
    spans: List[Interval]

    @property
    def window(self) -> Tuple[float, float]:
        w = [s for s in self.spans if s[0] == WINDOW]
        if len(w) != 1:
            raise ValueError(f"expected one {WINDOW!r} span, found {len(w)}")
        return w[0][1], w[0][2]

    @property
    def window_s(self) -> float:
        lo, hi = self.window
        return (hi - lo) / 1e9

    @property
    def tests(self) -> int:
        lo, hi = self.window
        return sum(1 for s in self.spans
                   if s[0] == TEST and s[1] >= lo and s[2] <= hi)

    @classmethod
    def from_json(cls, obj: dict) -> "Trace":
        return cls(ops={int(k): [tuple(e) for e in v]
                        for k, v in obj["ops"].items()},
                   spans=[tuple(s) for s in obj["spans"]])


def profile_options():
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0       # Python calls would swamp the trace
    opts.host_tracer_level = 2
    return opts


def load(log_dir: str) -> Trace:
    """Read the newest .xplane.pb under `log_dir`."""
    import jax
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    data = jax.profiler.ProfileData.from_file(paths[-1])
    ops: Dict[int, List[Interval]] = {}
    spans: List[Interval] = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = int(m.group(1))
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                ops.setdefault(dev, []).extend(
                    (e.name, float(e.start_ns), float(e.end_ns))
                    for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((e.name, float(e.start_ns), float(e.end_ns))
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIXES))
    return Trace(ops=ops, spans=spans)


def clip(intervals: Iterable[Interval], lo: float, hi: float):
    for name, s, e in intervals:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            yield name, s, e


def union(intervals: Iterable[Interval]) -> List[Tuple[float, float]]:
    merged: List[List[float]] = []
    for _, s, e in sorted(intervals, key=lambda x: x[1]):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def busy_s(trace: Trace, devices: Sequence[int]) -> float:
    """Seconds with an op running, averaged over `devices`."""
    lo, hi = trace.window
    tot = 0.0
    for d in devices:
        tot += sum(e - s for s, e in union(clip(trace.ops.get(d, []), lo, hi)))
    return tot / len(devices) / 1e9


def kernel_s(trace: Trace, pattern: str, device: int = 0) -> float:
    """Summed device seconds of ops whose name matches `pattern`."""
    lo, hi = trace.window
    rx = re.compile(pattern)
    return sum(e - s for name, s, e in clip(trace.ops.get(device, []), lo, hi)
               if rx.search(name)) / 1e9


HLO_OP = re.compile(r"^(%[\w.\-]+) = .*?\s([a-z][\w\-]*)\(")


def short(name: str) -> str:
    """`%permanova_sw.1 custom-call` for a device op named by its HLO
    text; other names unchanged."""
    m = HLO_OP.match(name)
    return f"{m.group(1)} {m.group(2)}" if m else name


def top_ops(trace: Trace, device: int = 0, k: int = 10):
    """The k ops that took most device time in the window, by short name."""
    lo, hi = trace.window
    by: Dict[str, float] = {}
    for name, s, e in clip(trace.ops.get(device, []), lo, hi):
        by[short(name)] = by.get(short(name), 0.0) + (e - s) / 1e9
    return sorted(([n, v] for n, v in by.items()), key=lambda x: -x[1])[:k]


def _label(trace: Trace, t: float) -> str:
    open_ = [s for s in trace.spans if s[1] <= t < s[2]]
    if not open_:
        return "no span"
    return min(open_, key=lambda s: s[2] - s[1])[0]     # innermost


def idle_gaps(trace: Trace, device: int = 0, k: int = 10):
    """The k longest gaps between ops in the window, each named by the
    host span open at its midpoint."""
    lo, hi = trace.window
    busy = union(clip(trace.ops.get(device, []), lo, hi))
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[0] - g[1])
    return [[_label(trace, (s + e) / 2), (e - s) / 1e9] for s, e in gaps[:k]]


def summary(trace: Trace, device: int = 0) -> dict:
    return {"window_s": trace.window_s, "tests": trace.tests,
            "devices": sorted(trace.ops),
            "ops_in_window": sum(1 for _ in clip(trace.ops.get(device, []),
                                                 *trace.window)),
            "top_ops": top_ops(trace, device, 25),
            "idle_gaps": idle_gaps(trace, device, 25)}

