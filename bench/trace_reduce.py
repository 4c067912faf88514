"""From a JAX profiler trace to device busy time, op times and idle gaps.

A trace is read into plain events first (``read_xplane``), so that the
reduction can be checked on a small recorded trace kept as JSON
(``{"devices": {plane: [[op, start_ns, end_ns], ...]}, "spans": [...]}``):

* device events: the ``XLA Ops`` line of each ``/device:<KIND>:<n>`` plane;
* host spans: the benchmark's own ``bench.*`` annotations.

The window is the ``bench.window`` span.  Busy time is the union of a
device's op intervals inside the window, averaged over devices; op times are
summed by name and averaged over devices.  An idle gap is a stretch of the
window in which a device runs no op; it is put down to the innermost
``bench.*`` span (other than the window) that holds its midpoint.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:[A-Z]+:\d+$")
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
WINDOW = "bench.window"
TOP = 10


@dataclasses.dataclass
class Trace:
    devices: dict  # plane name -> [(op name, start_ns, end_ns)]
    spans: list  # [(name, start_ns, end_ns)] host spans named bench.*

    @classmethod
    def from_json(cls, d: dict) -> "Trace":
        return cls({k: [tuple(e) for e in v] for k, v in d["devices"].items()},
                   [tuple(s) for s in d["spans"]])


def read_xplane(path: str) -> Trace:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices, spans = {}, []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices.setdefault(plane.name, []).extend(
                        (op_name(e.name), float(e.start_ns), float(e.end_ns))
                        for e in line.events)
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                spans.extend((e.name, float(e.start_ns), float(e.end_ns))
                             for e in line.events if e.name.startswith(SPAN_PREFIX))
    return Trace(devices, spans)


def op_name(text: str) -> str:
    """``%fusion.3 = f32[...] fusion(...)`` -> ``fusion.3``."""
    return text.split(" = ", 1)[0].lstrip("%")


def union(intervals) -> list:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def overlap(merged: list, lo: float, hi: float) -> float:
    return sum(max(0.0, min(e, hi) - max(s, lo)) for s, e in merged)


@dataclasses.dataclass
class Reduced:
    window: tuple  # (start_ns, end_ns)
    window_s: float
    busy_s: float  # mean over devices
    ops: dict  # op name -> seconds, mean over devices
    op_counts: dict  # op name -> events, mean over devices
    gaps: list  # [(span name, seconds)], the TOP longest, longest first
    spans: list  # host spans inside the window
    merged: dict  # device -> merged busy intervals inside the window

    @property
    def n_devices(self) -> int:
        return len(self.merged)

    def span_busy(self, name: str) -> list:
        """[(span seconds, device-busy seconds inside it)] per span ``name``,
        device time averaged over devices."""
        out = []
        for n, s, e in self.spans:
            if n == name:
                busy = sum(overlap(m, s, e) for m in self.merged.values()) / self.n_devices
                out.append(((e - s) * 1e-9, busy * 1e-9))
        return out

    def ops_matching(self, pattern: str) -> tuple[float, float]:
        """(seconds, events) of ops whose name matches ``pattern``."""
        rx = re.compile(pattern)
        keys = [k for k in self.ops if rx.search(k)]
        return sum(self.ops[k] for k in keys), sum(self.op_counts[k] for k in keys)

    def breakdown(self) -> dict:
        top = sorted(self.ops.items(), key=lambda kv: -kv[1])[:TOP]
        return {"device_ops": [[k, v] for k, v in top],
                "idle_gaps": [[k, v] for k, v in self.gaps[:TOP]]}


def reduce(trace: Trace) -> Reduced | None:
    """None where the trace holds no device op inside the window."""
    windows = [(s, e) for n, s, e in trace.spans if n == WINDOW]
    if not windows or not trace.devices:
        return None
    lo, hi = windows[0]
    spans = sorted((n, s, e) for n, s, e in trace.spans if n != WINDOW and e > lo and s < hi)
    ops, counts, merged, gaps = {}, {}, {}, []
    for dev, events in trace.devices.items():
        inside = [(n, max(s, lo), min(e, hi)) for n, s, e in events if e > lo and s < hi]
        for n, s, e in inside:
            ops[n] = ops.get(n, 0.0) + (e - s)
            counts[n] = counts.get(n, 0) + 1
        merged[dev] = union((s, e) for _, s, e in inside)
        edges = [lo] + [x for iv in merged[dev] for x in iv] + [hi]
        gaps.extend((g0, g1) for g0, g1 in zip(edges[::2], edges[1::2]) if g1 > g0)
    nd = len(trace.devices)
    busy = sum(sum(e - s for s, e in m) for m in merged.values()) / nd
    if busy <= 0:
        return None
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:TOP]
    gaps = [(_holder(spans, (g0 + g1) / 2), (g1 - g0) * 1e-9) for g0, g1 in gaps]
    return Reduced(window=(lo, hi), window_s=(hi - lo) * 1e-9, busy_s=busy * 1e-9,
                   ops={k: v * 1e-9 / nd for k, v in ops.items()},
                   op_counts={k: v / nd for k, v in counts.items()},
                   gaps=gaps, spans=spans, merged=merged)


def _holder(spans: list, t: float) -> str:
    best = None
    for n, s, e in spans:
        if s <= t <= e and (best is None or e - s < best[1]):
            best = (n, e - s)
    return best[0] if best else WINDOW


def reduce_dir(directory: str) -> Reduced | None:
    paths = glob.glob(os.path.join(directory, "**", "*.xplane.pb"), recursive=True)
    if not paths:
        return None
    return reduce(read_xplane(sorted(paths)[-1]))
