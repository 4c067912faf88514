"""From a JAX profiler trace to device busy time, op times, scope times and
idle gaps.

A trace is read into plain events first (``read_xplane``), so that the
reduction can be checked on a small recorded trace kept as JSON
(``{"devices": {plane: [[op, start_ns, end_ns, scope], ...]}, "spans":
[[name, start_ns, end_ns], ...]}``, the scope optional):

* device events: the ``XLA Ops`` line of each ``/device:<KIND>:<n>`` plane,
  each with its scope path: the ``op_name`` metadata of its instruction
  (``jit(step_fn)/alpt.step_size/while/...``) in the HLO of the module that
  the plane's ``XLA Modules`` line shows running at the op's start.  The
  profile keeps each module's HLO in its ``/host:metadata`` plane, which
  JAX's ``ProfileData`` does not expose; ``hlo_scopes`` reads it from the
  file;
* host spans: the benchmark's own ``bench.*`` annotations and the program's
  spans (``train.``, ``engine.``, ``storage.``, ``ckpt.``), which reach the
  profiler whenever it records.

The window is the ``bench.window`` span.  Busy time is the union of a
device's op intervals inside the window, averaged over devices; op times are
summed by name and averaged over devices; a scope's time is the union of the
intervals of the ops under it.  An idle gap is a stretch of the window in
which a device runs no op; it is put down to the innermost span of either
kind (other than the window) that holds its midpoint.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:[A-Z]+:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
METADATA_PLANE = "/host:metadata"
SPAN_PREFIX = "bench."
SPAN_PREFIXES = (SPAN_PREFIX, "train.", "engine.", "storage.", "ckpt.")
WINDOW = "bench.window"
TOP = 10


@dataclasses.dataclass
class Trace:
    devices: dict  # plane name -> [(op name, start_ns, end_ns[, scope path])]
    spans: list  # [(name, start_ns, end_ns)] host spans with SPAN_PREFIXES

    @classmethod
    def from_json(cls, d: dict) -> "Trace":
        return cls({k: [tuple(e) for e in v] for k, v in d["devices"].items()},
                   [tuple(s) for s in d["spans"]])


def read_xplane(path: str) -> Trace:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    hlo = hlo_scopes(path)
    devices, spans = {}, []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = {line.name: line for line in plane.lines}
            modules = sorted((float(e.start_ns), float(e.end_ns), e.name)
                             for e in lines[MODULES_LINE].events) if MODULES_LINE in lines else []
            starts = [m[0] for m in modules]
            ops = devices.setdefault(plane.name, [])
            for e in lines[OPS_LINE].events if OPS_LINE in lines else ():
                name, start = op_name(e.name), float(e.start_ns)
                ops.append((name, start, float(e.end_ns),
                            _scope_of(hlo, modules, starts, name, start) or ""))
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                spans.extend((e.name, float(e.start_ns), float(e.end_ns))
                             for e in line.events if e.name.startswith(SPAN_PREFIXES))
    return Trace(devices, spans)


def op_name(text: str) -> str:
    """``%fusion.3 = f32[...] fusion(...)`` -> ``fusion.3``."""
    return text.split(" = ", 1)[0].lstrip("%")


def _scope_of(scopes: dict, modules: list, starts: list, op: str, t: float) -> str | None:
    """The op's ``op_name`` in the module running at ``t``, if known."""
    i = bisect.bisect_right(starts, t) - 1
    if i < 0 or t > modules[i][1]:
        return None
    return scopes.get(modules[i][2], {}).get(op)


def in_scope(path: str, scope: str) -> bool:
    """Whether ``scope`` is a component of the scope path (transform
    wrappers such as ``jvp(...)`` count as components too)."""
    return re.search(rf"(^|[/(]){re.escape(scope)}($|[/)])", path) is not None


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
    scoped: dict  # device -> [(scope path, start_ns, end_ns, op name)] inside the window

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

    def count(self, name: str) -> int:
        """How many spans ``name`` the window holds."""
        return sum(1 for n, _, _ in self.spans if n == name)

    def span_ms(self, name: str) -> float | None:
        """Mean duration of the spans ``name`` in the window, in ms; None
        where there is none."""
        ms = [(e - s) * 1e-6 for n, s, e in self.spans if n == name]
        return sum(ms) / len(ms) if ms else None

    def ops_matching(self, pattern: str) -> tuple[float, float]:
        """(seconds, events) of ops whose name matches ``pattern``."""
        rx = re.compile(pattern)
        keys = [k for k in self.ops if rx.search(k)]
        return sum(self.ops[k] for k in keys), sum(self.op_counts[k] for k in keys)

    def scope_seconds(self, scope: str) -> float:
        """Device time under the named scope inside the window, mean over
        devices: the union of its ops' intervals, so an op nested in another
        (a ``while`` and its body) counts once."""
        hit = {p for events in self.scoped.values() for p, _, _, _ in events}
        hit = {p for p in hit if in_scope(p, scope)}
        total = sum(sum(e - s for s, e in union((s, e) for p, s, e, _ in events if p in hit))
                    for events in self.scoped.values())
        return total * 1e-9 / self.n_devices

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
    ops, counts, merged, scoped, gaps = {}, {}, {}, {}, []
    for dev, events in trace.devices.items():
        inside = [(n, max(s, lo), min(e, hi), scope[0] if scope else "")
                  for n, s, e, *scope in events if e > lo and s < hi]
        for n, s, e, _ in inside:
            ops[n] = ops.get(n, 0.0) + (e - s)
            counts[n] = counts.get(n, 0) + 1
        merged[dev] = union((s, e) for _, s, e, _ in inside)
        scoped[dev] = [(p, s, e, n) for n, s, e, p in inside if p]
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
                   gaps=gaps, spans=spans, merged=merged, scoped=scoped)


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


# The HLO of each module, from the profile's metadata plane, by a minimal
# protobuf wire reader.  Fields read: XSpace.planes = 1; XPlane.name = 2,
# .event_metadata = 4 (map entry: key 1, value 2); XEventMetadata.name = 2,
# .stats = 5; XStat.bytes_value = 6; HloProto.hlo_module = 1;
# HloModuleProto.computations = 3; HloComputationProto.instructions = 2;
# HloInstructionProto.name = 1, .metadata = 7; OpMetadata.op_name = 2.


def _varint(buf, i: int) -> tuple[int, int]:
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """(field number, value) of one message: an integer, a memoryview for a
    length-delimited value, None for a fixed-width one."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            value, i = None, i + (8 if wire == 1 else 4)
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield key >> 3, value


def _field(buf, number: int):
    return next((v for f, v in _fields(buf) if f == number), None)


def _text(buf) -> str:
    return bytes(buf).decode(errors="replace") if buf is not None else ""


def hlo_scopes(path: str) -> dict:
    """{module name: {instruction name: op_name}} from the profile's metadata
    plane; empty where the file holds none."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for number, plane in _fields(space):
        if number != 1 or _text(_field(plane, 2)) != METADATA_PLANE:
            continue
        for pf, entry in _fields(plane):
            meta = _field(entry, 2) if pf == 4 else None
            if meta is None:
                continue
            for mf, stat in _fields(meta):
                blob = _field(stat, 6) if mf == 5 else None
                if blob is not None:
                    out[_text(_field(meta, 2))] = _op_names(blob)
    return out


def _op_names(hlo_proto) -> dict:
    out = {}
    module = _field(hlo_proto, 1)
    for cf, comp in _fields(module) if module is not None else ():
        for inf, inst in _fields(comp) if cf == 3 else ():
            if inf != 2:
                continue
            fields = dict(_fields(inst))
            meta = fields.get(7)
            op = _text(_field(meta, 2)) if meta is not None else ""
            if op:
                out[_text(fields.get(1))] = op
    return out
