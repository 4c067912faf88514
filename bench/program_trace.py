"""The program's own spans and the ALPT step's named scopes in a profile.

    python3 bench/program_trace.py --workload <name> --seed <n> --seconds <s>

runs a training cell once under the profiler, as ``run.py --trace 1`` does,
and prints one JSON line of what the program's part of the trace shows before
the cell's result line (which stays the last line, as ``run.py`` prints it).
Not run by the benchmark's runs: its per-layer metrics read what
``trace_reduce`` keeps, and that keeps neither the program's spans nor the
ops' scopes.

What it reads beside ``trace_reduce``:

* host spans named with the program's prefixes (``train.``, ``engine.``,
  ``storage.``, ``ckpt.``), which reach the profiler whenever it records.
  They join the benchmark's spans, so an idle gap is put down to the
  innermost span of either kind;
* each device op's scope path: the ``op_name`` metadata of its instruction
  (``jit(step_fn)/alpt.step_size/while/...``) in the HLO of the module that
  the ``XLA Modules`` line shows running at the op's start.  The profile
  keeps each module's HLO in its ``/host:metadata`` plane, which JAX's
  ``ProfileData`` does not expose; ``hlo_scopes`` reads it from the file.

A recorded trace kept as JSON has the form ``trace_reduce`` reads, and a
device event may carry its scope path as a fourth entry.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import bisect  # noqa: E402
import dataclasses  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import re  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

import trace_reduce as tr  # noqa: E402

SPAN_PREFIXES = (tr.SPAN_PREFIX, "train.", "engine.", "storage.", "ckpt.")
MODULES_LINE = "XLA Modules"
METADATA_PLANE = "/host:metadata"
#: The in-jit phases of the ALPT step, by the scope that names them.
SCOPES = {"dedup_ms": "lpt.dedup", "step_size_ms": "alpt.step_size"}
HOST_STEP = "train.step"


@dataclasses.dataclass
class ProgramTrace:
    trace: tr.Trace  # device ops and every kept host span
    scopes: dict  # plane name -> [(scope path, start_ns, end_ns, op name)]

    @classmethod
    def from_json(cls, d: dict) -> "ProgramTrace":
        devices = {k: [tuple(e[:3]) for e in v] for k, v in d["devices"].items()}
        scopes = {k: [(e[3], e[1], e[2], e[0]) for e in v if len(e) > 3]
                  for k, v in d["devices"].items()}
        return cls(tr.Trace(devices, [tuple(s) for s in d["spans"]]), scopes)


def read_xplane(path: str) -> ProgramTrace:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    hlo = hlo_scopes(path)
    devices, scopes, spans = {}, {}, []
    for plane in pd.planes:
        if tr.DEVICE_PLANE.match(plane.name):
            lines = {line.name: line for line in plane.lines}
            modules = sorted((float(e.start_ns), float(e.end_ns), e.name)
                             for e in lines[MODULES_LINE].events) if MODULES_LINE in lines else []
            starts = [m[0] for m in modules]
            ops, scoped = devices.setdefault(plane.name, []), scopes.setdefault(plane.name, [])
            for e in lines[tr.OPS_LINE].events if tr.OPS_LINE in lines else ():
                name, start, end = tr.op_name(e.name), float(e.start_ns), float(e.end_ns)
                ops.append((name, start, end))
                scope = _scope_of(hlo, modules, starts, name, start)
                if scope:
                    scoped.append((scope, start, end, name))
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                spans.extend((e.name, float(e.start_ns), float(e.end_ns))
                             for e in line.events if e.name.startswith(SPAN_PREFIXES))
    return ProgramTrace(tr.Trace(devices, spans), scopes)


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


@dataclasses.dataclass
class Reading:
    reduced: tr.Reduced  # trace_reduce's reduction, with the program's spans
    scoped: dict  # device -> [(scope path, start_ns, end_ns, op name)] inside the window

    def scope_seconds(self, scope: str) -> float:
        """Device time under the named scope, mean over devices: the union of
        its ops' intervals, so an op nested in another (a ``while`` and its
        body) counts once."""
        total = sum(sum(e - s for s, e in tr.union(
            (s, e) for path, s, e, _ in events if in_scope(path, scope)))
            for events in self.scoped.values())
        return total * 1e-9 / self.reduced.n_devices

    def idle_under(self, name: str) -> float:
        """Seconds of the window in which a device is idle while the host is
        inside a span ``name``, mean over devices."""
        lo, hi = self.reduced.window
        held = tr.union((max(s, lo), min(e, hi)) for n, s, e in self.reduced.spans if n == name)
        idle = sum((e - s) - tr.overlap(m, s, e)
                   for m in self.reduced.merged.values() for s, e in held)
        return idle * 1e-9 / self.reduced.n_devices

    def span_ms(self) -> dict:
        """{span name: [count, mean ms]} of the program's spans in the window."""
        out = {}
        for n, s, e in self.reduced.spans:
            if not n.startswith(tr.SPAN_PREFIX):
                count, total = out.get(n, (0, 0.0))
                out[n] = (count + 1, total + (e - s) * 1e-6)
        return {n: [c, t / c] for n, (c, t) in sorted(out.items())}

    def summary(self, steps: int) -> dict:
        """The program's readings for a window of ``steps`` steps: the host's
        work per step (``train.step``: the batch's copies and the dispatch),
        the window's idle share under it, each phase's device time per step,
        every program span, and the top ops and idle gaps with their scope and
        holder."""
        red = self.reduced
        spans = self.span_ms()
        out = {"host_step_ms": spans[HOST_STEP][1] if HOST_STEP in spans else None,
               "host_step_idle": (100.0 * self.idle_under(HOST_STEP) / red.window_s
                                  if HOST_STEP in spans else None)}
        for key, scope in SCOPES.items():
            seconds = self.scope_seconds(scope)
            out[key] = 1e3 * seconds / steps if seconds > 0 and steps > 0 else None
        owner = {n: path for events in self.scoped.values() for path, _, _, n in events}
        top = red.breakdown()
        out.update(spans=spans,
                   device_ops=[[k, v, owner.get(k)] for k, v in top["device_ops"]],
                   idle_gaps=top["idle_gaps"])
        return out


def reduce(pt: ProgramTrace) -> Reading | None:
    red = tr.reduce(pt.trace)
    if red is None:
        return None
    lo, hi = red.window
    return Reading(red, {dev: [(path, max(s, lo), min(e, hi), n)
                               for path, s, e, n in events if e > lo and s < hi]
                         for dev, events in pt.scopes.items()})


def reduce_dir(directory: str) -> Reading | None:
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


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    import harness

    cell = harness.load_cell(args.workload)
    devices = harness.require_devices(cell.workload["chips"])
    harness.use_compile_cache()
    run = harness.Run(cell=cell, seed=args.seed, seconds=args.seconds, trace=True,
                      t_start=T_START, devices=devices,
                      peaks=harness.peaks_for(devices[0].device_kind))
    checks, correct = cell.runner().run(run)
    reading = reduce_dir(run.trace_dir)
    steps = run.counts.get("steps", 0)
    print(json.dumps({"program": reading.summary(steps) if reading else None}), flush=True)
    harness.report(run, checks, correct)
    return 0


if __name__ == "__main__":
    sys.exit(main())
