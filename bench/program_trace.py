"""The program's own spans and the ALPT step's named scopes in a profile,
laid out by hand.

    python3 bench/program_trace.py --workload <name> --seed <n> --seconds <s>

runs a training cell once under the profiler, as ``run.py --trace 1`` does,
and prints one JSON line of what the program's part of the trace shows before
the cell's result line (which stays the last line, as ``run.py`` prints it).
Not run by the benchmark's runs.  It reads the reduction that the per-layer
metrics read (``trace_reduce``), once: ``HOST_STEP`` is what
``host_step_ms.train`` reads, and ``SCOPES`` what ``dedup_ms.train``,
``step_size_ms.train`` and ``sparse_row_update_roofline`` read.  Beside them
it shows the device's idle share inside ``train.step``, every program span,
and the top ops with their scope paths.  Per step here means per step that
the runner counted; the metrics count the window's ``bench.train_step``
spans.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

import trace_reduce as tr  # noqa: E402

#: The in-jit phases of the ALPT step, by the scope that names them.
SCOPES = {"dedup_ms": "lpt.dedup", "step_size_ms": "alpt.step_size",
          "row_update_ms": "lpt.row_update"}
HOST_STEP = "train.step"


def idle_under(red: tr.Reduced, name: str) -> float:
    """Seconds of the window in which a device is idle while the host is
    inside a span ``name``, mean over devices."""
    lo, hi = red.window
    held = tr.union((max(s, lo), min(e, hi)) for n, s, e in red.spans if n == name)
    idle = sum((e - s) - tr.overlap(m, s, e) for m in red.merged.values() for s, e in held)
    return idle * 1e-9 / red.n_devices


def span_table(red: tr.Reduced) -> dict:
    """{span name: [count, mean ms]} of the program's spans in the window."""
    names = sorted({n for n, _, _ in red.spans if not n.startswith(tr.SPAN_PREFIX)})
    return {n: [red.count(n), red.span_ms(n)] for n in names}


def summary(red: tr.Reduced, steps: int) -> dict:
    """The program's readings for a window of ``steps`` steps: the host's
    work per step (``train.step``: the batch's copies and the dispatch), the
    window's idle share under it, each phase's device time per step, every
    program span, and the top ops and idle gaps with their scope and
    holder."""
    host = red.span_ms(HOST_STEP)
    out = {"host_step_ms": host,
           "host_step_idle": (100.0 * idle_under(red, HOST_STEP) / red.window_s
                              if host is not None else None)}
    for key, scope in SCOPES.items():
        seconds = red.scope_seconds(scope)
        out[key] = 1e3 * seconds / steps if seconds > 0 and steps > 0 else None
    owner = {n: path for events in red.scoped.values() for path, _, _, n in events}
    top = red.breakdown()
    out.update(spans=span_table(red),
               device_ops=[[k, v, owner.get(k)] for k, v in top["device_ops"]],
               idle_gaps=top["idle_gaps"])
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
    line = harness.result_line(run, checks, correct)
    red = run.reduced
    steps = run.counts.get("steps", 0)
    print(json.dumps({"program": summary(red, steps) if red else None}), flush=True)
    harness.report(run, line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
