"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout on a machine that holds the chips the cell asks
for.  Set-up (weights from the seed on the device, inputs from the seed,
every compile) is timed as ``setup_s``; then the cell's runner measures for
``--seconds``.  With ``--trace 1`` the window runs under the JAX profiler and
the line carries the cell's per-layer metrics instead of its end-to-end ones.
After the window the runner compares what the timed path produced with the
plain reference (``reference.py``); ``correct`` says whether every number
held its limit.  The last line of standard output is the result; the last
lines of standard error give each compared number beside its limit.  Exits
non-zero, with no result, where JAX finds no TPU or too few chips.
"""
import time

T_START = time.monotonic()

import argparse  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = harness.load_cell(args.workload)
    devices = harness.require_devices(cell.workload["chips"])
    harness.use_compile_cache()
    run = harness.Run(cell=cell, seed=args.seed, seconds=args.seconds,
                      trace=bool(args.trace), t_start=T_START, devices=devices,
                      peaks=harness.peaks_for(devices[0].device_kind))
    checks, correct = cell.runner().run(run)
    harness.report(run, checks, correct)
    return 0


if __name__ == "__main__":
    sys.exit(main())
