"""Readings that a cell's limits are set from, over many seeds in one process.

    python3 bench/readings.py --workload <name> --seeds 1,2,3 \
        --which program,control,half_batch,no_exchange

* ``program``: the program's numbers against the reference (lower reading);
* ``control``: the reference computed in bfloat16 in the program's place;
* ``half_batch``: the reference trained on the leading half of each batch,
  the mean taken over it (a planted fault);
* ``no_exchange``: the reference trained on one replica's share of the batch
  alone, as a replica that skips the gradient exchange would (4-chip cells).

Training cells need no window.  Prints one JSON line per (seed, which).
Not run by the benchmark's runs.
"""
import argparse
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--which", default="program,control")
    args = ap.parse_args(argv)
    import jax.numpy as jnp

    import compare

    cell = harness.load_cell(args.workload)
    devices = harness.require_devices(cell.workload["chips"])
    harness.use_compile_cache()
    cfg, traffic = cell.config, cell.traffic
    chips = cell.workload["chips"]
    which = args.which.split(",")
    drv = cell.runner()

    for seed in [int(s) for s in args.seeds.split(",")]:
        t0 = time.perf_counter()
        ids, labels, _ = drv.make_pool(cfg, traffic, seed, chips)
        steps = traffic["check_steps"]
        sync = traffic.get("sync_bits")
        ref = drv.reference_readings(cfg, seed, ids, labels, steps, shards=chips, sync_bits=sync)
        if "program" in which:
            prog = drv.Program(cfg, traffic, devices, chips, seed)
            got = drv.program_readings(prog, cfg, ids, labels, steps)
            del prog
            _emit(seed, "program", compare.train_numbers(got, ref), t0)
        variants = {"control": dict(dtype=jnp.bfloat16, shards=chips, sync_bits=sync),
                    "half_batch": dict(keep=0.5, shards=max(1, chips // 2), sync_bits=sync),
                    "no_exchange": dict(keep=1.0 / chips, shards=1)}
        for name, kw in variants.items():
            if name in which:
                got = drv.reference_readings(cfg, seed, ids, labels, steps, **kw)
                _emit(seed, name, compare.train_numbers(got, ref), t0)
    return 0


def _emit(seed, which, numbers, t0):
    print(json.dumps({"seed": seed, "which": which, "seconds": time.perf_counter() - t0,
                      **numbers}), flush=True)


if __name__ == "__main__":
    sys.exit(main())
