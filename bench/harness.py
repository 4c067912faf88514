"""Loads a cell by name from ``BENCHMARK.json`` and the files it names, runs
its runner, and prints the result line.

Everything that belongs to one configuration, traffic mix or per-layer metric
sits in a file of its own, found by name:

* ``configs/<config>.json``: sizes, source, cuts and assumptions;
* ``traffic/<traffic>.json``: the mix's parameters; its ``kind`` names the
  runner in ``cells/<kind>.py``;
* ``cells/<kind>.py``: the runner, with ``run(run)``, ``check_program(config)``
  and ``rehearsal(config, traffic, limits, cut)`` (``tests/conftest.py``);
* ``layer_metrics/<metric>.py``: a ``read(run)`` that returns the metric or
  None when it finds nothing to read;
* ``limits/<workload>.json``: the limit of each number that decides
  ``correct``;
* ``peaks.json``: published peaks by ``device_kind``.
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import os
import pathlib
import shutil
import sys
import tempfile
import time

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent


def load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: pathlib.Path):
    name = f"bench_{path.parent.name}_{path.stem}".replace(".", "_").replace("-", "_")
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    workload: dict
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list  # metric entries of BENCHMARK.json this cell reports
    per_layer: list
    bench: pathlib.Path  # the directory its files were found in

    def runner(self):
        return load_module(self.bench / "cells" / f"{self.traffic['kind']}.py")


def applies(metric: dict, workload: dict, e2e_names: set) -> bool:
    if "workloads" in metric:
        return workload["name"] in metric["workloads"]
    return metric.get("moves", metric["name"]) in e2e_names


def load_cell(name: str, root: pathlib.Path = ROOT) -> Cell:
    bench = load_json(root / "BENCHMARK.json")
    by_name = {w["name"]: w for w in bench["workloads"]}
    if name not in by_name:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json: {sorted(by_name)}")
    w = by_name[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    here = root / BENCH.name
    e2e = [m for m in bench["end_to_end"] if "workloads" not in m or name in m["workloads"]]
    names = {m["name"] for m in e2e}
    return Cell(
        name=name, workload=w,
        config=load_json(root / conf["file"]),
        traffic=load_json(here / "traffic" / f"{w['traffic']}.json"),
        limits=load_json(here / "limits" / f"{name}.json"),
        end_to_end=e2e,
        per_layer=[m for m in bench["per_layer"] if applies(m, w, names)],
        bench=here,
    )


class CompileCounter:
    """Counts the jit traces JAX makes (each new shape or signature traces;
    a compile, or a load from the cache, follows)."""

    def __init__(self):
        self.traces = 0
        self._on = False

    def listen(self) -> None:
        if not self._on:
            import jax

            jax.monitoring.register_event_duration_secs_listener(self._event)
            self._on = True

    def _event(self, name, seconds, **kw):
        if name == "/jax/core/compile/jaxpr_trace_duration":
            self.traces += 1


COMPILES = CompileCounter()


@dataclasses.dataclass
class Run:
    """One run of a cell, handed to its runner and then to the metric readers."""

    cell: Cell
    seed: int
    seconds: float
    trace: bool
    t_start: float  # time.monotonic() at process start
    devices: list
    peaks: dict = dataclasses.field(default_factory=dict)
    # Filled by the runner:
    e2e: dict = dataclasses.field(default_factory=dict)
    counts: dict = dataclasses.field(default_factory=dict)
    numbers: dict = dataclasses.field(default_factory=dict)
    attempted: int = 0
    failed: int = 0
    memory_peak_bytes: int = 0
    trace_dir: str | None = None
    reduced: object = None  # trace_reduce.Reduced once read

    @property
    def config(self) -> dict:
        return self.cell.config

    @property
    def traffic(self) -> dict:
        return self.cell.traffic

    def mark(self, phase: str) -> None:
        """Keeps set-up's clock at the end of ``phase`` in the counts."""
        self.counts.setdefault("setup_marks_s", {})[phase] = time.monotonic() - self.t_start

    def setup_done(self) -> None:
        """The window starts: set-up ends here."""
        self.e2e["setup_s"] = time.monotonic() - self.t_start
        self.counts["traces_before_window"] = COMPILES.traces

    def window_closed(self) -> None:
        self.counts["traces_in_window"] = COMPILES.traces - self.counts["traces_before_window"]

    @contextlib.contextmanager
    def profiled(self):
        """The profiler around the measured work when ``--trace 1``."""
        if not self.trace:
            yield
            return
        import jax

        self.trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        # The benchmark's own annotations and the runtime's events only: the
        # Python tracer would record every call of the open loop.
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        try:
            yield
        finally:
            jax.profiler.stop_trace()

    def read_memory_peak(self) -> None:
        used = self.devices[: self.cell.workload["chips"]]
        self.memory_peak_bytes = max(
            int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in used)


def require_devices(chips: int):
    """The accelerator devices, or exit without a result."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"bench: needs a TPU, JAX found {devs[0].platform!r}")
    if len(devs) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips, JAX found {len(devs)}")
    return devs


def use_compile_cache(root: pathlib.Path = ROOT) -> str:
    import jax

    COMPILES.listen()
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def peaks_for(kind: str) -> dict:
    table = load_json(BENCH / "peaks.json")["devices"]
    if kind not in table:
        raise SystemExit(f"bench: no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


def read_layer_metrics(run: Run) -> dict:
    import trace_reduce

    if run.trace_dir is None:
        return {}
    t0 = time.monotonic()
    try:
        run.reduced = trace_reduce.reduce_dir(run.trace_dir)
    finally:
        shutil.rmtree(run.trace_dir, ignore_errors=True)
    run.counts["trace_read_s"] = time.monotonic() - t0
    out = {}
    for m in run.cell.per_layer:
        value = load_module(run.cell.bench / "layer_metrics" / f"{m['name']}.py").read(run)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def result_line(run: Run, checks: dict, correct: bool) -> dict:
    dev = run.devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(run.devices), "memory_peak_bytes": run.memory_peak_bytes}
    if run.trace:
        metrics = read_layer_metrics(run)
        if run.reduced is not None:
            device["busy_s"] = run.reduced.busy_s
            device["window_s"] = run.reduced.window_s
    else:
        metrics = {m["name"]: {"value": run.e2e[m["name"]], "unit": m["unit"]}
                   for m in run.cell.end_to_end}
    out = {"correct": correct, "attempted": run.attempted, "failed": run.failed,
           "metrics": metrics, "device": device}
    if run.trace and run.reduced is not None:
        out["breakdown"] = run.reduced.breakdown()
    out["checks"] = checks
    return out


def report(run: Run, line: dict) -> None:
    """The result line last on standard output, each compared number beside
    its limit last on standard error."""
    print(json.dumps({"counts": run.counts}, default=str), file=sys.stderr)
    for k, v in line["checks"].items():
        print(f"check {k}: {v['value']!r} limit {v['limit']!r}", file=sys.stderr)
    print(f"check correct: {line['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
