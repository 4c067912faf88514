"""A later change adds a configuration, a traffic mix and a per-layer metric
as new files, and the harness finds them without an edit to a file that is
already there: a clone of a training configuration, and a configuration of
a new runner kind with its own runner, rehearsal file and scope reader."""
import glob
import json
import pathlib
import shutil
import time

import jax
import pytest

import harness
import trace_reduce as tr
from conftest import BENCH, ROOT, make_tiny_root, run_cell


def test_new_files_are_found_by_name(tmp_path):
    root = make_tiny_root(tmp_path)
    bench_dir = root / "bench"
    before = {p: p.read_bytes() for p in bench_dir.rglob("*") if p.is_file()}

    cfg = json.loads((bench_dir / "configs" / "tiny-criteo-train.json").read_text())
    cfg["name"] = "tiny-criteo-lpt8"
    (bench_dir / "configs" / "tiny-criteo-lpt8.json").write_text(json.dumps(cfg))
    (bench_dir / "traffic" / "tiny-train-uniform.json").write_text(json.dumps(
        {"kind": "train", "batch": 8, "pool_batches": 2, "check_steps": 3}))
    (bench_dir / "limits" / "tiny-new.json").write_text(json.dumps({"loss_gap": 1.0}))
    (bench_dir / "layer_metrics" / "steps_traced.py").write_text(
        "def read(run):\n    return float(run.counts['steps'])\n")

    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny-criteo-lpt8", "source": "x",
                             "file": "bench/configs/tiny-criteo-lpt8.json", "reduced": []})
    bench["workloads"].append({"name": "tiny-new", "config": "tiny-criteo-lpt8",
                               "traffic": "tiny-train-uniform", "chips": 1, "why": "x"})
    bench["per_layer"].append({"name": "steps_traced", "unit": "steps", "better": "higher",
                               "source": "program_counter", "layer": "trainer step",
                               "moves": "train_samples_per_s", "workloads": ["tiny-new"]})
    bench["end_to_end"][0]["workloads"].append("tiny-new")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = harness.load_cell("tiny-new", root)
    assert cell.config["name"] == "tiny-criteo-lpt8"
    assert cell.traffic["batch"] == 8 and cell.limits == {"loss_gap": 1.0}
    assert [m["name"] for m in cell.per_layer] == ["steps_traced"]
    assert {m["name"] for m in cell.end_to_end} == {"train_samples_per_s", "setup_s"}
    assert hasattr(cell.runner(), "run")

    class _Run:
        counts = {"steps": 7}

    metric = harness.load_module(cell.bench / "layer_metrics" / "steps_traced.py")
    assert metric.read(_Run()) == 7.0
    unchanged = {p: p.read_bytes() for p in before}
    assert unchanged == before


TOY_RUNNER = '''"""Toy runner: jitted steps x <- tanh(x @ w) under one named scope."""
import time

import jax
import jax.numpy as jnp
import numpy as np

SCOPE = "toy.matmul"


def check_program(cfg):
    if cfg["width"] % 8:
        raise SystemExit("toy: the width must be a multiple of 8")


def rehearsal(cfg, traffic, limits, cut):
    return (dict(cfg, width=cut["width"]), dict(traffic, batch=cut["batch"]),
            dict(cut["limits"]))


@jax.jit
def toy_step(w, x):
    with jax.named_scope(SCOPE):
        return jnp.tanh(x @ w)


def run(r):
    rng = np.random.default_rng(r.seed)
    d, b, n = r.config["width"], r.traffic["batch"], r.traffic["check_steps"]
    w0 = (rng.standard_normal((d, d)) / np.sqrt(d)).astype(np.float32)
    x0 = rng.standard_normal((b, d)).astype(np.float32)
    w, x = jnp.asarray(w0), jnp.asarray(x0)
    for _ in range(n):
        x = toy_step(w, x)
    got = np.asarray(x)
    r.setup_done()
    steps = 0
    with r.profiled():
        with jax.profiler.TraceAnnotation("bench.window"):
            t0 = time.perf_counter()
            while steps == 0 or time.perf_counter() - t0 < r.seconds:
                with jax.profiler.TraceAnnotation("bench.train_step"):
                    x = toy_step(w, x)
                steps += 1
            x.block_until_ready()
            window = time.perf_counter() - t0
    r.window_closed()
    r.e2e["toy_steps_per_s"] = steps / window
    r.counts.update(steps=steps, window_s=window)
    r.attempted, r.failed = steps, 0
    r.read_memory_peak()
    ref = x0.astype(np.float64)
    for _ in range(n):
        ref = np.tanh(ref @ w0)
    checks = {"max_gap": {"value": float(np.abs(got - ref).max()),
                          "limit": r.cell.limits["max_gap"]}}
    return checks, all(c["value"] <= c["limit"] for c in checks.values())
'''

TOY_READER = '''"""Device ms a toy step spends under the toy's scope."""


def read(run):
    red = run.reduced
    if red is None:
        return None
    seconds, steps = red.scope_seconds("toy.matmul"), red.count("bench.train_step")
    if seconds <= 0 or steps == 0:
        return None
    return 1e3 * seconds / steps
'''


def _add_toy(src, *, rehearsal=True):
    """Writes the toy's files into ``src`` and appends its entries to
    ``BENCHMARK.json``, as a change that adds a configuration would."""
    b = src / "bench"
    (b / "cells" / "toy.py").write_text(TOY_RUNNER)
    (b / "configs" / "toy-mlp.json").write_text(json.dumps(
        {"name": "toy-mlp", "width": 256, "dtype": "float32"}))
    (b / "traffic" / "toy-steps.json").write_text(json.dumps(
        {"kind": "toy", "batch": 512, "check_steps": 2}))
    (b / "limits" / "toy-train.json").write_text(json.dumps({"max_gap": 1e-4}))
    (b / "layer_metrics" / "toy_matmul_ms.py").write_text(TOY_READER)
    if rehearsal:
        (b / "tests" / "rehearsal" / "toy-mlp.json").write_text(json.dumps(
            {"width": 16, "batch": 8, "limits": {"max_gap": 1e-4}}))
    bench = json.loads((src / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "toy-mlp", "source": "x", "reduced": [],
                             "file": "bench/configs/toy-mlp.json"})
    bench["workloads"].append({"name": "toy-train", "config": "toy-mlp",
                               "traffic": "toy-steps", "chips": 1, "why": "x"})
    bench["end_to_end"].append({"name": "toy_steps_per_s", "unit": "steps/s",
                                "better": "higher", "bound": 0.03, "source": "host_clock",
                                "workloads": ["toy-train"]})
    bench["per_layer"].append({"name": "toy_matmul_ms", "unit": "ms", "better": "lower",
                               "source": "device_trace", "layer": "toy step",
                               "moves": "toy_steps_per_s", "workloads": ["toy-train"]})
    (src / "BENCHMARK.json").write_text(json.dumps(bench))


def _checkout(dest):
    shutil.copytree(BENCH, dest / "bench", ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", dest)
    return dest


def test_a_configuration_of_a_new_kind_joins_by_new_files(tmp_path):
    src = _checkout(tmp_path / "src")
    before = {p.relative_to(src): p.read_bytes() for p in src.rglob("*") if p.is_file()}
    _add_toy(src)
    after = {p.relative_to(src): p.read_bytes() for p in src.rglob("*") if p.is_file()}
    index = pathlib.Path("BENCHMARK.json")
    assert {p: after[p] for p in before if p != index} == {
        p: v for p, v in before.items() if p != index}
    old, new = json.loads(before[index]), json.loads(after[index])
    for key, value in old.items():  # BENCHMARK.json only grew at the ends of its lists
        assert new[key][:len(value)] == value if isinstance(value, list) else new[key] == value

    cell = harness.load_cell("toy-train", src)
    runner = cell.runner()
    runner.check_program(cell.config)
    assert [m["name"] for m in cell.end_to_end] == ["setup_s", "toy_steps_per_s"]
    assert [m["name"] for m in cell.per_layer] == ["toy_matmul_ms"]

    tiny = make_tiny_root(tmp_path / "tiny", src)
    small = harness.load_cell("tiny-toy-train", tiny)
    assert small.config["width"] == 16 and small.traffic == {
        "kind": "toy", "batch": 8, "check_steps": 2}
    assert [m["name"] for m in small.per_layer] == ["toy_matmul_ms"]
    assert "tiny-criteo-train" in json.loads((tiny / "BENCHMARK.json").read_text())[
        "per_layer"][0]["workloads"]

    line, _ = run_cell(tiny, "tiny-toy-train", seconds=0.3)
    assert line["correct"] is True and set(line["metrics"]) == {"setup_s", "toy_steps_per_s"}

    # Traced: the toy's scope reaches the profile's HLO, and its reader reads
    # the scope's time from a trace whose ops carry those paths.
    run = harness.Run(cell=small, seed=2**31 + 3, seconds=0.3, trace=True,
                      t_start=time.monotonic(), devices=jax.devices(),
                      peaks=harness.peaks_for("TPU v5 lite"))
    checks, correct = small.runner().run(run)
    path = glob.glob(f"{run.trace_dir}/**/*.xplane.pb", recursive=True)[0]
    paths = sorted({p for names in tr.hlo_scopes(path).values() for p in names.values()
                    if tr.in_scope(p, "toy.matmul")})
    line = harness.result_line(run, checks, correct)
    assert line["correct"] is True and list(line)[-1] == "checks"
    assert paths and all(p.startswith("jit(toy_step)/") for p in paths)
    ops = [(f"op.{i}", 100.0 * i, 100.0 * i + 40.0, p) for i, p in enumerate(paths)]
    red = tr.reduce(tr.Trace({"/device:TPU:0": ops},
                             [("bench.window", 0.0, 100.0 * len(paths)),
                              ("bench.train_step", 0.0, 50.0),
                              ("bench.train_step", 50.0, 100.0 * len(paths))]))
    run.reduced = red
    reader = harness.load_module(small.bench / "layer_metrics" / "toy_matmul_ms.py")
    assert reader.read(run) == pytest.approx(1e3 * 40e-9 * len(paths) / 2)


def test_a_configuration_without_a_rehearsal_file_is_refused(tmp_path):
    src = _checkout(tmp_path / "src")
    _add_toy(src, rehearsal=False)
    with pytest.raises(FileNotFoundError, match="rehearsal/toy-mlp.json"):
        make_tiny_root(tmp_path / "tiny", src)
