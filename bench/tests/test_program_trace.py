"""The program's spans and scopes in the trace reduction, and the by-hand
layout of ``program_trace``: by hand, on a recorded TPU trace, and in real
profiler files."""
import glob
import json

import pytest

import harness
import program_trace as pt
import trace_reduce as tr

DATA = harness.BENCH / "tests" / "data"
RECORDED = DATA / "program_trace_criteo-alpt8-train.json"


def _program_trace():
    # Window 0..100 ns, two benchmark steps; the program's spans nest inside
    # the first (feed 2-8, dispatch 8-20) and the second (55-60).  Device ops
    # carry scope paths: a dedup sort 10-30, a fenced while 40-70 with a body
    # op 45-55 inside it, and an op with no scope 80-90.
    return tr.Trace.from_json({
        "devices": {"/device:TPU:0": [
            ["sort.1", 10, 30, "jit(step_fn)/lpt.dedup/sort"],
            ["while.1", 40, 70, "jit(step_fn)/alpt.step_size/while"],
            ["fusion.2", 45, 55, "jit(step_fn)/alpt.step_size/while/body/transpose(jvp())/dot"],
            ["fusion.3", 80, 90]]},
        "spans": [["bench.window", 0, 100], ["bench.train_step", 0, 50],
                  ["bench.train_step", 50, 100], ["train.step", 2, 20],
                  ["train.feed", 2, 8], ["train.dispatch", 8, 20], ["train.step", 55, 60],
                  ["engine.wave", 60, 65]]})


def test_program_spans_and_scopes_by_hand():
    r = tr.reduce(_program_trace())
    assert r.busy_s == pytest.approx(60e-9)  # 10-30, 40-70, 80-90
    # The while and its body op overlap: the scope counts 40-70 once.
    assert r.scope_seconds("alpt.step_size") == pytest.approx(30e-9)
    assert r.scope_seconds("lpt.dedup") == pytest.approx(20e-9)
    assert r.scope_seconds("lpt") == 0.0  # a whole component, not a prefix
    # Idle inside train.step: 2-10 in the first, none in 55-60 (the while runs).
    assert pt.idle_under(r, "train.step") == pytest.approx(8e-9)
    assert pt.idle_under(r, "train.feed") == pytest.approx(6e-9)
    assert pt.idle_under(r, "bench.train_step") == pytest.approx(40e-9)  # all idle
    assert pt.idle_under(r, "no.such.span") == 0.0
    assert pt.span_table(r) == {"engine.wave": [1, pytest.approx(5e-6)],
                                "train.dispatch": [1, pytest.approx(12e-6)],
                                "train.feed": [1, pytest.approx(6e-6)],
                                "train.step": [2, pytest.approx(11.5e-6)]}
    s = pt.summary(r, steps=2)
    assert s["host_step_ms"] == pytest.approx(11.5e-6)
    assert s["host_step_idle"] == pytest.approx(8.0)
    assert s["dedup_ms"] == pytest.approx(1e-5) and s["step_size_ms"] == pytest.approx(1.5e-5)
    assert s["row_update_ms"] is None
    assert s["device_ops"][0] == ["while.1", pytest.approx(30e-9),
                                  "jit(step_fn)/alpt.step_size/while"]
    assert s["device_ops"][-1] == ["fusion.3", pytest.approx(10e-9), None]


def test_idle_gaps_go_to_the_innermost_span_of_either_kind():
    r = tr.reduce(_program_trace())
    gaps = sorted((round(s * 1e9), n) for n, s in r.gaps)
    # 0-10 (midpoint 5, in train.feed inside train.step inside the
    # benchmark's step), 30-40 and 70-80 (the benchmark's steps alone),
    # 90-100 (the benchmark's second step).
    assert gaps == [(10, "bench.train_step"), (10, "bench.train_step"),
                    (10, "bench.train_step"), (10, "train.feed")]


def test_the_benchmarks_reduction_reads_as_without_the_program():
    """The program's spans and the ops' scopes change no number that
    ``trace_reduce`` gave without them, only the holders of the idle gaps."""
    t = _program_trace()
    alone = tr.reduce(tr.Trace({k: [e[:3] for e in v] for k, v in t.devices.items()},
                               [s for s in t.spans if s[0].startswith(tr.SPAN_PREFIX)]))
    red = tr.reduce(t)
    assert (red.busy_s, red.window_s, red.ops, red.op_counts, red.merged) == (
        alone.busy_s, alone.window_s, alone.ops, alone.op_counts, alone.merged)
    assert red.span_busy("bench.train_step") == alone.span_busy("bench.train_step")
    assert all(n == "bench.train_step" for n, _ in alone.gaps)


def test_a_trace_without_program_spans_or_scopes_reads_nothing_of_them():
    t = _program_trace()
    t.devices = {k: [e[:3] for e in v] for k, v in t.devices.items()}
    t.spans = [s for s in t.spans if s[0].startswith(tr.SPAN_PREFIX)]
    s = pt.summary(tr.reduce(t), steps=2)
    assert [s[k] for k in ("host_step_ms", "host_step_idle", "dedup_ms", "step_size_ms",
                           "row_update_ms")] == [None] * 5
    assert s["spans"] == {}


def test_recorded_tpu_trace():
    with open(RECORDED) as f:
        rec = json.load(f)
    r = tr.reduce(tr.Trace.from_json(rec["trace"]))
    want = rec["expect"]
    assert r.window_s == pytest.approx(want["window_s"], rel=1e-9)
    assert r.busy_s == pytest.approx(want["busy_s"], rel=1e-9)
    s = pt.summary(r, rec["counts"]["steps"])
    assert s["device_ops"][0][:2] == [want["top_op"], pytest.approx(want["top_op_s"], rel=1e-9)]
    for key, value in rec["expect_program"].items():
        assert s[key] == pytest.approx(value, rel=1e-9), key


def test_host_step_is_the_hosts_work_not_the_devices_step():
    """On the chip the program's ``train.step`` holds the batch's copies and
    the dispatch only; the wait for the previous step follows it."""
    with open(RECORDED) as f:
        rec = json.load(f)
    r = tr.reduce(tr.Trace.from_json(rec["trace"]))
    work = r.span_ms("train.feed") + r.span_ms("train.dispatch")
    assert work <= r.span_ms("train.step") < work + 0.05
    assert r.span_ms("train.step") < 0.1 * 1e3 * r.busy_s / rec["counts"]["steps"]


def test_reads_program_spans_from_a_profiler_file(tmp_path):
    import jax

    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        for name in ("train.step", "engine.wave", "storage.writeback", "ckpt.save",
                     "other.span"):
            with jax.profiler.TraceAnnotation(name):
                pass
    jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)[0]
    assert sorted(s[0] for s in tr.read_xplane(path).spans) == [
        "bench.window", "ckpt.save", "engine.wave", "storage.writeback", "train.step"]


def test_reads_scopes_from_the_profiles_hlo(tmp_path):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def step_fn(x):
        with jax.named_scope("lpt.dedup"):
            y = jnp.sort(x.reshape(-1))
        return (x @ x).sum() + y[0]

    x = jnp.ones((64, 64))
    step_fn(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    step_fn(x).block_until_ready()
    jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)[0]
    # The metadata plane may also hold modules of earlier tests' programs.
    paths = [p for m, names in tr.hlo_scopes(path).items() if m.startswith("jit_step_fn")
             for p in names.values()]
    assert any(tr.in_scope(p, "lpt.dedup") and "sort" in p for p in paths)
    assert "jit(step_fn)/dot_general" in paths


def test_an_op_takes_the_scope_of_the_module_running_it():
    scopes = {"jit_step_fn(7)": {"fusion.1": "jit(step_fn)/lpt.dedup/sort"},
              "jit_other(3)": {"fusion.1": "jit(other)/add"}}
    modules = [(0.0, 10.0, "jit_step_fn(7)"), (20.0, 30.0, "jit_other(3)")]
    starts = [m[0] for m in modules]
    assert tr._scope_of(scopes, modules, starts, "fusion.1", 5.0) == "jit(step_fn)/lpt.dedup/sort"
    assert tr._scope_of(scopes, modules, starts, "fusion.1", 25.0) == "jit(other)/add"
    assert tr._scope_of(scopes, modules, starts, "fusion.1", 15.0) is None  # between modules
    assert tr._scope_of(scopes, modules, starts, "fusion.9", 5.0) is None
    assert tr._scope_of(scopes, [], [], "fusion.1", 5.0) is None


def test_a_traced_cell_run_holds_the_trainers_spans(tiny_root):
    """A tiny cell traced on the CPU, as ``main`` runs it: the profile the
    runner leaves holds the trainer's spans inside the window (the CPU has
    no device plane, so there is nothing to reduce)."""
    import shutil
    import time

    import jax

    cell = harness.load_cell("tiny-criteo-train", tiny_root)
    run = harness.Run(cell=cell, seed=2**31 + 5, seconds=0.5, trace=True,
                      t_start=time.monotonic(), devices=jax.devices(),
                      peaks=harness.peaks_for("TPU v5 lite"))
    cell.runner().run(run)
    try:
        assert tr.reduce_dir(run.trace_dir) is None
        path = glob.glob(f"{run.trace_dir}/**/*.xplane.pb", recursive=True)[0]
        spans = tr.read_xplane(path).spans
    finally:
        shutil.rmtree(run.trace_dir, ignore_errors=True)
    (lo, hi), = [(s, e) for n, s, e in spans if n == tr.WINDOW]
    inside = {n for n, s, e in spans if lo <= s and e <= hi}
    assert {"bench.train_step", "train.step", "train.feed", "train.dispatch",
            "train.wait", "train.writeback"} <= inside
