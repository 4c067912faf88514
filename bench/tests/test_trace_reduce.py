"""The trace reduction: interval arithmetic by hand, a recorded TPU trace,
and reading a real profiler file."""
import glob
import json

import pytest

import harness
import trace_reduce as tr

DATA = harness.BENCH / "tests" / "data"


def _trace():
    # Window 0..100 ns; device ops at 10-30 and 20-40 (overlapping) and 70-80;
    # host: a step span 0-50, a wave span 60-100.
    return tr.Trace(
        devices={"/device:TPU:0": [("a", 10, 30), ("b", 20, 40), ("a", 70, 80),
                                   ("c", 150, 160)]},
        spans=[("bench.window", 0, 100), ("bench.train_step", 0, 50),
               ("bench.engine_wave", 60, 100)])


def test_busy_ops_and_gaps_by_hand():
    red = tr.reduce(_trace())
    assert red.window_s == pytest.approx(100e-9)
    assert red.busy_s == pytest.approx(40e-9)  # 10-40 and 70-80
    assert red.ops == pytest.approx({"a": 30e-9, "b": 20e-9})  # "c" is outside
    gaps = sorted(red.gaps, key=lambda g: g[1])
    # Idle 0-10 (inside the step span), 40-70 (between spans), 80-100 (wave).
    assert [(n, round(s * 1e9)) for n, s in gaps] == [
        ("bench.train_step", 10), ("bench.engine_wave", 20), ("bench.window", 30)]
    assert red.span_busy("bench.engine_wave") == [pytest.approx((40e-9, 10e-9))]
    assert red.breakdown()["device_ops"][0] == ["a", pytest.approx(30e-9)]


def test_two_devices_are_averaged():
    t = _trace()
    t.devices["/device:TPU:1"] = [("a", 0, 100)]
    red = tr.reduce(t)
    assert red.busy_s == pytest.approx((40e-9 + 100e-9) / 2)
    assert red.ops["a"] == pytest.approx((30e-9 + 100e-9) / 2)


def test_no_device_op_reads_nothing():
    t = _trace()
    t.devices = {"/device:TPU:0": [("c", 150, 160)]}
    assert tr.reduce(t) is None
    t.spans = [s for s in t.spans if s[0] != "bench.window"]
    assert tr.reduce(t) is None


@pytest.mark.parametrize("path", sorted(glob.glob(str(DATA / "trace_*.json"))))
def test_recorded_tpu_trace(path):
    with open(path) as f:
        rec = json.load(f)
    red = tr.reduce(tr.Trace.from_json(rec["trace"]))
    want = rec["expect"]
    assert red.window_s == pytest.approx(want["window_s"], rel=1e-9)
    assert red.busy_s == pytest.approx(want["busy_s"], rel=1e-9)
    assert 0 < red.busy_s <= red.window_s
    top = red.breakdown()["device_ops"][0]
    assert top[0] == want["top_op"] and top[1] == pytest.approx(want["top_op_s"], rel=1e-9)


def test_reads_a_profiler_file(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)[0]
    t = tr.read_xplane(path)
    assert [s[0] for s in t.spans] == ["bench.window"]
    assert t.devices == {}  # the CPU backend has no device plane
