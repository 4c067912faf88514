"""The PR-10 observability contracts (`repro.obs`).

* **Registry semantics** — typed counters/gauges with label tuples,
  snapshot/diff isolating one window, the stable ``repro/obs/v1`` schema,
  and loud kind/label mismatches.
* **Tracing** — span nesting lands in Chrome-trace complete events, the
  disabled path is a shared null context (no events, no allocation), and
  export round-trips through JSON.
* **Quantiles** — the P² estimator tracks numpy.percentile on thousands of
  samples and is exact below its marker count.
* **Bitwise parity (the hard contract)** — an instrumented run (tracer
  armed, every surface registering) produces bit-identical training state,
  losses, and Engine outputs to an uninstrumented run: spans never enter
  traced code.
* **Perf gate** — seeded baselines pass against their own artifacts and
  fail on synthetic regressions, missing cells, and missing artifacts.
* **Legacy schemas** — ``ops.fallback_stats()`` and
  ``EngineMetrics.to_json()`` keep their pre-registry keys bit-for-bit.
"""
import glob
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import methods
from repro.data.ctr_synth import CTRDatasetConfig, CTRSynthetic
from repro.kernels import ops
from repro.models.ctr import DCNConfig
from repro.obs import counters as obs_counters
from repro.obs import gate
from repro.obs.counters import Counter, Gauge, Registry
from repro.obs.stats import P2Quantile, StreamingQuantiles
from repro.obs.trace import Tracer, tracer
from repro.serving.ctr import CTREngine, CTRRequest
from repro.training.ctr_trainer import CTRTrainer, TrainerConfig

jax.config.update("jax_platform_name", "cpu")

OBS_DATA = CTRDatasetConfig(
    name="obs", n_fields=4, cardinalities=(13, 29, 7, 53),
    teacher_rank=2, seed=0,
)


@pytest.fixture(autouse=True)
def _quiet_tracer():
    """The tracer is process-global; never leak an armed one across tests."""
    tracer().disable()
    tracer().clear()
    yield
    tracer().disable()
    tracer().clear()


def _trainer(method="lpt", bits=8):
    spec = methods.EmbeddingSpec(
        method=method, n=sum(OBS_DATA.cardinalities), d=8, bits=bits,
        init_scale=0.05,
    )
    dcn = DCNConfig(n_fields=OBS_DATA.n_fields, emb_dim=8, cross_depth=1,
                    mlp_widths=(16,))
    return CTRTrainer(TrainerConfig(spec=spec, model="dcn", dcn=dcn))


# ---------------------------------------------------------------- registry


class TestRegistry:
    def test_counter_inc_and_value(self):
        reg = Registry()
        c = reg.counter("t.hits")
        c.inc()
        c.inc(4)
        assert c.value() == 5

    def test_counter_rejects_negative(self):
        c = Registry().counter("t.hits")
        with pytest.raises(ValueError, match="cannot decrease"):
            c.inc(-1)

    def test_labeled_cells(self):
        reg = Registry()
        c = reg.counter("t.fallbacks", labels=("op", "reason"))
        c.inc(1, "gather", "shape")
        c.inc(2, "gather", "shape")
        c.inc(1, "update", "forced")
        assert c.value("gather", "shape") == 3
        assert c.value("update", "forced") == 1
        assert c.value("gather", "nope") == 0

    def test_label_arity_checked(self):
        c = Registry().counter("t.x", labels=("op",))
        with pytest.raises(ValueError, match="takes labels"):
            c.inc(1, "a", "b")

    def test_gauge_last_value_wins(self):
        g = Registry().gauge("t.bytes")
        g.set(100)
        g.set(42)
        assert g.value() == 42

    def test_get_or_create_is_same_object(self):
        reg = Registry()
        assert reg.counter("t.a", labels=("x",)) is reg.counter(
            "t.a", labels=("x",))

    def test_kind_mismatch_raises(self):
        reg = Registry()
        reg.counter("t.a")
        with pytest.raises(TypeError, match="already registered as counter"):
            reg.gauge("t.a")

    def test_label_mismatch_raises(self):
        reg = Registry()
        reg.counter("t.a", labels=("x",))
        with pytest.raises(ValueError, match="labels"):
            reg.counter("t.a", labels=("y",))

    def test_snapshot_diff_isolates_window(self):
        reg = Registry()
        c = reg.counter("t.n", labels=("op",))
        g = reg.gauge("t.depth")
        c.inc(5, "a")
        g.set(3)
        before = reg.snapshot()
        c.inc(2, "a")
        c.inc(1, "b")
        g.set(9)
        delta = reg.snapshot().diff(before)
        assert delta.value("t.n", "a") == 2
        assert delta.value("t.n", "b") == 1
        assert delta.value("t.depth") == 9  # gauges keep the later value

    def test_snapshot_is_point_in_time(self):
        reg = Registry()
        c = reg.counter("t.n")
        c.inc()
        snap = reg.snapshot()
        c.inc(10)
        assert snap.value("t.n") == 1

    def test_to_json_schema(self):
        reg = Registry()
        reg.counter("t.plain").inc(7)
        reg.counter("t.labeled", labels=("op",)).inc(2, "gather")
        reg.gauge("t.depth").set(3)
        doc = reg.to_json()
        assert doc["schema"] == "repro/obs/v1"
        assert doc["counters"]["t.plain"] == 7
        assert doc["counters"]["t.labeled"] == [
            {"labels": {"op": "gather"}, "value": 2}
        ]
        assert doc["gauges"]["t.depth"] == 3
        json.dumps(doc)  # wire schema must actually serialize

    def test_reset_zeroes_but_keeps_registrations(self):
        reg = Registry()
        c = reg.counter("t.n")
        c.inc(5)
        reg.reset()
        assert c.value() == 0
        assert "t.n" in reg.names()

    def test_global_registry_shared(self):
        assert obs_counters.registry() is obs_counters.registry()


# ----------------------------------------------------------------- tracing


class TestTracer:
    def test_disabled_span_is_shared_null_cm(self):
        t = Tracer()
        assert t.span("a") is t.span("b")  # no per-call allocation
        with t.span("a"):
            pass
        assert t.events == []

    def test_span_nesting_chrome_events(self):
        t = Tracer()
        t.enable()
        with t.span("train.step", step=3):
            with t.span("train.writeback"):
                pass
        evs = t.events
        assert [e["name"] for e in evs] == ["train.writeback", "train.step"]
        inner, outer = evs
        assert outer["ph"] == "X" and inner["ph"] == "X"
        assert outer["cat"] == "train"
        assert outer["args"] == {"step": 3}
        # nesting: the inner complete event sits inside the outer's window
        assert outer["ts"] <= inner["ts"]
        assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-3

    def test_instant_and_async_events(self):
        t = Tracer()
        t.enable()
        t.async_begin("engine.request", 7, scenario="ctr")
        t.instant("train.straggler", step=5)
        t.async_end("engine.request", 7)
        phs = [e["ph"] for e in t.events]
        assert phs == ["b", "i", "e"]
        assert t.events[0]["id"] == 7

    def test_export_round_trips(self, tmp_path):
        t = Tracer()
        t.enable(str(tmp_path / "trace.json"))
        with t.span("ckpt.save", step=1):
            pass
        path = t.export()
        doc = json.loads(open(path).read())
        assert doc["displayTimeUnit"] == "ms"
        assert doc["traceEvents"][0]["name"] == "ckpt.save"

    def test_export_nowhere_is_none(self):
        assert Tracer().export() is None

    def test_fence_passthrough_when_disabled(self):
        t = Tracer()
        x = object()
        assert t.fence(x) is x
        assert t.fence(None) is None


# --------------------------------------------------------------- quantiles


class TestQuantiles:
    def test_exact_below_marker_count(self):
        p = P2Quantile(0.5)
        for v in (5.0, 1.0, 3.0):
            p.add(v)
        assert p.value() == 3.0  # exact median of a tiny sample

    def test_empty_is_nan(self):
        assert np.isnan(P2Quantile(0.5).value())

    @pytest.mark.parametrize("q", [0.5, 0.95, 0.99])
    def test_tracks_numpy_percentile(self, q):
        rng = np.random.RandomState(0)
        xs = rng.lognormal(mean=3.0, sigma=0.7, size=5000)
        est = P2Quantile(q)
        for x in xs:
            est.add(float(x))
        exact = float(np.percentile(xs, q * 100))
        spread = float(np.percentile(xs, 99) - np.percentile(xs, 1))
        assert abs(est.value() - exact) <= 0.05 * spread

    def test_streaming_summary_json(self):
        s = StreamingQuantiles()
        assert s.to_json() == {"count": 0}
        for v in range(1, 101):
            s.add(float(v))
        doc = s.to_json()
        assert doc["count"] == 100
        assert doc["min"] == 1.0 and doc["max"] == 100.0
        assert doc["mean"] == pytest.approx(50.5)
        assert doc["p50"] == pytest.approx(50.5, rel=0.1)
        assert doc["p95"] == pytest.approx(95.0, rel=0.1)
        assert set(doc) == {"count", "mean", "min", "max",
                            "p50", "p95", "p99"}


# ---------------------------------------------------- bitwise parity (hard)


def _train_losses_and_state(method, steps=4):
    data = CTRSynthetic(OBS_DATA)
    tr = _trainer(method)
    state = tr.init_state()
    losses = []
    for i in range(steps):
        ids, labels = data.batch("train", i, 32)
        state, m = tr.train_step(state, ids, labels)
        losses.append(np.asarray(m["loss"]).tobytes())
    exported = jax.tree.leaves(tr.export_state(state))
    return losses, [np.asarray(x).tobytes() for x in exported]


@pytest.mark.parametrize("method,sink", [
    pytest.param("lpt", "chrome", id="lpt"),
    pytest.param("alpt", "chrome", id="alpt"),
    pytest.param("alpt", "profiler", id="alpt-profiler"),
])
def test_instrumented_training_bitwise_equal(method, sink, tmp_path):
    base_losses, base_state = _train_losses_and_state(method)
    if sink == "chrome":
        tracer().enable()
        try:
            inst_losses, inst_state = _train_losses_and_state(method)
        finally:
            tracer().disable()
            tracer().clear()
    else:
        with jax.profiler.trace(str(tmp_path)):
            inst_losses, inst_state = _train_losses_and_state(method)
    assert inst_losses == base_losses
    assert inst_state == base_state


# ------------------------------------- profiler spans, scopes, no syncs


def _profile_planes(trace_dir):
    from jax.profiler import ProfileData

    path = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)[0]
    return ProfileData.from_file(path).planes


def _host_spans(trace_dir) -> dict:
    """{name: [(start_ns, end_ns)]} of the host planes' events."""
    out = {}
    for plane in _profile_planes(trace_dir):
        if plane.name.startswith("/host"):
            for line in plane.lines:
                for e in line.events:
                    out.setdefault(e.name, []).append((e.start_ns, e.end_ns))
    return out


def test_profiler_state_check_is_pinned(tmp_path):
    """repro.obs.trace asks a private jaxlib call whether a profiler records;
    this pins that call's meaning across jax upgrades."""
    from jax._src.lib import _profiler

    from repro.obs.trace import profiling

    assert _profiler.TraceMe.is_enabled() is False and not profiling()
    with jax.profiler.trace(str(tmp_path)):
        assert _profiler.TraceMe.is_enabled() is True and profiling()
    assert not profiling()


def test_span_is_a_profiler_annotation_that_never_fences(tmp_path, monkeypatch):
    t = Tracer()
    fenced = []
    monkeypatch.setattr(jax, "block_until_ready", lambda v: fenced.append(v) or v)
    with jax.profiler.trace(str(tmp_path)):
        span = t.span("train.refresh", step=3)
        assert isinstance(span, jax.profiler.TraceAnnotation)
        with span:
            t.fence(np.ones(2))
    assert fenced == [] and t.events == []
    assert t.span("a") is t.span("b")  # the shared null context again
    assert "train.refresh" in _host_spans(str(tmp_path))


def test_enabled_span_also_reaches_the_profiler(tmp_path):
    t = Tracer()
    t.enable()
    with jax.profiler.trace(str(tmp_path)):
        with t.span("ckpt.save", step=1):
            pass
    assert [e["name"] for e in t.events] == ["ckpt.save"]
    assert "ckpt.save" in _host_spans(str(tmp_path))


def test_trainer_spans_reach_the_profiler(tmp_path):
    data = CTRSynthetic(OBS_DATA)
    tr = _trainer("alpt")
    state, _ = tr.train_step(tr.init_state(), *data.batch("train", 0, 32))
    with jax.profiler.trace(str(tmp_path)):
        state, m = tr.train_step(state, *data.batch("train", 1, 32))
        jax.block_until_ready(m)
    spans = _host_spans(str(tmp_path))
    for name in ("train.step", "train.feed", "train.dispatch", "train.wait",
                 "train.writeback"):
        assert len(spans.get(name, [])) == 1, (name, sorted(spans))
    (s0, s1), = spans["train.step"]
    for child in ("train.feed", "train.dispatch"):
        (c0, c1), = spans[child]
        assert s0 <= c0 <= c1 <= s1
    assert spans["train.feed"][0][1] <= spans["train.dispatch"][0][0]
    # The wait for the previous step is host idling, not the step's host
    # work: it follows train.step instead of nesting in it.
    assert s1 <= spans["train.wait"][0][0] <= spans["train.writeback"][0][0]


def test_host_runs_at_most_one_step_ahead(monkeypatch):
    """Each step waits for the one before it and never for itself, so at
    most two steps hold device buffers."""
    from repro.training import run_ahead

    data = CTRSynthetic(OBS_DATA)
    tr = _trainer("alpt")
    state = tr.init_state()
    waited, dispatched = [], []
    monkeypatch.setattr(run_ahead.jax, "block_until_ready",
                        lambda v: waited.append(v) or v)
    for i in range(3):
        state, m = tr.train_step(state, *data.batch("train", i, 32))
        dispatched.append(m)
    assert [id(w) for w in waited] == [id(m) for m in dispatched[:2]]


@pytest.mark.parametrize("method", ["lpt", "alpt"])
def test_train_step_reads_nothing_from_the_device(method, monkeypatch):
    """With the tracer off a step only copies its batch in and dispatches:
    no device-to-host read, so the host can run ahead of the chip."""
    from jax._src import array as jarray

    data = CTRSynthetic(OBS_DATA)
    tr = _trainer(method)
    state, _ = tr.train_step(tr.init_state(), *data.batch("train", 0, 32))
    jax.block_until_ready(state)
    value = jarray.ArrayImpl.__dict__["_value"]
    reads = []

    def counting(self):
        reads.append(self.shape)
        return value.fget(self)

    monkeypatch.setattr(jarray.ArrayImpl, "_value", property(counting))
    for i in (1, 2):
        state, m = tr.train_step(state, *data.batch("train", i, 32))
    monkeypatch.undo()
    assert reads == []
    assert np.isfinite(float(m["loss"]))


@pytest.mark.parametrize("bits", [8, 4])
def test_fused_step_carries_phase_scopes(bits):
    """The ALPT step's in-jit phases are named in the compiled step's op
    metadata (int8 and packed 4-bit), and no op is in both."""
    data = CTRSynthetic(OBS_DATA)
    tr = _trainer("alpt", bits=bits)
    ids, labels = data.batch("train", 0, 32)
    hlo = tr._train_step.lower(tr.init_state(), jnp.asarray(ids),
                               jnp.asarray(labels)).compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', hlo))
    dedup = {n for n in names if "/lpt.dedup/" in n}
    step_size = {n for n in names if "/alpt.step_size/" in n}
    assert any("sort" in n for n in dedup)  # jnp.unique's sort
    assert any("scatter" in n for n in dedup)  # the gradient segment-sum
    assert any("while" in n for n in step_size)  # the fenced Delta pass
    assert not dedup & step_size


def _engine_probs():
    data = CTRSynthetic(OBS_DATA)
    tr = _trainer("alpt")
    state = tr.init_state()
    for i in range(2):
        ids, labels = data.batch("train", i, 32)
        state, _ = tr.train_step(state, ids, labels)
    engine = CTREngine.from_state(state, tr.cfg, batch=8)
    ids, _ = data.batch("test", 0, 16)
    rids = [engine.submit(CTRRequest(ids=row)) for row in ids]
    done = engine.run()
    return [done[r]["prob"] for r in rids]


def test_instrumented_engine_bitwise_equal():
    base = _engine_probs()
    tracer().enable()
    try:
        inst = _engine_probs()
    finally:
        tracer().disable()
        tracer().clear()
    assert inst == base  # exact float equality, not approx


def test_engine_latency_quantiles_reported():
    tracer().clear()
    _ = _engine_probs  # parity helper reused for a metrics-shape check
    data = CTRSynthetic(OBS_DATA)
    tr = _trainer("alpt")
    state = tr.init_state()
    engine = CTREngine.from_state(state, tr.cfg, batch=8)
    ids, _ = data.batch("test", 0, 16)
    for row in ids:
        engine.submit(CTRRequest(ids=row))
    engine.run()
    m = engine.metrics()
    assert m.latency_us is not None
    for which in ("wave", "request"):
        q = m.latency_us[which]
        assert q["count"] > 0
        assert q["p50"] <= q["p95"] <= q["p99"]
    # the serving cells' BENCH spread picks the key up automatically
    assert "latency_us" in dict(m)


# ------------------------------------------------------------------- gate


def _e2e_doc(us=100.0, packed=512, fallbacks=0):
    return {
        "schema": "repro/e2e_step_bench/v1",
        "cells": {
            "ctr/bits8/kernels_on": {
                "us_per_step": us,
                "packed_bytes": packed,
                "shape_fallbacks": fallbacks,
                "table_rows": 128,  # ungated: informational
            },
        },
        "obs_overhead": {"overhead_frac": 0.01},
    }


class TestGate:
    def test_seed_then_self_compare_passes(self):
        doc = _e2e_doc()
        base = gate.seed_baseline({"BENCH_X.json": doc})
        assert base["schema"] == gate.SCHEMA
        assert gate.compare(base, {"BENCH_X.json": doc}) == []

    def test_time_regression_fails_past_tolerance(self):
        base = gate.seed_baseline({"BENCH_X.json": _e2e_doc(us=100.0)})
        # default time tol 1.5 => allowed 250us
        assert gate.compare(base, {"BENCH_X.json": _e2e_doc(us=240.0)}) == []
        bad = gate.compare(base, {"BENCH_X.json": _e2e_doc(us=260.0)})
        assert len(bad) == 1 and bad[0].metric == "us_per_step"

    def test_bytes_and_count_are_exact(self):
        base = gate.seed_baseline({"BENCH_X.json": _e2e_doc()})
        grown = gate.compare(base, {"BENCH_X.json": _e2e_doc(packed=513)})
        assert [f.metric for f in grown] == ["packed_bytes"]
        fell = gate.compare(base, {"BENCH_X.json": _e2e_doc(fallbacks=1)})
        assert [f.metric for f in fell] == ["shape_fallbacks"]

    def test_missing_cell_and_artifact_are_findings(self):
        base = gate.seed_baseline({"BENCH_X.json": _e2e_doc()})
        none = gate.compare(base, {})
        assert any("missing" in f.message for f in none)
        empty = gate.compare(base, {"BENCH_X.json": {"cells": {}}})
        assert any(f.cell == "ctr/bits8/kernels_on" for f in empty)

    def test_fresh_extra_cells_pass(self):
        base = gate.seed_baseline({"BENCH_X.json": _e2e_doc()})
        doc = _e2e_doc()
        doc["cells"]["ctr/bits4/kernels_on"] = {"us_per_step": 1e9}
        assert gate.compare(base, {"BENCH_X.json": doc}) == []

    def test_serving_list_cells_named_and_rate_gated(self):
        doc = {"cells": [{
            "scenario": "ctr", "embedding_method": "alpt",
            "cache_rows": 409, "cold_tier": True,
            "us_per_request": 50.0, "cache_hit_rate": 0.9,
            "latency_us": {"wave": {"p95": 1000.0}},
        }]}
        base = gate.seed_baseline({"BENCH_Y.json": doc})
        cells = base["benches"]["BENCH_Y.json"]["cells"]
        assert list(cells) == ["ctr/alpt/cold"]
        assert "latency_us.wave.p95" in cells["ctr/alpt/cold"]
        worse = {"cells": [dict(doc["cells"][0], cache_hit_rate=0.7)]}
        bad = gate.compare(base, {"BENCH_Y.json": worse})
        assert [f.metric for f in bad] == ["cache_hit_rate"]

    def test_perf_layer_wires_into_analysis(self, tmp_path):
        from repro.analysis.perf import run_perf_checks

        doc = _e2e_doc()
        (tmp_path / "BENCH_X.json").write_text(json.dumps(doc))
        base = gate.seed_baseline({"BENCH_X.json": doc})
        (tmp_path / "BENCH_BASELINE.json").write_text(json.dumps(base))
        assert run_perf_checks(root=tmp_path) == []
        (tmp_path / "BENCH_X.json").write_text(json.dumps(_e2e_doc(us=1e6)))
        report = tmp_path / "report.json"
        found = run_perf_checks(root=tmp_path, report_path=report)
        assert found and all(f.rule == "perf-regression" for f in found)
        assert json.loads(report.read_text())  # CI diff artifact written

    def test_no_baseline_means_pass(self, tmp_path):
        from repro.analysis.perf import run_perf_checks

        assert run_perf_checks(root=tmp_path) == []

    def test_committed_baseline_holds(self):
        """The repo's own committed baseline passes against its artifacts
        for everything deterministic (time cells are machine-relative, so
        they are exempt here — CI runs the full gate on its own numbers)."""
        from repro.analysis.lint import REPO_ROOT

        path = REPO_ROOT / "BENCH_BASELINE.json"
        if not path.exists():
            pytest.skip("no committed baseline")
        baseline = gate.load_baseline(path)
        fresh = gate.load_fresh(REPO_ROOT, baseline)
        hard = [
            f for f in gate.compare(baseline, fresh)
            if gate.classify(f.metric) not in ("time", "frac")
        ]
        assert hard == [], hard


# ----------------------------------------------------------- legacy shims


class TestLegacySchemas:
    def test_fallback_stats_keys(self):
        ops.reset_fallback_stats()
        stats = ops.fallback_stats()
        assert set(stats) == {"kernel_calls", "fallbacks", "total_fallbacks"}
        assert stats["total_fallbacks"] == 0
        assert stats["fallbacks"] == []

    def test_fallback_stats_reads_registry(self):
        ops.reset_fallback_stats()
        reg = obs_counters.registry()
        reg.counter("kernels.fallbacks",
                    labels=("op", "shape", "reason")).inc(
                        2, "dequant_gather", "(8, 8)", "test-reason")
        stats = ops.fallback_stats()
        assert stats["total_fallbacks"] == 2
        assert stats["fallbacks"] == [{
            "op": "dequant_gather", "shape": "(8, 8)",
            "reason": "test-reason", "count": 2,
        }]
        ops.reset_fallback_stats()

    def test_engine_metrics_legacy_keys(self):
        data = CTRSynthetic(OBS_DATA)
        tr = _trainer("alpt")
        engine = CTREngine.from_state(tr.init_state(), tr.cfg, batch=8)
        ids, _ = data.batch("test", 0, 8)
        for row in ids:
            engine.submit(CTRRequest(ids=row))
        engine.run()
        doc = engine.metrics().to_json()
        # the pre-registry schema, pinned: renames/removals break consumers
        for key in (
            "scenario", "embedding_method", "requests_submitted",
            "requests_completed", "steps", "wall_s",
            "resident_embedding_bytes", "embedding_code_bytes",
            "embedding_scale_bytes", "int8_resident", "kernel_fallbacks",
            "served_degraded", "deadline_misses", "wave_retries",
            "retry_failures", "us_per_request",
        ):
            assert key in doc, key
        assert doc["requests_completed"] == 8
        json.dumps(doc)
