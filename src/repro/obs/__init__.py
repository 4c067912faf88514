"""`repro.obs` — the single observability layer for the whole stack.

Four pieces, one contract:

* :mod:`repro.obs.counters` — a typed, namespaced counter/gauge registry
  every existing telemetry surface re-registers into (kernel fallback
  tallies, Engine/cache counters, fault-guard and retry counters), so CLI
  reports and bench artifacts read one ``to_json()`` schema instead of four.
* :mod:`repro.obs.trace` — host-side span tracing.  Spans wrap *host*
  boundaries only (trainer step with its ``train.feed``/``train.dispatch``
  children, the wait for the previous step (``train.wait``), cache
  write-back, Engine waves, storage tier events, checkpoint save/restore)
  and reach any running JAX profiler trace as annotations on the device
  trace's clock; the ALPT step's in-jit phases carry named scopes (``lpt.dedup``, ``alpt.step_size``).  Chrome-trace
  JSON export is ``--trace-out`` on both launch CLIs; device-sync fences
  run only at span edges and only while that export is enabled.
* :mod:`repro.obs.stats` — streaming quantile estimation (P²) behind the
  per-host step-time quantiles and serving p50/p95/p99 that the BENCH
  artifacts carry.
* :mod:`repro.obs.gate` — the perf-regression gate: compares BENCH_*.json
  artifacts against the committed ``BENCH_BASELINE.json`` and fails
  ``python -m repro.analysis`` the way a jaxpr contract violation does.

The hard contract: obs-on changes no jitted computation.  Spans never enter
traced code, and instrumented runs — Chrome export or profiler — are
bitwise-equal to uninstrumented ones (tests/test_obs.py).
"""
from __future__ import annotations

from repro.obs.counters import (  # noqa: F401
    Counter,
    Gauge,
    Registry,
    Snapshot,
    registry,
)
from repro.obs.trace import tracer  # noqa: F401
