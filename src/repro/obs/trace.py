"""Host-side span tracing: into a running JAX profiler trace, and to
Chrome-trace JSON.

Spans wrap **host** boundaries only — they never enter traced code, so an
instrumented run executes the exact same jitted programs as an
uninstrumented one (the bitwise-parity contract in tests/test_obs.py).  The
span catalog:

    train.step        the host's work on one fused trainer step (fenced at
                      the span edge when the Chrome export is on), parent of:
      train.feed        the batch's host-to-device copies
      train.dispatch    the jitted step's call (returns once enqueued)
    train.wait        the wait for the previous step, which bounds the
                      host's run-ahead to one step
    train.writeback   host cache-policy write-back after the step
    train.refresh     host-refresh phase (methods that rebuild host state)
    ckpt.save         checkpoint write (atomic rename included)
    ckpt.restore      checkpoint read + verify
    engine.wave       one Engine scheduler step (prefill/score/decode
                      children where the frontend separates them)
    engine.prefill    LM prefill of one admitted request
    engine.decode     LM decode step across active slots
    engine.score      CTR wave scoring
    storage.cold.fetch     demand host->device row fetch
    storage.cold.prefetch  staging of the next wave's gather
    storage.writeback      dirty hot-row write-back to the backing tier

plus per-request async spans (``request/<rid>``) from submit to finish.

**Two sinks, one span.**  Whenever a JAX profiler session is recording
(``jax.profiler.trace`` / ``start_trace``, e.g. a benchmark's traced run),
every span is also a ``jax.profiler.TraceAnnotation``: it lands on the host
plane of the profile, on the same clock as the device's ops.  The fused
step's in-jit phases are not host-separable; they carry ``jax.named_scope``
names in their op metadata instead (``lpt.dedup``: id de-duplication and
the gradient segment-sum; ``alpt.step_size``: ALPT's step-size gradient
pass and update).  ``enable(path)`` arms the second sink, the Chrome-trace
JSON that ``--trace-out`` writes on both launch CLIs.

Device-sync fences run **only at span edges and only while the Chrome
export is enabled** (:meth:`Tracer.fence`): profiler spans never fence, so
a profiled run keeps the dispatch pattern of an unprofiled one.  The fence
call is the repo's single reviewed exception to the ``no-host-sync`` lint
rule (analysis-suppressions.txt).

Disabled-path cost: with neither sink on, ``tracer().span(...)`` returns a
shared null context manager — no allocation, no clock read, no device
read — after one C++ check of the profiler's state.  Callers keep span
arguments host-side for the same reason: an argument is evaluated before
``span()`` can decline it.

Export is the Chrome trace-event JSON format: load the file in
``chrome://tracing`` or https://ui.perfetto.dev.
"""
from __future__ import annotations

import contextlib
import json
import os
import time
from typing import Any

from jax._src.lib import _profiler  # private; pinned by tests/test_obs.py
from jax.profiler import TraceAnnotation

_NULL_CM = contextlib.nullcontext()


def profiling() -> bool:
    """Whether a JAX profiler session is recording (one C++ call)."""
    return _profiler.TraceMe.is_enabled()


class _Span:
    """Context manager for one complete ('X') trace event, also annotated
    into the profiler's trace while one records."""

    __slots__ = ("_tracer", "_name", "_args", "_t0", "_annotation")

    def __init__(self, tracer: "Tracer", name: str, args: dict) -> None:
        self._tracer = tracer
        self._name = name
        self._args = args

    def __enter__(self) -> "_Span":
        self._annotation = TraceAnnotation(self._name, **self._args) if profiling() else None
        if self._annotation is not None:
            self._annotation.__enter__()
        self._t0 = time.perf_counter_ns()
        self._tracer._depth += 1
        return self

    def __exit__(self, *exc) -> None:
        t1 = time.perf_counter_ns()
        if self._annotation is not None:
            self._annotation.__exit__(*exc)
        tr = self._tracer
        tr._depth -= 1
        tr._events.append({
            "ph": "X",
            "name": self._name,
            "cat": self._name.split(".", 1)[0],
            "ts": (self._t0 - tr._epoch_ns) / 1e3,
            "dur": (t1 - self._t0) / 1e3,
            "pid": tr._pid,
            "tid": 0,
            **({"args": self._args} if self._args else {}),
        })


class Tracer:
    """Span collector; a process-global instance lives behind :func:`tracer`.

    Disabled by default; its spans still reach a recording profiler.
    ``enable(path)`` arms the Chrome-trace collector and records the export
    path; ``export()`` writes the JSON (called by the launch CLIs at end of
    run, or explicitly).
    """

    def __init__(self) -> None:
        self.enabled = False
        self.out_path: str | None = None
        self._events: list[dict] = []
        self._epoch_ns = time.perf_counter_ns()
        self._pid = os.getpid()
        self._depth = 0

    # ------------------------------------------------------------ control

    def enable(self, out_path: str | None = None) -> None:
        self.enabled = True
        self.out_path = out_path
        self._epoch_ns = time.perf_counter_ns()

    def disable(self) -> None:
        self.enabled = False

    def clear(self) -> None:
        self._events.clear()
        self._epoch_ns = time.perf_counter_ns()

    @property
    def events(self) -> list[dict]:
        return list(self._events)

    # ------------------------------------------------------------ spans

    def span(self, name: str, **args: Any):
        """Context manager timing one host-side phase (nesting = call nesting
        in the exported trace and in the profiler's).  A profiler-only span
        is a bare ``TraceAnnotation``; with neither sink on, the shared null
        context."""
        if self.enabled:
            return _Span(self, name, args)
        if profiling():
            return TraceAnnotation(name, **args)
        return _NULL_CM

    def instant(self, name: str, **args: Any) -> None:
        """Zero-duration marker ('i') — fault injections, straggler flags."""
        if not self.enabled:
            return
        self._events.append({
            "ph": "i", "s": "t",
            "name": name,
            "cat": name.split(".", 1)[0],
            "ts": (time.perf_counter_ns() - self._epoch_ns) / 1e3,
            "pid": self._pid, "tid": 0,
            **({"args": args} if args else {}),
        })

    def async_begin(self, name: str, aid: int, **args: Any) -> None:
        """Open one async span ('b') — e.g. a request entering the queue."""
        if not self.enabled:
            return
        self._events.append({
            "ph": "b", "cat": name.split(".", 1)[0],
            "name": name, "id": aid,
            "ts": (time.perf_counter_ns() - self._epoch_ns) / 1e3,
            "pid": self._pid, "tid": 0,
            **({"args": args} if args else {}),
        })

    def async_end(self, name: str, aid: int, **args: Any) -> None:
        if not self.enabled:
            return
        self._events.append({
            "ph": "e", "cat": name.split(".", 1)[0],
            "name": name, "id": aid,
            "ts": (time.perf_counter_ns() - self._epoch_ns) / 1e3,
            "pid": self._pid, "tid": 0,
            **({"args": args} if args else {}),
        })

    # ------------------------------------------------------------ fences

    def fence(self, value: Any) -> Any:
        """Device-sync fence at a span *edge*.

        While the Chrome export is enabled, block until ``value``'s device
        work is done so the enclosing span measures compute, not dispatch.
        Otherwise — a profiler recording included, whose device plane
        already times the compute — a pure pass-through: no sync, dispatch
        stays async.  This is the
        one reviewed ``no-host-sync`` exception (analysis-suppressions.txt):
        it is host code at a span boundary, never inside a step function.
        """
        if self.enabled and value is not None:
            import jax

            jax.block_until_ready(value)
        return value

    # ------------------------------------------------------------ export

    def to_chrome_trace(self) -> dict:
        return {"traceEvents": list(self._events), "displayTimeUnit": "ms"}

    def export(self, path: str | None = None) -> str | None:
        """Write the Chrome-trace JSON; returns the path written (or None
        when there is nowhere to write)."""
        path = path or self.out_path
        if path is None:
            return None
        with open(path, "w") as f:
            json.dump(self.to_chrome_trace(), f)
        return path


_TRACER = Tracer()


def tracer() -> Tracer:
    """The process-global tracer every instrumented surface shares."""
    return _TRACER
