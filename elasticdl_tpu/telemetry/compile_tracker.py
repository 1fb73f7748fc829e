"""Process-wide XLA compile counting (shape-canonical batching's gauge).

The whole point of canonicalizing batch shapes
(docs/designs/shape_canonicalization.md) is that the steady-state step
stream executes exactly ONE train-step program (plus one stacked-scan
variant) — so the number of backend compiles is the regression signal
worth watching.  This module makes it observable:

- a **counter**: every XLA backend compile in this process increments a
  process-wide total (:func:`compile_count`); the master mirrors it —
  plus the ``compile_count`` exec counters lockstep chiefs ship with
  task reports — onto ``/metrics`` as ``elasticdl_compile_total``.
- a **span**: each compile lands in the trace timeline as a ``compile``
  span (duration = the backend compile), so ``trace analyze``'s
  ``warmup_compile`` reform phase shows measured compile time instead of
  inferring it from the uncovered remainder.

Mechanism: :func:`install` registers a ``jax.monitoring`` duration
listener for the ``/jax/core/compile/backend_compile_duration`` event
(one firing per program handed to the backend — in-memory jit cache
hits and traces don't fire it; a persistent-cache hit does, with the
retrieval time as its duration).  The same listener sums the two stages
before it, ``jaxpr_trace_duration`` and ``jaxpr_to_mlir_module_duration``
(:func:`trace_secs_total`, :func:`lower_secs_total`): what a process
pays before its first step even when every program comes from the
persistent cache.

Install is idempotent and the disabled cost is zero: nothing here sits
on the step path — compiles are the rare event being counted.
"""

from __future__ import annotations

import threading
import time

# the exec-counter key lockstep chiefs report compile DELTAS under
# (summed by the TaskDispatcher, mirrored by MasterTelemetry._collect)
COMPILE_COUNT_KEY = "compile_count"

_BACKEND_COMPILE_SUFFIX = "backend_compile_duration"
# the two stages before the backend's, which fire for every program
# traced and lowered — a persistent-cache hit included: tracing the
# Python into a jaxpr, and lowering the jaxpr to an MLIR module
_TRACE_SUFFIX = "jaxpr_trace_duration"
_LOWER_SUFFIX = "jaxpr_to_mlir_module_duration"

_lock = threading.Lock()
_count = 0
_secs_total = 0.0
_trace_secs_total = 0.0
_lower_secs_total = 0.0
_installed = False


def _record(duration_secs: float):
    global _count, _secs_total
    with _lock:
        _count += 1
        _secs_total += max(0.0, float(duration_secs))
    # retroactive trace span: recorded on whatever thread compiled; the
    # tracer is thread-safe and lifecycle spans are never sampled away
    from elasticdl_tpu.telemetry import tracing

    tracer = tracing.get_tracer()
    if tracer is not None:
        now = time.monotonic()
        tracer.record_span(
            tracing.SPAN_COMPILE, now - max(0.0, float(duration_secs)), now
        )


def _on_event_duration(event: str, duration_secs: float, **_kwargs):
    global _trace_secs_total, _lower_secs_total
    if event.endswith(_BACKEND_COMPILE_SUFFIX):
        _record(duration_secs)
    elif event.endswith(_TRACE_SUFFIX):
        with _lock:
            _trace_secs_total += max(0.0, float(duration_secs))
    elif event.endswith(_LOWER_SUFFIX):
        with _lock:
            _lower_secs_total += max(0.0, float(duration_secs))


def install():
    """Register the compile listener, once per process."""
    global _installed
    with _lock:
        if _installed:
            return
        _installed = True
    from jax import monitoring

    monitoring.register_event_duration_secs_listener(_on_event_duration)


def compile_count() -> int:
    """XLA programs compiled by THIS process since install (0 before)."""
    return _count


def compile_secs_total() -> float:
    """Total seconds this process spent in backend compiles."""
    return _secs_total


def trace_secs_total() -> float:
    """Total seconds this process spent tracing Python into jaxprs."""
    return _trace_secs_total


def lower_secs_total() -> float:
    """Total seconds this process spent lowering jaxprs to MLIR."""
    return _lower_secs_total


class ExecCounterReporter:
    """THE one implementation of shipping compile deltas with task
    reports (both worker runtimes use it, so the contract cannot drift):
    :meth:`attach` stages the unreported delta into the report's exec
    counters, and the watermark advances only in :meth:`commit` AFTER
    the report RPC succeeded — a failed report re-ships the delta with
    the next one instead of silently dropping it."""

    def __init__(self):
        self._reported = compile_count()

    def attach(self, counters: dict) -> int:
        """Stage the pending delta under ``COMPILE_COUNT_KEY`` (when
        nonzero); returns the total to pass to :meth:`commit` once the
        report went through."""
        total = compile_count()
        delta = total - self._reported
        if delta > 0:
            counters[COMPILE_COUNT_KEY] = delta
        return total

    def commit(self, total: int):
        self._reported = max(self._reported, total)


def _reset_for_tests():
    """Zero the totals (tests simulating a fresh process / generation;
    the listener registration itself is process-permanent)."""
    global _count, _secs_total, _trace_secs_total, _lower_secs_total
    with _lock:
        _count = 0
        _secs_total = 0.0
        _trace_secs_total = 0.0
        _lower_secs_total = 0.0
