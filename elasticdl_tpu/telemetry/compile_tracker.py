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

The program store (parallel/program_store.py) loads a trainer's programs
AHEAD of trace and lower, so none of the three events fires for them.  It
reports here instead: ``program_store_hits`` / ``_misses`` / ``_rejects``
(an entry found and refused), and each hit as one program handed to the
backend — :func:`compile_count` and the ``compile`` span keep their
meaning, with the load's duration, exactly as for a persistent-cache hit
— plus a ``program_load`` span of its own.

Install is idempotent and the disabled cost is zero: nothing here sits
on the step path — compiles are the rare event being counted.
"""

from __future__ import annotations

import threading
import time

# the exec-counter key lockstep chiefs report compile DELTAS under
# (summed by the TaskDispatcher, mirrored by MasterTelemetry._collect)
COMPILE_COUNT_KEY = "compile_count"
# the program store's counters ride the same reports, key -> reader
PROGRAM_STORE_HITS_KEY = "program_store_hits"
PROGRAM_STORE_MISSES_KEY = "program_store_misses"
PROGRAM_STORE_REJECTS_KEY = "program_store_rejects"

_BACKEND_COMPILE_SUFFIX = "backend_compile_duration"
# the two stages before the backend's, which fire for every program
# traced and lowered — a persistent-cache hit included: tracing the
# Python into a jaxpr, and lowering the jaxpr to an MLIR module
_TRACE_SUFFIX = "jaxpr_trace_duration"
_LOWER_SUFFIX = "jaxpr_to_mlir_module_duration"
# fires when the persistent compile cache serves a compile request
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"

_lock = threading.Lock()
_count = 0
_secs_total = 0.0
_trace_secs_total = 0.0
_lower_secs_total = 0.0
_cache_hits = 0
_store_hits = 0
_store_misses = 0
_store_rejects = 0
_installed = False


def record_program_load(duration_secs: float):
    """One program taken from the program store in ``duration_secs``
    (read, decompress, hand to the backend)."""
    global _store_hits
    with _lock:
        _store_hits += 1
    _record(duration_secs, program_load=True)


def record_program_store_miss():
    global _store_misses
    with _lock:
        _store_misses += 1


def record_program_store_reject():
    global _store_rejects
    with _lock:
        _store_rejects += 1


def _record(duration_secs: float, program_load: bool = False):
    global _count, _secs_total
    duration_secs = max(0.0, float(duration_secs))
    with _lock:
        _count += 1
        _secs_total += duration_secs
    # retroactive trace span: recorded on whatever thread compiled; the
    # tracer is thread-safe and lifecycle spans are never sampled away
    from elasticdl_tpu.telemetry import tracing

    tracer = tracing.get_tracer()
    if tracer is not None:
        now = time.monotonic()
        tracer.record_span(tracing.SPAN_COMPILE, now - duration_secs, now)
        if program_load:
            tracer.record_span(
                tracing.SPAN_PROGRAM_LOAD, now - duration_secs, now
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


def _on_event(event: str, **_kwargs):
    global _cache_hits
    if event == _CACHE_HIT_EVENT:
        with _lock:
            _cache_hits += 1


def install():
    """Register the compile listeners, once per process."""
    global _installed
    with _lock:
        if _installed:
            return
        _installed = True
    from jax import monitoring

    monitoring.register_event_duration_secs_listener(_on_event_duration)
    monitoring.register_event_listener(_on_event)


def compile_count() -> int:
    """XLA programs compiled by THIS process since install (0 before)."""
    return _count


def compile_cache_hits() -> int:
    """Compile requests of THIS process the persistent compile cache
    served (their executables were loaded, not compiled)."""
    return _cache_hits


def compile_secs_total() -> float:
    """Total seconds this process spent in backend compiles."""
    return _secs_total


def trace_secs_total() -> float:
    """Total seconds this process spent tracing Python into jaxprs."""
    return _trace_secs_total


def lower_secs_total() -> float:
    """Total seconds this process spent lowering jaxprs to MLIR."""
    return _lower_secs_total


def program_store_hits() -> int:
    """Programs THIS process took from the program store."""
    return _store_hits


def program_store_misses() -> int:
    """Programs the store had no entry for (built, then stored)."""
    return _store_misses


def program_store_rejects() -> int:
    """Entries found and refused (built again, then stored)."""
    return _store_rejects


# what rides a task report, and how each total is read
EXEC_COUNTERS = {
    COMPILE_COUNT_KEY: compile_count,
    PROGRAM_STORE_HITS_KEY: program_store_hits,
    PROGRAM_STORE_MISSES_KEY: program_store_misses,
    PROGRAM_STORE_REJECTS_KEY: program_store_rejects,
}


class ExecCounterReporter:
    """THE one implementation of shipping compile deltas with task
    reports (both worker runtimes use it, so the contract cannot drift):
    :meth:`attach` stages the unreported delta of every counter in
    :data:`EXEC_COUNTERS` into the report's exec counters, and the
    watermarks advance only in :meth:`commit` AFTER the report RPC
    succeeded — a failed report re-ships the deltas with the next one
    instead of silently dropping them."""

    def __init__(self):
        self._reported = {key: read() for key, read in EXEC_COUNTERS.items()}

    def attach(self, counters: dict) -> dict:
        """Stage each pending delta under its key (when nonzero); returns
        the totals to pass to :meth:`commit` once the report went
        through."""
        totals = {key: read() for key, read in EXEC_COUNTERS.items()}
        for key, total in totals.items():
            if total > self._reported[key]:
                counters[key] = total - self._reported[key]
        return totals

    def commit(self, totals: dict):
        for key, total in totals.items():
            self._reported[key] = max(self._reported[key], total)


def _reset_for_tests():
    """Zero the totals (tests simulating a fresh process / generation;
    the listener registration itself is process-permanent)."""
    global _count, _secs_total, _trace_secs_total, _lower_secs_total
    global _store_hits, _store_misses, _store_rejects
    with _lock:
        _count = 0
        _secs_total = 0.0
        _trace_secs_total = 0.0
        _lower_secs_total = 0.0
        _store_hits = _store_misses = _store_rejects = 0
