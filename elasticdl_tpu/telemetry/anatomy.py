"""Step anatomy: the host's dispatch timeline, and its sum-exact mode.

Two things live here, one store under both.

**The timeline** (:class:`Timeline`, the process's :data:`TIMELINE`):
a bounded in-memory ring of host spans, written by the program at the
place each piece of work happens — ``enqueue`` and ``h2d_transfer``
inside ``SPMDTrainer``, ``assemble`` inside
``stacking.assemble_canonical_group``, ``host_fetch`` and
``step_bookkeeping`` at ``run_stacked_steps``' seams, the producer
thread's ``produce_*`` inside ``TaskPrefetcher`` — so every runtime
that reaches those callees is covered without touching its loop.  It is
ALWAYS recording: no flag, no environment variable, no sampling.  The
cost is a handful of clock reads and one slot write per span, a few
microseconds a dispatch against steps of 45-215 ms, and the use is
that a stall is on record when it happens (PERF.md, PR 24).  It never
blocks on the device, never changes ``stage_depth``, never writes an
event or a line per span.  Readers: ``snapshot()`` / ``dump(path)``;
the profile window (utils/profiling.py) writes the window's spans to
``host_spans.json`` beside the device trace, anchored by one ``sync``
span; the benchmark reads ``snapshot()`` (perf/program_spans.py).

**The blocking mode** (:class:`AnatomyRecorder`, ``--step_anatomy``):
every dispatch group's wall time split into named, NON-OVERLAPPING
phases measured on the dispatching thread — the timeline's spans of
that thread since the previous commit, plus a ``block_until_ready``
after each dispatch so device time is measured, not queued:

- ``host_fetch``    — waiting on the reader/decode pipeline (the time
  the consumer thread blocked in ``next()``; with a healthy prefetcher
  this is residual stall, not raw decode cost);
- ``assemble``      — pad/stack to the canonical shape (host numpy);
- ``h2d_transfer``  — ``device_put`` / sharded placement of the batch.
  With ``--device_prefetch`` (trainer/device_pipeline.py) assembly and
  placement run on a staging thread while the previous group computes,
  so the CONSUMER-VISIBLE ``h2d_transfer`` becomes the wait for a
  staged group — the residual stall after overlap, whatever its
  upstream cause — and ``host_fetch``/``assemble`` go to ~0 on the
  dispatching thread.  That is the honest consumer view: the goodput
  smoke gates that this share DROPS when the prefetcher is on;
- ``device_compute``— jitted dispatch to ready: the *enqueue* segment
  (the async dispatch call returning) and the *ready-wait* segment
  (``block_until_ready`` on the dispatch's outputs) are recorded
  separately inside the phase, so async-dispatch overlap stays visible;
- ``step_bookkeeping`` — per-step hooks (telemetry samples, profiler),
  reports, checkpoint/eval milestone hooks after the group.

The sum-exact contract (the same discipline ``trace analyze`` enforces
on reform downtime): phases are disjoint intervals inside the dispatch
window, and the residual — loop glue between the timed segments — is
tracked honestly as its own ``untracked`` phase, so

    host_fetch + assemble + h2d_transfer + device_compute
      + step_bookkeeping + untracked  ==  dispatch wall time (exactly).

``scripts/goodput_smoke.py`` gates ``untracked`` < 2% of wall.

Three consumers:

1. ``/metrics`` — workers accumulate monotone per-phase totals and
   log-bucket counts here and ship them on the heartbeat (the PR-8 RPC
   counter pattern: the beat keeps flowing when reports stall); the
   master mirrors them onto ``elasticdl_step_phase_ms_total{phase=}``
   and the ``elasticdl_step_phase_seconds{phase=}`` histogram family
   (telemetry/master_hooks.py — the single registration site).
2. ``telemetry.report`` — every dispatch emits a ``step_anatomy`` event
   (when ``--telemetry_dir`` is configured), from which the report's
   ``goodput`` section computes live ``e2e_vs_roofline``, per-phase
   percentiles, model-FLOPs MFU and per-worker straggler attribution.
3. Perfetto — sampled ``step_anatomy`` spans (one per phase interval,
   ``phase=`` attribute) render the breakdown inside the existing
   ``train_step`` timeline; ``trace analyze`` aggregates them into a
   steady-state section.

Enablement of the blocking mode: the master's ``--step_anatomy`` flag,
env-forwarded to workers as ``ELASTICDL_TPU_STEP_ANATOMY`` (never argv
— worker command lines stay byte-identical with the feature off).
Overhead contract: with no recorder installed nothing blocks, no event
is emitted and no recorder code runs (``get_recorder()`` is one global
load; tests poison ``block_until_ready`` to prove it) — only the
timeline's spans are written.  With the
recorder on, each dispatch additionally blocks on its outputs
(``block_until_ready``), trading a little async-dispatch pipelining for
exact attribution — the documented cost of measuring (see
docs/designs/step_anatomy.md).  ``--device_prefetch``'s retire-behind
window likewise collapses to 1 under anatomy
(``device_pipeline.stage_depth``): the ``enqueue``/``ready_wait``
split stays sum-exact because every phase interval still lives inside
its own group's dispatch window.
"""

from __future__ import annotations

import contextlib
import itertools
import json
import os
import threading
import time
from typing import NamedTuple

from elasticdl_tpu.telemetry.registry import STEP_LATENCY_BUCKETS

STEP_ANATOMY_ENV = "ELASTICDL_TPU_STEP_ANATOMY"
PEAK_FLOPS_ENV = "ELASTICDL_TPU_PEAK_FLOPS_PER_CHIP"

# ---- phase vocabulary (one definition site; linted like EVENT_*/SPAN_*) -----

PHASE_HOST_FETCH = "host_fetch"
PHASE_ASSEMBLE = "assemble"
PHASE_H2D_TRANSFER = "h2d_transfer"
PHASE_DEVICE_COMPUTE = "device_compute"
PHASE_STEP_BOOKKEEPING = "step_bookkeeping"
PHASE_UNTRACKED = "untracked"
# serving-plane phases (elasticdl_tpu/serving): a request's latency
# decomposes as queue_wait (submit -> its first dispatch group opens)
# followed by the shared batch phases (assemble/h2d_transfer/
# device_compute) plus d2h_transfer (outputs device -> host) — same
# sum-exact residual discipline, per REQUEST instead of per dispatch
PHASE_QUEUE_WAIT = "queue_wait"
PHASE_D2H_TRANSFER = "d2h_transfer"
# boundary-stall counter (trainer/device_pipeline.py): device-idle time
# between the last retire of task N and the first dispatch of task N+1.
# A COUNTER in the phase vocabulary, not a member of TRACKED_PHASES /
# ALL_PHASES — it spans dispatch windows, so adding it to the per-
# dispatch sum would break the sum-exactness contract
PHASE_BOUNDARY_STALL = "boundary_stall"

# the measured (timer-covered) phases, in pipeline order
TRACKED_PHASES = (
    PHASE_HOST_FETCH,
    PHASE_ASSEMBLE,
    PHASE_H2D_TRANSFER,
    PHASE_DEVICE_COMPUTE,
    PHASE_STEP_BOOKKEEPING,
)
ALL_PHASES = TRACKED_PHASES + (PHASE_UNTRACKED,)

# a serving request's phases, in pipeline order (serving/engine.py is
# the one consumer; defined HERE so the phase vocabulary keeps a single
# linted definition site)
SERVING_REQUEST_PHASES = (
    PHASE_QUEUE_WAIT,
    PHASE_ASSEMBLE,
    PHASE_H2D_TRANSFER,
    PHASE_DEVICE_COMPUTE,
    PHASE_D2H_TRANSFER,
)

# ---- timeline-only span names (same definition site, same lint) -----------
# The always-on timeline (below) records these beside the tracked
# phases; they are NOT members of TRACKED_PHASES / ALL_PHASES.
# ``enqueue`` is the jitted call returning (mesh scope entry included),
# recorded inside SPMDTrainer; ``ready_wait`` the blocking mode's
# block_until_ready.  Under ``--step_anatomy`` the two sum to
# ``device_compute`` (extra event fields, not phases of their own).
PHASE_ENQUEUE = "enqueue"
PHASE_READY_WAIT = "ready_wait"
# the one block at a profile window's close (utils/profiling.py): its
# end is the instant the last dispatched step finished on the device,
# the anchor ``perf/trace_reduce.align_host_spans`` puts the host's
# clock on the device trace's clock with
PHASE_SYNC = "sync"
# the TaskPrefetcher's producer thread (trainer/host_pipeline.py): the
# dispatcher call, one batch made (read, decode, shuffle, stack; carries
# the thread's CPU time and the batch's bytes), and the wait for a
# buffer budget
PHASE_PRODUCE_NEXT_TASK = "produce_next_task"
PHASE_PRODUCE_BATCH = "produce_batch"
PHASE_PRODUCE_BLOCKED = "produce_blocked"


# ---- the timeline: always-on host spans --------------------------------------

# spans the ring holds before it overwrites its oldest: at the 10-20 a
# dispatch the train path writes (producer thread included), some
# thousand dispatches — minutes of a 100 ms step
TIMELINE_SPANS = 16384
# `since` looks this many slots past a hole (a thread that took its
# slot number and has not stored yet) before it calls the ring's end
_LOOKAHEAD = 64


class Span(NamedTuple):
    """One piece of host work, on ``time.perf_counter_ns``
    (CLOCK_MONOTONIC: the clock ``time.monotonic`` reads).  ``ordinal``
    is the thread's dispatch ordinal — the ``enqueue`` this span led up
    to — or, for ``host_fetch`` and the producer's spans, the batch
    ordinal: the k-th ``produce_batch`` is the k-th ``host_fetch`` (the
    prefetch queue is FIFO).  ``count`` is bytes for ``h2d_transfer``
    and ``produce_batch``, batches delivered for ``host_fetch`` (0: the
    stream ended)."""

    name: str
    thread: str
    start_ns: int
    duration_ns: int
    cpu_ns: int | None
    ordinal: int
    count: int | None


class _ThreadState(threading.local):
    def __init__(self):
        self.name = threading.current_thread().name
        self.dispatch = 0  # ordinal the thread's next enqueue carries
        self.batch = 0  # ordinal of its next fetched / produced batch


class Timeline:
    """A bounded ring of host spans, written where the work happens.

    Always recording: no flag, no sampling, no event or file per span.
    An append is two clock reads (the caller's start, ours at the end),
    one ``next()`` on a shared ``itertools.count`` — atomic under the
    interpreter lock, so every thread gets a slot of its own without a
    lock — and one store of a finished tuple, so a reader never sees a
    torn span.  Nothing here touches the device but :meth:`sync`, which
    only the profile window's close calls."""

    def __init__(self, capacity: int = TIMELINE_SPANS):
        if capacity & (capacity - 1):
            raise ValueError("the ring's capacity is a power of two")
        self._mask = capacity - 1
        self._ring: list = [None] * capacity
        self._seq = itertools.count()
        self._head = 0  # a hint: the newest slot number + 1, may lag
        self._tls = _ThreadState()
        self._last_output = None

    # ---- writers (any thread) ----------------------------------------------

    def _append(self, name, start_ns, cpu_ns, count, ordinal):
        end_ns = time.perf_counter_ns()
        seq = next(self._seq)
        self._ring[seq & self._mask] = (
            seq, name, self._tls.name, start_ns, end_ns - start_ns, cpu_ns,
            ordinal, count,
        )
        self._head = seq + 1

    def record(self, name, start_ns, cpu_ns=None, count=None):
        """The span ``name`` from ``start_ns`` to now, on this thread,
        under the dispatch ordinal of the thread's next enqueue."""
        self._append(name, start_ns, cpu_ns, count, self._tls.dispatch)

    def record_enqueue(self, start_ns, output):
        """The jitted call returned: an ``enqueue`` span, after which
        the thread's dispatch ordinal moves on.  ``output`` (the
        dispatch's metrics) is kept until the next one replaces it, for
        :meth:`sync` to block on."""
        tls = self._tls
        self._append(PHASE_ENQUEUE, start_ns, None, None, tls.dispatch)
        tls.dispatch += 1
        self._last_output = output

    def record_batch(self, name, start_ns, cpu_ns=None, count=None):
        """A span numbered by the thread's batch ordinal, which moves on
        when the span delivered a batch (``count`` is not 0).  Returns
        the ordinal the span got."""
        tls = self._tls
        ordinal = tls.batch
        self._append(name, start_ns, cpu_ns, count, ordinal)
        if count != 0:
            tls.batch = ordinal + 1
        return ordinal

    def set_batch_ordinal(self, ordinal: int):
        """The batch this thread is about to be handed is the producer's
        ``ordinal``-th: the prefetch queue's consumer side says so, and
        the ``host_fetch`` recorded at the seam above it carries it."""
        self._tls.batch = ordinal

    def timed_fetches(self, iterable):
        """``iterable`` with the time inside every ``next()`` — this
        thread waiting on the host pipeline — recorded as
        ``host_fetch``; the wait that ends the stream too (count 0)."""
        it = iter(iterable)
        while True:
            t0 = time.perf_counter_ns()
            try:
                item = next(it)
            except StopIteration:
                self.record_batch(PHASE_HOST_FETCH, t0, count=0)
                return
            self.record_batch(PHASE_HOST_FETCH, t0, count=1)
            yield item

    def sync(self):
        """Block once on the newest dispatch's output and record the
        wait as a ``sync`` span: its end is when the device finished the
        last step this process dispatched.  The profile window's close
        is the one caller."""
        import jax

        t0 = time.perf_counter_ns()
        output, self._last_output = self._last_output, None
        if output is not None:
            jax.block_until_ready(output)
        self.record(PHASE_SYNC, t0)

    # ---- readers (any thread) ----------------------------------------------

    def _ordered(self) -> list:
        # list(list) copies under the interpreter lock: a consistent cut
        return sorted(s for s in list(self._ring) if s is not None)

    def snapshot(self) -> list[Span]:
        """Every span the ring still holds, oldest first."""
        return [Span(*s[1:]) for s in self._ordered()]

    def head(self) -> int:
        """The slot number the next span gets (spans appended so far)."""
        return self.since(self._head)[1]

    def since(self, mark: int) -> tuple[list[Span], int]:
        """The spans appended from slot number ``mark`` on, oldest
        first, and the mark to pass next time."""
        ring, mask = self._ring, self._mask
        found, seq, misses = [], mark, 0
        while misses < _LOOKAHEAD:
            s = ring[seq & mask]
            if s is not None and s[0] == seq:
                found.append(s)
                misses = 0
            elif s is not None and s[0] > seq:
                # the ring lapped the mark: take what is left of it
                found = [x for x in self._ordered() if x[0] >= mark]
                break
            else:
                misses += 1
            seq += 1
        new_mark = found[-1][0] + 1 if found else mark
        return [Span(*s[1:]) for s in found], new_mark

    def dump(self, path: str, start_ns: int | None = None, end_ns: int | None = None):
        """Write the spans (those inside ``[start_ns, end_ns]`` where
        given) as JSON: ``fields`` names the columns of ``spans``."""
        spans = [
            list(s)
            for s in self.snapshot()
            if (start_ns is None or s.start_ns + s.duration_ns >= start_ns)
            and (end_ns is None or s.start_ns <= end_ns)
        ]
        with open(path, "w") as f:
            json.dump(
                {
                    "clock": "time.perf_counter_ns",
                    "fields": list(Span._fields),
                    "spans": spans,
                },
                f,
                separators=(",", ":"),
            )
        return len(spans)


# THE process's timeline: the runtimes' callees write to it, the profile
# window and the benchmark read it
TIMELINE = Timeline()
snapshot = TIMELINE.snapshot
dump = TIMELINE.dump


def timed_device_dispatch(recorder, dispatch):
    """THE blocking mode's device dispatch (``--step_anatomy`` only):
    run ``dispatch()`` — whose ``enqueue`` span the trainer records
    itself — then block on its outputs as ``ready_wait``.  One
    definition site, so the sum-exactness contract (enqueue +
    ready_wait == device_compute) cannot drift between call sites.
    Returns the dispatch outputs."""
    out = dispatch()
    recorder.ready_wait(out)
    return out

# ---- model-FLOPs table (goodput MFU) ----------------------------------------
#
# Per-record TRAINING FLOPs (forward + backward ~= 3x forward) for zoo
# models whose cost is a closed-form function of their fixed
# architecture.  Keyed by the model module name (the first dotted
# component of --model_def).  Models with data-dependent cost
# (transformer seq length, custom params) return None — the report then
# says WHY mfu is absent instead of inventing a number.
MODEL_FLOPS_PER_RECORD = {
    # Conv(32,3x3)@26x26 + Conv(64,3x3)@24x24 + Dense(9216->10), x3 for
    # fwd+bwd: ~2.2e7 fwd MACs -> ~6.6e7 train FLOPs
    "mnist_functional_api": 6.6e7,
    "mnist_subclass": 6.6e7,
    # ResNet-50 @224 (He et al., Table 1): 3.858e9 multiply-accumulates
    # forward from the layer shapes, 2 FLOPs each, x3 for fwd+bwd — what
    # perf/flop_functions/resnet50.py derives (a test holds them equal)
    "imagenet_resnet50": 2.315e10,
}

# peak dense FLOP/s per chip by device kind (bf16); used only when the
# operator did not pin ELASTICDL_TPU_PEAK_FLOPS_PER_CHIP
_PEAK_FLOPS_BY_DEVICE_KIND = {
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,
    "TPU v5e": 197e12,
    "TPU v5p": 459e12,
    "TPU v6e": 918e12,
}


def model_flops_per_record(model_def: str) -> float | None:
    """Known per-record training FLOPs for ``--model_def``, or None."""
    module = (model_def or "").split(".", 1)[0]
    return MODEL_FLOPS_PER_RECORD.get(module)


def peak_flops_per_chip() -> float | None:
    """Peak FLOP/s of one local device: the env pin wins, else the
    device-kind table, else None (CPU backends have no honest peak)."""
    raw = os.environ.get(PEAK_FLOPS_ENV, "")
    if raw:
        try:
            return float(raw)
        except ValueError:
            pass
    try:
        import jax

        kind = jax.devices()[0].device_kind
    except Exception:  # noqa: BLE001 — no backend is a valid state
        return None
    return _PEAK_FLOPS_BY_DEVICE_KIND.get(kind)


def _bucket_index(secs: float) -> int:
    for i, bound in enumerate(STEP_LATENCY_BUCKETS):
        if secs <= bound:
            return i
    return len(STEP_LATENCY_BUCKETS)  # +Inf slot


# which tracked phase a timeline span counts towards under
# ``--step_anatomy`` (the producer's spans and ``sync`` are not the
# dispatching thread's dispatch time)
_PHASE_OF_SPAN = {
    PHASE_HOST_FETCH: PHASE_HOST_FETCH,
    PHASE_ASSEMBLE: PHASE_ASSEMBLE,
    PHASE_H2D_TRANSFER: PHASE_H2D_TRANSFER,
    PHASE_STEP_BOOKKEEPING: PHASE_STEP_BOOKKEEPING,
    PHASE_DEVICE_COMPUTE: PHASE_DEVICE_COMPUTE,
    PHASE_ENQUEUE: PHASE_DEVICE_COMPUTE,
    PHASE_READY_WAIT: PHASE_DEVICE_COMPUTE,
}


class AnatomyRecorder:
    """The blocking, sum-exact mode (``--step_anatomy``).  It keeps no
    intervals of its own: the spans the dispatching thread wrote to the
    timeline since the previous :meth:`commit` ARE the dispatch's
    intervals, so no dispatch is ever recorded in two places.
    :meth:`commit` closes the window, derives ``untracked`` as the
    exact residual, and fans out to the event log / cumulative
    heartbeat totals / sampled spans.  The cumulative totals are read
    concurrently by the heartbeat thread, so they sit behind a lock.

    Identity (worker/process/generation) is deliberately NOT stored
    here: events are stamped by the installed
    :class:`~elasticdl_tpu.telemetry.worker_hooks.StepRecorder` and
    spans by the installed tracer — one identity source per process,
    nothing to go stale across a reform."""

    def __init__(self, flops_per_record: float | None = None):
        self._flops_per_record = flops_per_record
        self._peak_flops = peak_flops_per_chip()
        try:
            import jax

            self._n_chips = max(1, len(jax.devices()))
        except Exception:  # noqa: BLE001
            self._n_chips = 1
        self._timeline = TIMELINE
        # the open dispatch starts at the first span after this mark
        self._mark = self._timeline.head()
        # cumulative (heartbeat-shipped) totals: phase -> [secs, count,
        # per-bucket counts over STEP_LATENCY_BUCKETS + Inf]
        self._lock = threading.Lock()
        self._totals: dict[str, list] = {}  # guarded-by: _lock
        self.dispatches = 0

    # ---- per-dispatch measurement (dispatch thread only) -------------------

    def wrap_fetches(self, iterable):
        """A batch stream no runtime seam times already (the task-stream
        worker's own loop): every ``next()`` lands in ``host_fetch``."""
        return self._timeline.timed_fetches(iterable)

    @contextlib.contextmanager
    def phase(self, name: str, sub: str | None = None):
        """Attribute the block's wall time to ``name`` (a
        ``device_compute`` sub-segment under its ``sub`` label), for
        work no callee records itself."""
        t0 = time.perf_counter_ns()
        try:
            yield
        finally:
            self._timeline.record(sub or name, t0)

    def ready_wait(self, out):
        """Block on a dispatch's outputs: the ``ready_wait`` half of
        ``device_compute`` (the trainer recorded the ``enqueue`` half)."""
        import jax

        t0 = time.perf_counter_ns()
        jax.block_until_ready(out)
        self._timeline.record(PHASE_READY_WAIT, t0)

    def commit(self, steps: int = 1, records: int = 0, step=None):
        """Close the open dispatch window: wall time is the first span's
        start -> now, ``untracked`` is wall minus the tracked phases
        (exact by construction), and the result fans out to the three
        consumers.  A window with no spans is a no-op."""
        now = time.perf_counter_ns()
        spans, self._mark = self._timeline.since(self._mark)
        me = threading.current_thread().name
        intervals = [
            (s.name, s.start_ns / 1e9, (s.start_ns + s.duration_ns) / 1e9)
            for s in spans
            if s.thread == me and s.name in _PHASE_OF_SPAN
        ]
        if not intervals:
            return None
        window_start = min(t0 for _n, t0, _t1 in intervals)
        wall = now / 1e9 - window_start
        phases, subs = {}, {}
        for name, t0, t1 in intervals:
            phase = _PHASE_OF_SPAN[name]
            phases[phase] = phases.get(phase, 0.0) + (t1 - t0)
            if phase != name:
                subs[name] = subs.get(name, 0.0) + (t1 - t0)
        tracked = sum(phases.values())
        phases[PHASE_UNTRACKED] = max(0.0, wall - tracked)
        self.dispatches += 1
        with self._lock:
            for name, secs in phases.items():
                slot = self._totals.get(name)
                if slot is None:
                    slot = self._totals[name] = [
                        0.0,
                        0,
                        [0] * (len(STEP_LATENCY_BUCKETS) + 1),
                    ]
                slot[0] += secs
                slot[1] += 1
                slot[2][_bucket_index(secs)] += 1
        self._emit_event(phases, subs, wall, steps, records, step)
        self._emit_spans(intervals, step)
        return phases

    def _emit_event(self, phases, subs, wall, steps, records, step):
        from elasticdl_tpu.telemetry import worker_hooks
        from elasticdl_tpu.telemetry.events import EVENT_STEP_ANATOMY

        fields = {
            "steps": int(steps),
            "records": int(records),
            "wall_ms": wall * 1000.0,
        }
        if step is not None:
            fields["step"] = int(step)
        for name, secs in phases.items():
            fields[f"{name}_ms"] = secs * 1000.0
        for name, secs in subs.items():
            fields[f"{name}_ms"] = secs * 1000.0
        if self._flops_per_record is not None:
            fields["flops_per_record"] = self._flops_per_record
        if self._peak_flops is not None:
            fields["peak_flops_per_chip"] = self._peak_flops
        fields["n_chips"] = self._n_chips
        worker_hooks.emit_event(EVENT_STEP_ANATOMY, **fields)

    def _emit_spans(self, intervals, step):
        from elasticdl_tpu.telemetry import tracing

        tracer = tracing.get_tracer()
        if tracer is None or not tracer.should_sample(
            tracing.SPAN_STEP_ANATOMY
        ):
            return
        for name, t0, t1 in intervals:
            tracer.record_span(
                tracing.SPAN_STEP_ANATOMY,
                t0,
                t1,
                phase=_PHASE_OF_SPAN[name],
                step=int(step) if step is not None else None,
            )

    # ---- heartbeat shipping (any thread) -----------------------------------

    def heartbeat_snapshot(self) -> dict:
        """Monotone per-phase totals for ``HeartbeatRequest.phases``:
        ``{phase: {"ms": float, "count": int, "buckets": {str(secs):
        int}}}`` (bucket keys are strings — the msgpack transport
        rejects non-str map keys; ``"inf"`` is the overflow slot)."""
        with self._lock:
            out = {}
            for name, (secs, count, buckets) in self._totals.items():
                bucket_map = {
                    str(bound): n
                    for bound, n in zip(STEP_LATENCY_BUCKETS, buckets)
                    if n
                }
                if buckets[-1]:
                    bucket_map["inf"] = buckets[-1]
                out[name] = {
                    "ms": secs * 1000.0,
                    "count": count,
                    "buckets": bucket_map,
                }
            return out


# ---- module-level install + zero-cost-when-disabled accessors ---------------

_active: AnatomyRecorder | None = None


def install(model_def: str = "") -> AnatomyRecorder:
    global _active
    _active = AnatomyRecorder(
        flops_per_record=model_flops_per_record(model_def)
    )
    return _active


def install_if_enabled(flag, model_def: str = "") -> AnatomyRecorder | None:
    """Install when the master's ``--step_anatomy`` flag OR the
    env-forwarded ``ELASTICDL_TPU_STEP_ANATOMY`` asks for it; clears
    any stale recorder otherwise — a runtime constructed WITHOUT the
    flag must not inherit a previous in-process install (bench runs
    several configs per process)."""
    if not flag and not os.environ.get(STEP_ANATOMY_ENV, ""):
        uninstall()
        return None
    return install(model_def=model_def)


def install_from_env(model_def: str = "") -> AnatomyRecorder | None:
    """Worker-subprocess entry: install only when the master exported
    the enabling env (the chaos-plan/telemetry-dir pattern)."""
    return install_if_enabled(None, model_def=model_def)


def uninstall():
    global _active
    _active = None


def get_recorder() -> AnatomyRecorder | None:  # elastic-lint: hot-path
    """THE runtime seam: None (one global load, no clock read) unless
    anatomy was installed — the runtimes branch ONCE on this per
    dispatch path."""
    return _active


def heartbeat_snapshot() -> dict:  # elastic-lint: hot-path
    """Phase totals for the heartbeat; {} when disabled (old payloads
    decode the same, so the field is wire-compatible)."""
    recorder = _active
    if recorder is None:
        return {}
    return recorder.heartbeat_snapshot()
