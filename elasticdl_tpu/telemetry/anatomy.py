"""Per-dispatch step anatomy: continuous, sum-exact time attribution.

An end-to-end rate below its roofline comes without any way to say
*where inside a dispatch* the missing time goes: the XLA
profiler is a 5-step one-shot window and the ``step`` histogram is one
undifferentiated number.  This module is the always-on decomposition —
every dispatch group's wall time split into named, NON-OVERLAPPING
phases measured on the dispatching thread:

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

Enablement: the master's ``--step_anatomy`` flag, env-forwarded to
workers as ``ELASTICDL_TPU_STEP_ANATOMY`` (never argv — worker command
lines stay byte-identical with the feature off).  Overhead contract:
with no recorder installed the runtimes take ONE branch per dispatch
path (``if anatomy is None: <uninstrumented block>``) — no clock read,
no wrapper allocation (tests poison the clock to prove it).  With the
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
import os
import threading
import time

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

# device_compute sub-segments (recorded as extra event fields, not
# phases: they SUM to device_compute, they don't add to it)
SUB_ENQUEUE = "enqueue"
SUB_READY_WAIT = "ready_wait"


def timed_device_dispatch(recorder, dispatch):
    """THE instrumented device dispatch: run ``dispatch()`` with its
    wall attributed to ``device_compute`` as the ``enqueue`` sub-segment
    (the async dispatch call returning) and then block on its outputs
    as ``ready_wait``.  One definition site for the sub-segment split —
    every runtime's anatomy branch (serial flush, device-pipeline
    dispatch, task-stream staged/anatomized steps) calls this, so the
    sum-exactness contract (enqueue + ready_wait == device_compute)
    cannot drift between call sites.  Returns the dispatch outputs."""
    import jax

    with recorder.phase(PHASE_DEVICE_COMPUTE, sub=SUB_ENQUEUE):
        out = dispatch()
    with recorder.phase(PHASE_DEVICE_COMPUTE, sub=SUB_READY_WAIT):
        jax.block_until_ready(out)
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
    # ResNet-50 @224: ~4.1 GFLOPs forward -> ~1.23e10 train FLOPs
    "imagenet_resnet50": 1.23e10,
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


class AnatomyRecorder:
    """Per-process phase timer.  One dispatch group at a time: phase
    intervals accumulate on the dispatching thread, :meth:`commit`
    closes the window, derives ``untracked`` as the exact residual, and
    fans out to the event log / cumulative heartbeat totals / sampled
    spans.  The cumulative totals are read concurrently by the
    heartbeat thread, so they sit behind a lock; the open dispatch
    accumulator is dispatch-thread-only.

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
        # open dispatch: [(phase, start, end)] + sub-segment sums
        self._intervals: list[tuple[str, float, float]] = []
        self._subs: dict[str, float] = {}
        # cumulative (heartbeat-shipped) totals: phase -> [secs, count,
        # per-bucket counts over STEP_LATENCY_BUCKETS + Inf]
        self._lock = threading.Lock()
        self._totals: dict[str, list] = {}  # guarded-by: _lock
        self.dispatches = 0

    # ---- per-dispatch measurement (dispatch thread only) -------------------

    def wrap_fetches(self, iterable):
        """Wrap a batch stream so every ``next()`` — the time this
        thread waited on the host pipeline — lands in ``host_fetch`` of
        the dispatch group being accumulated."""
        it = iter(iterable)
        while True:
            t0 = time.monotonic()
            try:
                item = next(it)
            except StopIteration:
                return
            self._intervals.append((PHASE_HOST_FETCH, t0, time.monotonic()))
            yield item

    @contextlib.contextmanager
    def phase(self, name: str, sub: str | None = None):
        """Attribute the block's wall time to ``name``; ``sub`` records
        the same duration under a device_compute sub-segment label."""
        t0 = time.monotonic()
        try:
            yield
        finally:
            t1 = time.monotonic()
            self._intervals.append((name, t0, t1))
            if sub is not None:
                self._subs[sub] = self._subs.get(sub, 0.0) + (t1 - t0)

    def wrapped_hook(self, hook):
        """``pre_batch``-style hooks (telemetry samples, profiler) run
        inside the dispatch window but outside any device phase — time
        them as ``step_bookkeeping`` so they can't leak into
        ``untracked``.  Returns None for a None hook."""
        if hook is None:
            return None

        def timed(*args, **kwargs):
            with self.phase(PHASE_STEP_BOOKKEEPING):
                return hook(*args, **kwargs)

        return timed

    def commit(self, steps: int = 1, records: int = 0, step=None):
        """Close the open dispatch window: wall time is first interval
        start -> now, ``untracked`` is wall minus the tracked phases
        (exact by construction), and the result fans out to the three
        consumers.  A window with no intervals is a no-op."""
        intervals, self._intervals = self._intervals, []
        subs, self._subs = self._subs, {}
        if not intervals:
            return None
        now = time.monotonic()
        window_start = min(t0 for _n, t0, _t1 in intervals)
        wall = now - window_start
        phases = {}
        for name, t0, t1 in intervals:
            phases[name] = phases.get(name, 0.0) + (t1 - t0)
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
                phase=name,
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
