"""XLA profiler windows: flag-armed at launch, or on-demand at runtime.

The reference's only tracing is wall-clock buckets at DEBUG level
(``common/timing_utils.py``, kept as ``utils.timing_utils``); on TPU the
tool that actually explains a slow step is the XLA profiler (op-level
device timeline, HLO attribution, TensorBoard ``profile`` plugin).  Two
ways to open a capture window:

1. **Launch flags** — ``--profile_dir d --profile_steps N`` traces steps
   [start, start + N) into ``d`` (past compile + warmup), exactly as
   before.
2. **On demand** — the ``request_profile`` master RPC arms a window on a
   RUNNING job: the command rides down on heartbeat responses
   (``HeartbeatResponse.profile``), :func:`apply_profile_command` calls
   :meth:`StepProfiler.arm`, and the next training step opens an
   ``N``-step capture into the telemetry dir — a live degraded job gets
   op-level attribution without a relaunch.  Workers dedupe by
   ``window_id`` (monotone per master), so the command may be
   re-delivered or re-sent every beat and is absorbed.

Both paths emit the same ``profile_window_open``/``profile_window_close``
events and the ``profile_window`` span, so the capture window can be
located on the same timeline as the distributed trace.

What a window records, and what it leaves alone.  The capture is of the
DEVICE planes only: it is always opened with ``python_tracer_level = 0``
and ``host_tracer_level = 0``, and there is no other way to open one.
With the host tracer on, the runtime's transfer threads trace their own
events and a ResNet-50 job ran at a third of its rate (PERF.md, PR 23):
the operator's tool for a degraded job must not degrade it.  The host's
side of the window is the program's own timeline
(``telemetry/anatomy.py``), which records whether or not a window is
open.  At close the profiler blocks ONCE on the newest dispatch's output
(a ``sync`` span: the instant the device finished, on the host's clock)
and writes the timeline's spans of the window to ``host_spans.json``
beside the ``.xplane.pb``; ``perf/program_spans.py::align_profile_window`` puts them on
the trace's clock by that anchor.  One block at the end of a window
changes nothing the window measured.

The HLO attribution is the program's own: beside those two files the
close writes ``op_scopes.json``, the train programs' map from the op
line's names (``fusion.25``) to the model's scopes (part, phase, kind:
``telemetry/op_scopes.py``, read from the compiled programs the trainer
dispatched, one parse of each program's HLO text at the close and nothing
before it), and ``python -m elasticdl_tpu.telemetry.op_scopes <dir>``
prints the window's device time by part x phase x kind.  The byte side is
one more file of the same close, ``step_memory.json``
(``telemetry/memory.py::read_step_memory``: the state on the fullest device,
XLA's sizes of each train program, what is alive at the step's peak by the
same scopes, the allocator's figures); ``... op_scopes <dir> --memory``
prints it.

Disabled cost: with no window pending or open, :meth:`on_step` is one
attribute load and a ``not x`` check (``# elastic-lint: hot-path``).
Thread model: :meth:`arm` is called from the heartbeat thread,
:meth:`on_step` from the training thread — the engaged flag is the
lock-free gate, everything behind it synchronizes on a small lock.
"""

from __future__ import annotations

import glob
import os
import threading
import time

from elasticdl_tpu.telemetry import anatomy, memory, op_scopes
from elasticdl_tpu.utils.log_utils import default_logger as logger

# subdirectory of the telemetry dir an on-demand capture lands in when
# the request names no explicit out_dir
PROFILE_SUBDIR = "profile"
# the window's host spans, written beside the device trace
HOST_SPANS_FILE = "host_spans.json"


class StepProfiler:
    """Capture step windows with ``jax.profiler``.

    ``on_step()`` is called once per step by the training loop and counts
    calls SINCE PROCESS START (not the model version — a checkpoint-
    resumed run at version 10000 still warms up before its window).  The
    flag-armed window starts at call ``start_step`` (past compile +
    warmup) and stops ``num_steps`` later; an :meth:`arm`-ed window
    starts at the NEXT call.  One window at a time; idle (nothing
    pending or tracing) it is one attribute load per step.
    """

    def __init__(
        self,
        out_dir: str | None,
        start_step: int = 5,
        num_steps: int = 5,
    ):
        self._lock = threading.Lock()
        self._seen = 0  # guarded-by: _lock
        self._tracing = False  # guarded-by: _lock (writes)
        self._out_dir = ""  # dir of the OPEN window  # guarded-by: _lock
        self._stop_at = 0  # last in-window call index  # guarded-by: _lock
        # a window armed in seconds closes at the first step past this
        # perf_counter_ns instant instead  # guarded-by: _lock
        self._stop_after_ns: int | None = None
        self._opened_at = 0  # guarded-by: _lock
        self._opened_ns = 0  # guarded-by: _lock
        self._window_id: int | None = None  # guarded-by: _lock
        self._window_span = None  # guarded-by: _lock
        # flag-armed window (never opened yet when _flag_dir non-empty)
        self._flag_dir = out_dir or ""  # guarded-by: _lock
        self._flag_start = start_step
        self._flag_num = num_steps
        self._flag_ever_armed = bool(out_dir)
        # on-demand window waiting to open  # guarded-by: _lock
        self._pending: dict | None = None
        # replay dedup: the largest window id ever armed
        self._last_window_id = 0  # guarded-by: _lock
        # lock-free hot gate: True iff a window is pending or open.
        # Writes happen under _lock; the training thread's stale read
        # costs at most one extra locked call
        self._engaged = bool(out_dir)

    # ---- runtime arming (heartbeat thread) ---------------------------------

    def arm(
        self,
        out_dir: str,
        num_steps: int = 5,
        window_id: int | None = None,
        seconds: float | None = None,
    ) -> bool:
        """Arm an on-demand window opening at the next ``on_step``.
        ``seconds`` sizes the window by the clock instead of in steps:
        it closes at the first step after that long (a degraded job's
        step time is what the operator does not know).
        Returns False when absorbed (a replayed ``window_id``) or
        refused (a window is already pending/open — the caller retries
        on a later beat; an unconsumed id stays armable)."""
        if not out_dir:
            return False
        with self._lock:
            if window_id is not None and window_id <= self._last_window_id:
                return False  # replayed command: absorbed
            if self._tracing or self._pending is not None:
                return False  # one window at a time; retry later
            if window_id is not None:
                self._last_window_id = window_id
            self._pending = {
                "out_dir": out_dir,
                "num_steps": max(1, int(num_steps)),
                "window_id": window_id,
                "seconds": float(seconds) if seconds else None,
            }
            self._engaged = True
        logger.info(
            "XLA profiler: on-demand window armed (%s into %s)",
            f"{seconds} s" if seconds else f"{max(1, int(num_steps))} steps",
            out_dir,
        )
        return True

    # ---- the per-step hook (training thread) -------------------------------

    def on_step(self, _step=None):  # elastic-lint: hot-path
        """Count one training step (the argument is accepted and ignored
        for call-site readability); one attribute load when idle."""
        if not self._engaged:
            return
        self._on_step_engaged()

    def _on_step_engaged(self):
        with self._lock:
            self._seen += 1
            if not self._tracing:
                if self._pending is not None:
                    pending, self._pending = self._pending, None
                    self._open_window_locked(
                        pending["out_dir"],
                        self._seen + pending["num_steps"] - 1,
                        pending["window_id"],
                        seconds=pending["seconds"],
                    )
                elif self._flag_dir and self._seen > self._flag_start:
                    flag_dir, self._flag_dir = self._flag_dir, ""
                    self._open_window_locked(
                        flag_dir,
                        self._flag_start + self._flag_num,
                        None,
                    )
            elif (
                self._seen > self._stop_at
                if self._stop_after_ns is None
                else time.perf_counter_ns() >= self._stop_after_ns
            ):
                self._close_window_locked()
            self._refresh_engaged_locked()

    # lock-holding: _lock
    def _refresh_engaged_locked(self):
        self._engaged = bool(
            self._tracing or self._pending is not None or self._flag_dir
        )

    # lock-holding: _lock
    def _open_window_locked(
        self, out_dir: str, stop_at: int, window_id, seconds=None
    ):
        import jax

        # the device planes only: with either tracer on, the capture
        # slows the job it is meant to explain (module docstring)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 0
        try:
            jax.profiler.start_trace(out_dir, profiler_options=options)
        except Exception:  # noqa: BLE001 — a failed capture (another
            # trace active, unwritable dir) must not kill the training
            # thread; the window is abandoned
            logger.exception("XLA profiler: start_trace failed")
            return
        self._tracing = True
        self._out_dir = out_dir
        self._stop_at = stop_at
        self._opened_at = self._seen
        self._opened_ns = time.perf_counter_ns()
        self._stop_after_ns = (
            self._opened_ns + int(seconds * 1e9) if seconds else None
        )
        self._window_id = window_id
        # telemetry marker + span so the XLA profiler window can be
        # located on the SAME timeline as the distributed trace (both
        # no-ops when telemetry/tracing is not installed)
        from elasticdl_tpu.telemetry import tracing as _trace
        from elasticdl_tpu.telemetry import worker_hooks
        from elasticdl_tpu.telemetry.events import EVENT_PROFILE_WINDOW_OPEN

        fields = dict(at_call=self._seen, out_dir=out_dir)
        if window_id is not None:
            fields["window_id"] = int(window_id)
        worker_hooks.emit_event(EVENT_PROFILE_WINDOW_OPEN, **fields)
        tracer = _trace.get_tracer()
        if tracer is not None:
            self._window_span = tracer.start_span(
                _trace.SPAN_PROFILE_WINDOW, out_dir=out_dir
            )
        logger.info(
            "XLA profiler: tracing %s into %s",
            f"{seconds} s"
            if seconds
            else f"{self._stop_at - self._seen + 1} steps",
            out_dir,
        )

    # lock-holding: _lock
    def _close_window_locked(self):
        import jax

        try:
            # the one block a window costs, at its end: the device has
            # finished the last dispatched step when `sync` ends, which
            # puts the host's clock on the trace's (module docstring)
            anatomy.TIMELINE.sync()
        except Exception:  # noqa: BLE001 — a failed step surfaces where
            # the training thread next touches it, not here
            logger.exception("XLA profiler: sync at window close failed")
        try:
            jax.profiler.stop_trace()
        except Exception:  # noqa: BLE001 — a torn capture must not kill
            # the training thread
            logger.exception("XLA profiler: stop_trace failed")
        else:
            try:
                self._write_beside_trace()
            except OSError:
                logger.exception("XLA profiler: host spans not written")
        self._tracing = False
        from elasticdl_tpu.telemetry import worker_hooks
        from elasticdl_tpu.telemetry.events import EVENT_PROFILE_WINDOW_CLOSE

        fields = dict(
            at_call=self._seen,
            out_dir=self._out_dir,
            steps=self._seen - self._opened_at,
        )
        if self._window_id is not None:
            fields["window_id"] = int(self._window_id)
        worker_hooks.emit_event(EVENT_PROFILE_WINDOW_CLOSE, **fields)
        if self._window_span is not None:
            self._window_span.end(steps=self._seen - self._opened_at)
            self._window_span = None
        logger.info("XLA profiler: trace written to %s", self._out_dir)
        self._window_id = None
        self._out_dir = ""

    # lock-holding: _lock
    def _write_beside_trace(self):
        """The timeline's spans of the window, the train programs' op scopes
        and the step's bytes, beside the newest ``.xplane.pb`` (in the
        window's directory where there is none)."""
        traces = sorted(
            glob.glob(
                os.path.join(
                    self._out_dir, "plugins", "profile", "*", "*.xplane.pb"
                )
            )
        )
        where = os.path.dirname(traces[-1]) if traces else self._out_dir
        os.makedirs(where, exist_ok=True)
        anatomy.TIMELINE.dump(
            os.path.join(where, HOST_SPANS_FILE),
            start_ns=self._opened_ns,
            end_ns=time.perf_counter_ns(),
        )
        for name, dump in (
            (op_scopes.OP_SCOPES_FILE, op_scopes.dump),
            (op_scopes.STEP_MEMORY_FILE, memory.dump_step_memory),
        ):
            try:
                dump(os.path.join(where, name))
            except Exception:  # noqa: BLE001 — the maps are an aid: a
                # program whose text cannot be read must not cost the
                # window its trace
                logger.exception("XLA profiler: %s not written", name)

    def stop(self):
        """Idempotent; called at loop exit so a short run still flushes
        a partial window (and warns when a flag window never opened)."""
        with self._lock:
            if self._tracing:
                self._close_window_locked()
            elif self._flag_dir and self._flag_ever_armed:
                logger.warning(
                    "XLA profiler: window never opened — the run had %d "
                    "steps but tracing starts after step %d "
                    "(--profile_steps only sets the window length)",
                    self._seen,
                    self._flag_start,
                )
            self._flag_dir = ""
            self._flag_ever_armed = False
            self._pending = None
            self._refresh_engaged_locked()


def apply_profile_command(
    profiler: StepProfiler,
    command: dict,
    telemetry_dir: str = "",
    tag: str = "",
) -> bool:
    """Arm ``profiler`` from a heartbeat-borne ``request_profile``
    command (the worker side of the round trip).  The capture lands in
    the command's ``out_dir`` or ``<telemetry_dir>/profile``, under a
    per-window (and per-process, via ``tag``) subdirectory so
    concurrent workers on one host never interleave trace files.
    Absorbed replays (seen window ids) return False — THE dedup that
    lets the master redistribute the command on every beat."""
    if not command or not isinstance(command, dict):
        return False
    try:
        window_id = int(command.get("window_id", 0))
    except (TypeError, ValueError):
        return False
    if window_id <= 0:
        return False
    base = str(command.get("out_dir") or "") or (
        os.path.join(telemetry_dir, PROFILE_SUBDIR) if telemetry_dir else ""
    )
    if not base:
        return False
    leaf = f"window_{window_id}" + (f"_{tag}" if tag else "")
    try:
        num_steps = int(command.get("num_steps", 5))
    except (TypeError, ValueError):
        num_steps = 5
    try:
        seconds = float(command.get("seconds") or 0) or None
    except (TypeError, ValueError):
        seconds = None
    return profiler.arm(
        os.path.join(base, leaf),
        num_steps=num_steps,
        window_id=window_id,
        seconds=seconds,
    )
