"""Device-path pipelining: double-buffered h2d staging, batch-buffer
donation and async retire-behind dispatch.

The jitted step standalone runs faster than the end-to-end record flow
(by how much on the current machine: not measured, ROADMAP A1), and
PR 9's anatomy says the gap is host-side serialization — every
dispatch group's batch is padded,
stacked and placed on device ON the dispatching thread, between
dispatches.  This module closes that gap for the canonical-shape path
(shapes are pure functions of config since PR 5, so staging buffers
never change shape):

- **Staging** (:class:`DeviceStager`): a daemon thread pulls host
  batches from the upstream stream (the ``TaskPrefetcher`` host queue,
  so decode -> stage -> compute form a three-deep pipeline), assembles
  them to the canonical dispatch shape and places them on device while
  the CURRENT group computes.  The queue is bounded (double buffering:
  one group being consumed, one staged, one in assembly) so device
  memory stays bounded.
- **Donation**: the runtimes construct their ``SPMDTrainer`` with
  ``donate_batch=True`` when the feature is on, extending ``jax.jit``
  donation from state-only to the batch/mask buffers — XLA reuses the
  staged input buffers for outputs, so steady-state dispatches do zero
  fresh h2d allocations.  A donated buffer is dead after its dispatch;
  :class:`StagedGroup` enforces single ``take()`` ownership so a
  read-after-retire is caught at the staging layer too (and JAX itself
  raises on a deleted Array — both are pinned by falsification tests).
- **Retire-behind** (:func:`run_pipelined_steps`): dispatch outputs are
  retired one group behind inside a bounded in-flight window
  (:data:`RETIRE_WINDOW`), so XLA async dispatch actually overlaps; the
  full barrier is retained at task boundaries (the function drains
  before returning, so a task is only ever reported after every one of
  its groups retired), and ``--step_anatomy`` collapses the window to 1
  (:func:`stage_depth`) because exact per-group walls need the
  per-group block — the documented cost of measuring.

- **Cross-task staging** (:func:`run_pipelined_task_stream`): with
  ``--boundary_fusion`` the pipeline survives TASK boundaries instead
  of draining and re-staging from host at each one.  One persistent
  :class:`DeviceStager` walks the whole task stream; in-stream
  :class:`TaskMark` sentinels delimit tasks, so at a boundary the
  consumer only retires the PREVIOUS task's in-flight window and runs
  the boundary bookkeeping (report, milestone checks, memory sample)
  while the stager concurrently stages the NEXT task's groups — the
  next pull finds task N+1's first group already device-resident.
  Exactly-once is preserved by retiring-and-reporting per task: a task
  is reported only after its own window drained, and staged-but-
  unreported groups of a reclaimed/fenced task die un-taken when the
  stager closes (single-take ownership — nothing dispatched, nothing
  reported).  The boundary gap is measured as the ``boundary_stall``
  counter (device-idle time between the last retire of task N and the
  first dispatch of task N+1), shipped on the heartbeat next to the
  prefetch totals and mirrored as ``elasticdl_boundary_stall_ms_total``.

Enablement: the master's ``--device_prefetch`` flag, env-forwarded to
workers as ``ELASTICDL_TPU_DEVICE_PREFETCH`` (never argv — worker
command lines stay byte-identical with the feature off); cross-task
staging adds ``--boundary_fusion`` (``ELASTICDL_TPU_BOUNDARY_FUSION``)
and the window/queue bound becomes ``--pipeline_depth``
(``ELASTICDL_TPU_PIPELINE_DEPTH``, default preserving the classic 2),
with the memory ledger's ``device_stager`` component bounding how deep
staging may actually run (admission against the live device headroom /
``ELASTICDL_TPU_STAGING_BUDGET_BYTES``, loud degrade to depth 1 on
pressure).  Disabled cost: the runtimes resolve the flags ONCE at build
time and ``run_stacked_steps`` takes one boolean branch per call — no
thread, no queue, no clock reads (the annotated gates below are
machine-checked by elastic-lint's hot-path checker).

Lockstep safety: staging changes WHEN placement happens, never what is
dispatched — dispatch order, shapes and programs remain pure functions
of (task data, k, canonical rows), identical on every process.  The
enabling env is master-forwarded, so a world can never mix donated and
undonated step programs.
"""

from __future__ import annotations

import contextlib
import os
import queue
import threading
import time
from collections import deque
from typing import Callable, Iterable

import jax
import numpy as np

from elasticdl_tpu.telemetry.anatomy import TIMELINE
from elasticdl_tpu.trainer.stacking import (
    timed_hook,
    PreStacked,
    assemble_canonical_group,
    prestacked_weights,
    resolve_steps_per_dispatch,
)

DEVICE_PREFETCH_ENV = "ELASTICDL_TPU_DEVICE_PREFETCH"
BOUNDARY_FUSION_ENV = "ELASTICDL_TPU_BOUNDARY_FUSION"
PIPELINE_DEPTH_ENV = "ELASTICDL_TPU_PIPELINE_DEPTH"
# absolute byte budget for staged-but-untaken device buffers (admission
# control when --pipeline_depth > 1); unset = live device headroom from
# memory_stats, and backends without allocator stats stay unbounded —
# the ledger's device_stager component still records what is held
STAGING_BUDGET_ENV = "ELASTICDL_TPU_STAGING_BUDGET_BYTES"

# bounded in-flight dispatch window: how many dispatched groups may be
# un-retired before the consumer blocks on the oldest.  2 = the classic
# one-behind pipeline (group N computes while group N+1 enqueues).
# --pipeline_depth overrides it per job (resolve_pipeline_depth).
RETIRE_WINDOW = 2
# staging queue depth: 1 = double buffering (one staged group ready
# while the consumer's current group dispatches; the stager may be
# assembling a third).  Scales as pipeline_depth - 1 when tuned.
STAGE_DEPTH = 1

_STAGE_KIND_GROUP = "group"
_STAGE_KIND_ERROR = "error"
_STAGE_KIND_DONE = "done"
_STAGE_KIND_MARK = "mark"


# ---- flag resolution (shared by all three runtimes) -------------------------


# explicit spellings the env accepts — the env must parse like the
# flag's parse_bool, not truthy-string: "0"/"false" silently ENABLING
# the feature on some hosts would build the mixed donated/undonated
# world the uniformity contract forbids, and an unrecognized spelling
# (typo) must fail SAFE (off, with an error log), never silently on
_FALSEY_ENV = frozenset({"", "0", "false", "no", "off"})
_TRUTHY_ENV = frozenset({"1", "true", "yes", "on"})


def resolve_device_prefetch(flag=None) -> bool:
    """THE enablement rule: the master's ``--device_prefetch`` flag when
    set, else the master-forwarded env (workers never see the flag in
    argv; parse_bool spellings — ``1``/``true``/``yes``/``on`` on,
    ``0``/``false``/``no``/``off``/unset off, anything else logs an
    ERROR and stays off).  Resolved once per runtime at build time."""
    if flag is not None:
        return bool(flag)
    raw = os.environ.get(DEVICE_PREFETCH_ENV, "").strip().lower()
    if raw in _TRUTHY_ENV:
        return True
    if raw not in _FALSEY_ENV:
        from elasticdl_tpu.utils.log_utils import default_logger

        default_logger.error(
            "Unrecognized %s=%r; device prefetch stays OFF (use "
            "1/true/yes/on or 0/false/no/off)",
            DEVICE_PREFETCH_ENV,
            raw,
        )
    return False


def resolve_boundary_fusion(flag=None) -> bool:
    """THE ``--boundary_fusion`` enablement rule — same discipline as
    :func:`resolve_device_prefetch` (master flag wins, else the
    master-forwarded env, parse_bool spellings, typo fails SAFE to
    off).  Cross-task staging additionally requires device prefetch:
    the runtimes fuse only when BOTH resolve on."""
    if flag is not None:
        return bool(flag)
    raw = os.environ.get(BOUNDARY_FUSION_ENV, "").strip().lower()
    if raw in _TRUTHY_ENV:
        return True
    if raw not in _FALSEY_ENV:
        from elasticdl_tpu.utils.log_utils import default_logger

        default_logger.error(
            "Unrecognized %s=%r; boundary fusion stays OFF (use "
            "1/true/yes/on or 0/false/no/off)",
            BOUNDARY_FUSION_ENV,
            raw,
        )
    return False


def resolve_pipeline_depth(flag=None) -> int:
    """THE ``--pipeline_depth`` resolution: the master flag when set,
    else the master-forwarded env, else :data:`RETIRE_WINDOW` (2 — the
    classic one-behind pipeline, byte-identical to the pre-flag
    behavior).  Values clamp to >= 1; a malformed env logs an ERROR
    and keeps the default (fail SAFE to the proven depth)."""
    if flag is not None:
        return max(1, int(flag))
    raw = os.environ.get(PIPELINE_DEPTH_ENV, "").strip()
    if not raw:
        return RETIRE_WINDOW
    try:
        depth = int(raw)
    except ValueError:
        depth = 0
    if depth < 1:
        from elasticdl_tpu.utils.log_utils import default_logger

        default_logger.error(
            "Unrecognized %s=%r; pipeline depth stays %d (use a "
            "positive integer)",
            PIPELINE_DEPTH_ENV,
            raw,
            RETIRE_WINDOW,
        )
        return RETIRE_WINDOW
    return depth


def staging_budget_bytes() -> int | None:
    """Byte budget for staged-but-untaken device buffers, or None for
    unbounded: the env override when set, else half the fullest device's
    headroom (its ``bytes_limit`` less ``bytes_in_use`` and less
    ``bytes_reserved``, where the step's temporaries stand:
    ``telemetry/memory.py::device_headroom_bytes``), else None —
    backends without allocator stats (CPU) stay unbounded and rely on
    the queue bound alone."""
    raw = os.environ.get(STAGING_BUDGET_ENV, "").strip()
    if raw:
        try:
            budget = int(raw)
        except ValueError:
            from elasticdl_tpu.utils.log_utils import default_logger

            default_logger.error(
                "Unrecognized %s=%r; staging budget falls back to "
                "device headroom (use a byte count)",
                STAGING_BUDGET_ENV,
                raw,
            )
        else:
            return budget if budget > 0 else None
    from elasticdl_tpu.telemetry.memory import device_headroom_bytes

    headroom = device_headroom_bytes()
    return None if headroom is None else headroom // 2


def resolve_donate_state(args) -> bool:
    """THE ``--donate_state`` resolution — one definition site for what
    was copied verbatim into all three runtimes (local_executor, worker,
    lockstep).  Default True: the state buffers are always dead after
    the optimizer update."""
    return bool(getattr(args, "donate_state", True))


def stage_depth(anatomy, depth=None) -> int:  # elastic-lint: hot-path
    """The retire window for a dispatch loop: ``depth``
    (``--pipeline_depth``, default :data:`RETIRE_WINDOW`) groups in
    flight normally; 1 (retire every group before the next dispatch)
    under ``--step_anatomy``, whose ``enqueue``/``ready_wait`` split
    needs exact per-group walls — the barrier the design doc documents
    as the cost of measuring."""
    if anatomy is None:
        return RETIRE_WINDOW if depth is None else depth
    return 1


# ---- heartbeat-shipped staging totals ---------------------------------------

_TOTALS_LOCK = threading.Lock()
# monotone process-lifetime totals; ms accumulate as floats here and
# ship as ints (the wire merge is utils.merge.max_merge_counters,
# integer-only — truncating per-event sub-ms samples would lose them)
_TOTALS = {
    "groups": 0,
    "stall_ms": 0.0,
    "stage_ms": 0.0,
    "boundaries": 0,
    "boundary_stall_ms": 0.0,
}
_active = False
# monotonic stamp armed at a task boundary (after the previous task's
# window drained and its bookkeeping ran) and closed by the FIRST
# dispatch of the next task — the gap is the boundary_stall counter.
# Single-writer (the dispatch thread), so no lock on the mark itself.
_boundary_mark = None


def _note_staged(stage_secs: float):
    global _active
    with _TOTALS_LOCK:
        _active = True
        _TOTALS["groups"] += 1
        _TOTALS["stage_ms"] += stage_secs * 1000.0


def _note_stall(stall_secs: float):
    global _active
    with _TOTALS_LOCK:
        _active = True
        _TOTALS["stall_ms"] += stall_secs * 1000.0


def _boundary_armed() -> bool:
    """Whether boundary-stall timing is worth a clock read: a stager
    ran in this process (the pipelined paths) or an anatomy recorder is
    installed (the serial measurement windows)."""
    if _active:
        return True
    from elasticdl_tpu.telemetry.anatomy import get_recorder

    return get_recorder() is not None


def note_task_boundary():  # elastic-lint: hot-path
    """Arm the boundary-stall clock — called at each task boundary, as
    soon as the previous task's window has drained and BEFORE its
    boundary bookkeeping (report, milestone checks, memory sample)
    runs, so the counter covers the whole device-idle gap the fused
    path shrinks.  Unarmed (no stager, no anatomy) this is one
    zero-arg gate call."""
    global _boundary_mark
    if not _boundary_armed():
        return
    _boundary_mark = time.monotonic()


def note_boundary_dispatch():  # elastic-lint: hot-path
    """Close a pending boundary mark: the FIRST dispatch after a task
    boundary records the device-idle gap as ``boundary_stall``.  Every
    other dispatch pays one global load and a None check."""
    global _boundary_mark, _active
    mark = _boundary_mark
    if mark is None:
        return
    _boundary_mark = None
    gap = time.monotonic() - mark
    with _TOTALS_LOCK:
        _active = True
        _TOTALS["boundaries"] += 1
        _TOTALS["boundary_stall_ms"] += gap * 1000.0


def clear_boundary_mark():
    """Disarm a pending boundary mark (end of run / stream teardown),
    so the final task's mark never attributes cross-run idle time to
    the first dispatch of a LATER run in the same process."""
    global _boundary_mark
    _boundary_mark = None


def heartbeat_snapshot() -> dict:  # elastic-lint: hot-path
    """Monotone staging totals for ``HeartbeatRequest.prefetch``; ``{}``
    when no stager ever ran in this process (the off state costs one
    global load, like the anatomy snapshot)."""
    if not _active:
        return {}
    with _TOTALS_LOCK:
        return {
            "groups": int(_TOTALS["groups"]),
            "stall_ms": int(_TOTALS["stall_ms"]),
            "stage_ms": int(_TOTALS["stage_ms"]),
            "boundaries": int(_TOTALS["boundaries"]),
            "boundary_stall_ms": int(_TOTALS["boundary_stall_ms"]),
        }


def _reset_totals_for_tests():
    global _active, _boundary_mark
    with _TOTALS_LOCK:
        _active = False
        _boundary_mark = None
        for key in _TOTALS:
            _TOTALS[key] = 0


# ---- staged groups ----------------------------------------------------------


class RetiredBufferError(RuntimeError):
    """A staged group's device buffers were taken twice.

    With ``donate_batch`` the buffers are DONATED to the first dispatch
    — XLA reuses their memory for outputs — so a second consumer would
    read garbage (or trip JAX's deleted-Array check).  Single ``take()``
    ownership turns that read-after-retire into a loud, immediate
    error at the staging layer."""


class StagedGroup:
    """One dispatch group, already assembled and device-resident.

    ``kind``: ``KIND_STACKED`` — ``placed`` is the ``(features, labels,
    weights)`` stacked ``(k, rows, ...)`` tuple for one
    ``train_steps_stacked`` dispatch; ``KIND_SINGLES`` — ``placed`` is a
    list of per-batch ``(features, labels, mask)`` tuples (a trailing
    partial group, dispatched through the single-step program).

    ``hook_features``: one host features ref per STEP, for the
    consumer's ``pre_batch`` hook cadence.  ``host``: the original host
    item(s), kept so a failed dispatch can retry from host memory after
    the staged buffers were donated.

    ``error``: staging itself (assemble or placement) failed — no
    placed buffers exist, but ``host`` still carries the group, so the
    task-stream worker can fall back to its serial per-minibatch
    retry/containment path instead of losing the error policy the
    serial loop had (the grouped runtimes re-raise, which is exactly
    what their serial path would have done)."""

    KIND_STACKED = "stacked"
    KIND_SINGLES = "singles"

    __slots__ = (
        "kind",
        "steps",
        "records",
        "hook_features",
        "host",
        "error",
        "nbytes",
        "_placed",
        "_release",
    )

    def __init__(
        self, kind, placed, steps, records, hook_features, host=None,
        error=None, nbytes=0, release=None,
    ):
        self.kind = kind
        self.steps = int(steps)
        self.records = int(records)
        self.hook_features = hook_features
        self.host = host
        self.error = error
        # staged device bytes this group holds until taken (memory
        # ledger accounting); `release` hands them back to the stager
        self.nbytes = int(nbytes)
        self._placed = placed
        self._release = release

    def take(self):
        """Transfer ownership of the placed buffers to the caller —
        exactly once.  The dispatch donates them; a second take is a
        read-after-retire and raises :class:`RetiredBufferError`."""
        if self._placed is None:
            raise RetiredBufferError(
                "staged dispatch group already taken: its device buffers "
                "were donated to the dispatch and no longer exist"
            )
        placed, self._placed = self._placed, None
        if self._release is not None:
            release, self._release = self._release, None
            release(self.nbytes)
        return placed


def _batch_rows(tree) -> int:
    leaves = jax.tree_util.tree_leaves(tree)
    return int(np.shape(leaves[0])[0]) if leaves else 0


def _assemble_prestacked(item: PreStacked):
    """A ready-made ``(k, B, ...)`` group with its all-ones scan-shape
    weights (``stacking.prestacked_weights`` — the shared policy)."""
    return (item.features, item.labels, prestacked_weights(item))


def _place_assembled(trainer, kind, assembled):
    if kind == StagedGroup.KIND_STACKED:
        feats, labels, weights = assembled
        return (
            trainer.place_stacked(feats),
            trainer.place_stacked(labels),
            trainer.place_stacked(weights),
        )
    return [
        (
            trainer.place_batch(f),
            trainer.place_batch(l),
            trainer.place_batch(m),
        )
        for f, l, m in assembled
    ]


class TaskMark:
    """In-stream task delimiter for cross-task staging
    (:func:`run_pipelined_task_stream` and the task-stream worker's
    fused loop).

    ``START`` — the next groups belong to this task (open its span,
    reset per-task accounting); ``END`` — all of the task's groups were
    handed over (retire the window, run the boundary bookkeeping).  The
    stager forwards marks in stream order and FLUSHES any pending
    partial group at a mark, so a trailing partial of task N never
    merges with task N+1's first batch — grouping (and therefore the
    dispatch-shape sequence) stays per-task, bit-identical to the
    drain-at-boundary path.

    ``payload`` carries an arbitrary serial item for tasks that do not
    stage (evaluation, non-training types): the consumer processes it
    inline at the mark's position, preserving stream order."""

    START = "start"
    END = "end"

    __slots__ = ("kind", "tid", "task", "payload")

    def __init__(self, kind, tid, task, payload=None):
        self.kind = kind
        self.tid = tid
        self.task = task
        self.payload = payload


# ---- the staging thread -----------------------------------------------------


class DeviceStager:
    """Background host->device staging for a canonical-shape batch
    stream.

    A daemon thread walks ``batches`` (plain ``(features, labels)``
    pairs and/or :class:`~elasticdl_tpu.trainer.stacking.PreStacked`
    groups), forms dispatch groups of ``k`` under the shared grouping
    policy, assembles and PLACES them on device, and hands
    :class:`StagedGroup` objects to the consumer through a bounded
    queue (:data:`STAGE_DEPTH`) — so the h2d transfer of group N+1
    overlaps the device compute of group N.  Groups arrive in exact
    stream order (single producer, FIFO queue); a producer-side error
    is re-raised by :meth:`next_staged` at its position in the stream.

    Placement from a non-dispatch thread is safe: ``device_put`` /
    ``make_array_from_callback`` are process-local (no collectives), and
    the trainer's placement caches are pure memoizations (a benign
    double-compute under the GIL).  The lockstep dispatch ORDER stays on
    the consumer thread, untouched.
    """

    def __init__(
        self,
        get_trainer: Callable,
        batches: Iterable,
        k,
        canonical_rows: int,
        deterministic_auto: bool = False,
        depth: int = STAGE_DEPTH,
    ):
        self._get_trainer = get_trainer
        self._batches = batches
        self._k = k
        self._rows = int(canonical_rows)
        self._deterministic_auto = deterministic_auto
        self._depth = max(1, int(depth))
        self._q: queue.Queue = queue.Queue(maxsize=self._depth)
        # admission control (memory ledger): how many staged groups may
        # wait un-taken.  Starts at the configured depth and degrades —
        # loudly, once — to 1 when staged bytes would exceed the budget
        # (env override, else half the live device headroom).
        self._admitted = self._depth
        self._stop = threading.Event()
        self._done = False
        # staged-but-untaken device bytes (memory ledger): incremented
        # when a group lands in the queue, released at take()
        self._bytes_lock = threading.Lock()
        self._staged_bytes = 0  # guarded-by: _bytes_lock
        from elasticdl_tpu.telemetry import memory as memory_mod

        self._ledger_cb = lambda: self._staged_bytes
        memory_mod.register_component(
            memory_mod.COMPONENT_DEVICE_STAGER, self._ledger_cb
        )
        self._thread = threading.Thread(
            target=self._produce, name="device-stage", daemon=True
        )
        self._thread.start()

    # ---- producer ----------------------------------------------------------

    def _put(self, item) -> bool:
        """Bounded put that aborts when the consumer closed us (the
        queue bound is the device-memory bound: at most ``depth`` staged
        groups wait while one more is in assembly).  A degraded
        ``_admitted`` shrinks the effective bound below the queue's
        configured maxsize."""
        while not self._stop.is_set():
            # only the DEGRADED state needs the poll: at full admission
            # the queue's own maxsize is the bound, and its blocking put
            # wakes the instant the consumer takes a slot
            if (
                self._admitted < self._depth
                and self._q.qsize() >= self._admitted
            ):
                self._stop.wait(0.02)
                continue
            try:
                self._q.put(item, timeout=0.1)
                return True
            except queue.Full:
                continue
        return False

    def _admit(self, nbytes: int):
        """Admission against the staging budget: when the staged-but-
        untaken bytes plus this group would exceed it, degrade the
        staging depth to 1 for the rest of this stager's life."""
        if self._admitted <= 1:
            return
        budget = staging_budget_bytes()
        if budget is None:
            return
        with self._bytes_lock:
            pending = self._staged_bytes
        if pending + nbytes <= budget:
            return
        self._admitted = 1
        from elasticdl_tpu.utils.log_utils import default_logger

        default_logger.warning(
            "device_stager: staged bytes %d + next group %d exceed the "
            "staging budget %d; degrading staging depth %d -> 1 (set "
            "%s to override the budget)",
            pending,
            nbytes,
            budget,
            self._depth,
            STAGING_BUDGET_ENV,
        )

    def _stage(self, trainer, assemble, steps, records, hooks, host):
        """Assemble + place one group; a STAGING failure (bad batch
        shape, transient placement error) degrades to an error-carrying
        group instead of poisoning the stream — upstream ITERATOR
        errors (decode) keep the crash contract via ``_produce``'s
        outer handler."""
        t0 = time.monotonic()
        try:
            kind, assembled = assemble()
            placed = _place_assembled(trainer, kind, assembled)
        except Exception as e:  # noqa: BLE001 — consumer decides policy
            staged = StagedGroup(
                StagedGroup.KIND_SINGLES,
                None,
                steps=steps,
                records=records,
                hook_features=hooks,
                host=host,
                error=e,
            )
            return self._put((_STAGE_KIND_GROUP, staged))
        from elasticdl_tpu.telemetry.memory import pytree_bytes

        nbytes = pytree_bytes(placed)
        staged = StagedGroup(
            kind,
            placed,
            steps=steps,
            records=records,
            hook_features=hooks,
            host=host,
            nbytes=nbytes,
            release=self._release_bytes,
        )
        self._admit(nbytes)
        with self._bytes_lock:
            self._staged_bytes += nbytes
        _note_staged(time.monotonic() - t0)
        return self._put((_STAGE_KIND_GROUP, staged))

    def _release_bytes(self, nbytes: int):
        with self._bytes_lock:
            self._staged_bytes -= nbytes

    def _stage_plain(self, trainer, group) -> bool:
        return self._stage(
            trainer,
            lambda: assemble_canonical_group(
                trainer, group, self._k, self._rows
            ),
            steps=len(group),
            records=sum(n for _f, _l, n in group),
            hooks=[f for f, _l, _n in group],
            host=list(group),
        )

    def _stage_prestacked(self, trainer, item: PreStacked) -> bool:
        return self._stage(
            trainer,
            lambda: (
                StagedGroup.KIND_STACKED,
                _assemble_prestacked(item),
            ),
            steps=item.num_steps,
            records=item.num_records,
            hooks=[item.sample_features] * item.num_steps,
            host=item,
        )

    def _produce(self):
        group: list = []
        try:
            trainer = self._get_trainer()
            for item in self._batches:
                if self._stop.is_set():
                    return
                if isinstance(item, TaskMark):
                    # task boundary: flush the pending partial group —
                    # grouping resets per task, so the dispatch-shape
                    # sequence matches the drain-at-boundary path —
                    # then forward the mark in stream order
                    if group:
                        if not self._stage_plain(trainer, group):
                            return
                        group = []
                    if not self._put((_STAGE_KIND_MARK, item)):
                        return
                    continue
                if isinstance(item, PreStacked):
                    # ready-made group: flush pending plain batches first
                    # (stream order is the contract)
                    if group:
                        if not self._stage_plain(trainer, group):
                            return
                        group = []
                    if not self._stage_prestacked(trainer, item):
                        return
                    continue
                features, labels = item
                if self._k == "auto":
                    self._k = resolve_steps_per_dispatch(
                        self._k,
                        (features, labels),
                        deterministic=self._deterministic_auto,
                    )
                group.append((features, labels, _batch_rows(labels)))
                if len(group) == self._k:
                    if not self._stage_plain(trainer, group):
                        return
                    group = []
            if group and not self._stage_plain(trainer, group):
                return
        except BaseException as e:  # noqa: BLE001 — re-raised by consumer
            self._put((_STAGE_KIND_ERROR, e))
            return
        self._put((_STAGE_KIND_DONE, None))

    # ---- consumer ----------------------------------------------------------

    def next_event(self, anatomy=None):
        """The next stream event as a ``(kind, payload)`` pair — a
        staged GROUP, a :class:`TaskMark` (cross-task streams), DONE,
        or a producer-side ERROR (returned, not raised: the cross-task
        consumer owns the boundary policy).

        The blocking wait is the CONSUMER-VISIBLE h2d cost — everything
        the stager overlapped is gone from this thread's critical path —
        so under ``--step_anatomy`` it is attributed to the
        ``h2d_transfer`` phase (whose share dropping vs prefetch-off is
        the goodput smoke's gate)."""
        if self._done:
            return _STAGE_KIND_DONE, None
        if anatomy is None:
            t0 = time.monotonic()
            kind, payload = self._q.get()
            _note_stall(time.monotonic() - t0)
        else:
            from elasticdl_tpu.telemetry.anatomy import PHASE_H2D_TRANSFER

            with anatomy.phase(PHASE_H2D_TRANSFER):
                t0 = time.monotonic()
                kind, payload = self._q.get()
                _note_stall(time.monotonic() - t0)
        if kind in (_STAGE_KIND_DONE, _STAGE_KIND_ERROR):
            self._done = True
        return kind, payload

    def next_staged(self, anatomy=None) -> StagedGroup | None:
        """The next :class:`StagedGroup` in stream order, or None at end
        of stream; a producer-side error (decode failure, placement
        failure) is re-raised here, at its position in the stream.
        Marks, if the stream carries any, are skipped."""
        while True:
            kind, payload = self.next_event(anatomy)
            if kind == _STAGE_KIND_DONE:
                return None
            if kind == _STAGE_KIND_ERROR:
                raise payload
            if kind == _STAGE_KIND_MARK:
                continue
            return payload

    def __iter__(self):
        while True:
            staged = self.next_staged()
            if staged is None:
                return
            yield staged

    def close(self):
        """Stop the producer and release it if blocked on a full
        queue."""
        self._stop.set()
        try:
            while True:
                self._q.get_nowait()
        except queue.Empty:
            pass
        self._thread.join(timeout=5)
        # drop the ledger callback: a closed stager (and any untaken
        # staged buffers) must not be pinned by the component registry
        from elasticdl_tpu.telemetry import memory as memory_mod

        memory_mod.unregister_component(
            memory_mod.COMPONENT_DEVICE_STAGER, self._ledger_cb
        )


# ---- the pipelined dispatch loop --------------------------------------------


class _DispatchEngine:
    """The dispatch half of the pipelined loops — single-take dispatch,
    hook cadence, retire-behind window, anatomy attribution — shared by
    :func:`run_pipelined_steps` (per-task) and
    :func:`run_pipelined_task_stream` (cross-task), so the parity pins
    on one cover both."""

    def __init__(self, get_trainer, depth, pre_batch, post_group, ctx, anatomy):
        self._get_trainer = get_trainer
        self._depth = depth
        # the timeline's step_bookkeeping spans, as on the serial path
        self._pre = timed_hook(pre_batch)
        self._post = timed_hook(post_group)
        self._ctx = ctx
        self._anatomy = anatomy
        self._inflight: deque = deque()
        self.processed = 0

    def _retire_push(self, out):
        # async retire-behind: keep at most `depth` dispatched groups
        # un-retired; blocking on the OLDEST keeps the device queue
        # bounded while group N+1's enqueue overlaps group N's compute
        self._inflight.append(out)
        if len(self._inflight) > self._depth:
            jax.block_until_ready(self._inflight.popleft())

    def _dispatched(self, out):
        # blocking mode: the dispatch retires here (ready_wait), so the
        # window (depth 1 under anatomy) has nothing to hold
        if self._anatomy is None:
            self._retire_push(out)
        else:
            self._anatomy.ready_wait(out)

    def _dispatch_stacked(self, trainer, placed):
        with self._ctx():
            out = trainer.train_steps_stacked(*placed)
        self._dispatched(out)

    def _dispatch_singles(self, trainer, placed_list):
        for placed in placed_list:
            with self._ctx():
                out = trainer.train_step(*placed)
            self._dispatched(out)

    def dispatch(self, staged: StagedGroup, run_hooks: bool = True):
        if staged.error is not None:
            # staging failed: the serial path would have raised from the
            # same pad/place call on this thread — keep that contract
            # (lockstep report-and-crash, LocalExecutor propagation)
            raise staged.error
        if run_hooks and self._pre is not None:
            for feats in staged.hook_features:
                self._pre(feats)
        trainer = self._get_trainer()
        note_boundary_dispatch()
        if staged.kind == StagedGroup.KIND_STACKED:
            self._dispatch_stacked(trainer, staged.take())
        else:
            self._dispatch_singles(trainer, staged.take())
        self.processed += staged.records
        if self._post is not None:
            self._post()
        if self._anatomy is not None:
            self._anatomy.commit(
                steps=staged.steps,
                records=staged.records,
                step=getattr(trainer, "step", None),
            )

    def drain(self):
        # the boundary barrier: every dispatched group retires before
        # the caller may report its task (exactly-once)
        while self._inflight:
            jax.block_until_ready(self._inflight.popleft())


def run_pipelined_steps(
    get_trainer: Callable,
    batches: Iterable,
    k,
    pre_batch: Callable | None = None,
    post_group: Callable | None = None,
    dispatch_ctx: Callable | None = None,
    deterministic_auto: bool = False,
    *,
    canonical_rows: int,
    anatomy=None,
    pipeline_depth: int | None = None,
) -> int:
    """The ``--device_prefetch`` body of
    :func:`~elasticdl_tpu.trainer.stacking.run_stacked_steps`
    (staging stands on the canonical shape: a pure function of
    config).  Same grouping policy, same hook cadence
    (``pre_batch`` once per step before its group dispatches — the
    PreStacked precedent — ``post_group`` after every dispatch), same
    accounting; what changes is the execution discipline:

    - the FIRST group runs on the serial path (its ``pre_batch`` lazily
      creates the trainer the stager needs for placement), then a
      :class:`DeviceStager` stages every later group off-thread;
    - dispatch outputs retire one group behind in a window of
      :func:`stage_depth` (``pipeline_depth``, default 2; 1 — the
      per-group barrier — under ``--step_anatomy``), and the function
      DRAINS before returning, so the caller's task report never covers
      an un-retired group (exactly-once holds across the async window).
    """
    ctx = dispatch_ctx or contextlib.nullcontext
    rows = int(canonical_rows)
    depth = stage_depth(anatomy, pipeline_depth)
    engine = _DispatchEngine(
        get_trainer, depth, pre_batch, post_group, ctx, anatomy
    )
    _dispatch = engine.dispatch
    # the warm-up group's hooks run here as its batches arrive
    pre_batch = timed_hook(pre_batch)

    # the batch-stream seam: the time inside next() is host_fetch on
    # whichever thread pulls — this one for the warm-up group, the
    # stager's afterwards
    it = TIMELINE.timed_fetches(batches)

    # ---- warmup: first group on the serial path (creates the trainer) ------
    warm: list = []
    warm_prestacked = None
    ended = False
    while True:
        item = next(it, None)
        if item is None:
            ended = True
            break
        if isinstance(item, PreStacked):
            warm_prestacked = item
            break
        features, labels = item
        if pre_batch is not None:
            pre_batch(features)
        if k == "auto":
            k = resolve_steps_per_dispatch(
                k, (features, labels), deterministic=deterministic_auto
            )
        warm.append((features, labels, _batch_rows(labels)))
        if len(warm) == k:
            break

    def _warm_stage(trainer, kind_assembled):
        kind, assembled = kind_assembled
        return kind, _place_assembled(trainer, kind, assembled)

    if warm:
        trainer = get_trainer()
        kind, placed = _warm_stage(
            trainer, assemble_canonical_group(trainer, warm, k, rows)
        )
        _dispatch(
            StagedGroup(
                kind,
                placed,
                steps=len(warm),
                records=sum(n for _f, _l, n in warm),
                hook_features=(),
            ),
            run_hooks=False,  # already ran as the batches arrived
        )
    if warm_prestacked is not None:
        if pre_batch is not None:
            # one call per STEP, the plain path's hook cadence
            for _ in range(warm_prestacked.num_steps):
                pre_batch(warm_prestacked.sample_features)
        trainer = get_trainer()
        kind, placed = _warm_stage(
            trainer,
            (StagedGroup.KIND_STACKED, _assemble_prestacked(warm_prestacked)),
        )
        _dispatch(
            StagedGroup(
                kind,
                placed,
                steps=warm_prestacked.num_steps,
                records=warm_prestacked.num_records,
                hook_features=(),
            ),
            run_hooks=False,
        )

    if ended:
        engine.drain()
        return engine.processed

    # ---- steady state: stage off-thread, retire one group behind -----------
    stager = DeviceStager(
        get_trainer,
        it,
        k,
        rows,
        deterministic_auto=deterministic_auto,
        depth=max(1, depth - 1),
    )
    try:
        while True:
            staged = stager.next_staged(anatomy)
            if staged is None:
                break
            _dispatch(staged)
    finally:
        stager.close()
        # the task-boundary barrier: every dispatched group retires
        # before the caller can report the task (exactly-once)
        engine.drain()
    return engine.processed


def run_pipelined_task_stream(
    get_trainer: Callable,
    tasks: Iterable,
    k,
    pre_batch: Callable | None = None,
    post_group: Callable | None = None,
    dispatch_ctx: Callable | None = None,
    deterministic_auto: bool = False,
    *,
    canonical_rows: int,
    anatomy=None,
    task_start: Callable | None = None,
    task_done: Callable | None = None,
    pipeline_depth: int | None = None,
) -> int:
    """The ``--boundary_fusion`` task loop: one persistent
    :class:`DeviceStager` walks the WHOLE task stream, so task N+1's
    first groups assemble and place while task N's last groups compute,
    and the boundary barrier shrinks from "drain + re-stage from host"
    to "retire the previous task's in-flight window".

    ``tasks`` yields ``(task_id, task, batches)`` triples (the
    ``TaskPrefetcher`` consumer shape); the stream is pulled from the
    STAGER thread, so host decode keeps running through boundaries too.
    ``task_start(task_id, task)`` runs when a task's first group is
    about to dispatch; ``task_done(task_id, task, records)`` is the
    boundary bookkeeping (report, milestone checks, memory sample) and
    runs only AFTER that task's own dispatch window drained — a task is
    reported exactly when all its groups retired (exactly-once), while
    the stager concurrently stages the next task.

    The FIRST task runs through :func:`run_pipelined_steps` (its serial
    warmup creates the trainer the persistent stager needs for
    placement).  If ``task_done`` raises (lease reclaimed, preemption
    fence), the stager closes and every staged-but-undispatched group
    dies un-taken — never dispatched, never reported, so a re-lease of
    those tasks replays them from scratch.  Bit-exactness: marks flush
    the grouping per task, so dispatch order, shapes and outputs are
    identical to the drain-at-boundary path.
    """
    it = iter(tasks)
    first = next(it, None)
    if first is None:
        return 0
    tid, task, batches = first
    if task_start is not None:
        task_start(tid, task)
    n = run_pipelined_steps(
        get_trainer,
        batches,
        k,
        pre_batch=pre_batch,
        post_group=post_group,
        dispatch_ctx=dispatch_ctx,
        deterministic_auto=deterministic_auto,
        canonical_rows=canonical_rows,
        anatomy=anatomy,
        pipeline_depth=pipeline_depth,
    )
    total = n
    note_task_boundary()
    if task_done is not None:
        task_done(tid, task, n)

    ctx = dispatch_ctx or contextlib.nullcontext
    depth = stage_depth(anatomy, pipeline_depth)
    engine = _DispatchEngine(
        get_trainer, depth, pre_batch, post_group, ctx, anatomy
    )

    def _flatten():
        # runs on the stager thread: marks delimit tasks in-stream, so
        # the producer flushes grouping at each boundary and the
        # consumer learns boundaries in exact stream order
        for tid_, task_, batches_ in it:
            yield TaskMark(TaskMark.START, tid_, task_)
            yield from TIMELINE.timed_fetches(batches_)
            yield TaskMark(TaskMark.END, tid_, task_)

    # one extra queue slot vs the per-task stager: the END/START marks
    # occupy slots at each boundary, and the whole point is for the
    # next task's first group to be staged while they drain
    stager = DeviceStager(
        get_trainer,
        _flatten(),
        k,
        int(canonical_rows),
        deterministic_auto=deterministic_auto,
        depth=depth,
    )
    task_records = 0
    try:
        while True:
            kind, payload = stager.next_event(anatomy)
            if kind == _STAGE_KIND_DONE:
                break
            if kind == _STAGE_KIND_ERROR:
                raise payload
            if kind == _STAGE_KIND_MARK:
                if payload.kind == TaskMark.START:
                    task_records = 0
                    if task_start is not None:
                        task_start(payload.tid, payload.task)
                else:
                    # the fused boundary: retire THIS task's window,
                    # then its bookkeeping — the stager keeps staging
                    # the next task's groups meanwhile
                    engine.drain()
                    note_task_boundary()
                    if task_done is not None:
                        task_done(payload.tid, payload.task, task_records)
                continue
            engine.dispatch(payload)
            total += payload.records
            task_records += payload.records
    finally:
        stager.close()
        engine.drain()
        clear_boundary_mark()
    return total
