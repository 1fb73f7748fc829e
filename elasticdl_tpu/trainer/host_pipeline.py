"""Cross-task host-pipeline prefetch: decode ahead while the device runs.

The reference's worker overlapped host decode with device compute through
tf.data's internal threading plus ``prefetch(1)``
(``elasticdl/python/worker/worker.py:977``).  The TPU runtimes get the
same overlap here, one level up: a single producer thread walks the TASK
stream (dispatcher -> task -> minibatch pipeline) and fills a bounded
queue, so while the device executes the current stacked dispatch — and
while the main thread is blocked in host->device transfers, both of which
release the GIL — the next task's records are already being read, decoded
and batched.  On a single-core host this is the only free parallelism
there is: decode burns the core exactly when the main thread isn't using
it.

Ordering and accounting semantics are unchanged from the serial loop:
batches arrive in task order, a task's batches are contiguous, and the
caller reports each task only after consuming all its batches — so
exactly-once accounting, milestone hooks, and lockstep's deterministic
batch stream behave identically.

With ``--device_prefetch`` (trainer/device_pipeline.py) this queue is
the DECODE stage of a three-deep pipeline: the TaskPrefetcher reads and
decodes task N+1's records while the device-side stager pads/places the
next dispatch group of task N and the device computes the current one —
decode -> stage -> compute, each on its own thread, each bounded.
"""

from __future__ import annotations

import queue
import threading
import time
from typing import Callable, Iterator

import jax
import numpy as np

from elasticdl_tpu.telemetry.anatomy import (
    PHASE_PRODUCE_BATCH,
    PHASE_PRODUCE_BLOCKED,
    PHASE_PRODUCE_NEXT_TASK,
    TIMELINE,
)
from elasticdl_tpu.trainer.stacking import PreStacked

_TASK = "task"
_BATCH = "batch"
_END_TASK = "end"
_ERROR = "error"
_DONE = "done"


class TaskPrefetcher:
    """Iterate ``(task_id, task, batches)`` triples with the host
    pipeline running ahead on a background thread.

    ``next_task()`` -> ``(task_id, task)`` or ``(_, None)`` at end of
    stream (the dispatcher contract).  ``make_batches(task)`` -> iterable
    of minibatches.  Decode-ahead memory is bounded by BOTH
    ``max_buffered_batches`` (size it in batches the consumer works
    ahead by, e.g. two ``--steps_per_dispatch`` groups) and
    ``max_buffered_bytes`` (so large-image batches can't multiply the
    count bound into gigabytes).

    Each yielded ``batches`` iterator must be consumed before advancing
    the outer iteration (the runtimes' per-task loops do).
    """

    def __init__(
        self,
        next_task: Callable,
        make_batches: Callable,
        max_buffered_batches: int = 32,
        max_buffered_bytes: int = 64 << 20,
    ):
        self._next_task = next_task
        self._make_batches = make_batches
        # the queue itself is unbounded; _put blocks on whichever budget
        # (batch count or BYTES) is exhausted first — a flat batch count
        # alone would buffer gigabytes for large-image models
        self._q: queue.Queue = queue.Queue()
        self._max_batches = max(1, max_buffered_batches)
        self._max_bytes = max_buffered_bytes
        self._credit = threading.Condition()
        self._buffered_batches = 0
        self._buffered_bytes = 0
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._produce, name="task-prefetch", daemon=True
        )
        self._started = False
        # memory-ledger accounting: the decode-ahead buffer is exactly
        # the bytes budget this class already tracks (GIL-atomic read)
        from elasticdl_tpu.telemetry import memory as memory_mod

        self._ledger_cb = lambda: self._buffered_bytes
        memory_mod.register_component(
            memory_mod.COMPONENT_TASK_PREFETCHER, self._ledger_cb
        )

    # ---- producer ---------------------------------------------------------

    @staticmethod
    def _batch_bytes(batch) -> int:
        # module-level imports: this runs once per produced batch on the
        # decode thread — a per-call import chain (jax + numpy +
        # stacking) was measurable overhead on the prefetch hot path
        if isinstance(batch, PreStacked):
            batch = (batch.features, batch.labels)
        return sum(
            getattr(leaf, "nbytes", 0) or np.asarray(leaf).nbytes
            for leaf in jax.tree_util.tree_leaves(batch)
        )

    def _put(self, item, count: int = 0, nbytes: int = 0) -> bool:
        """Blocking put that aborts when the consumer closed us; batch
        items charge both buffering budgets (``count`` = batches carried
        — a PreStacked group counts its steps, not 1), and marker items
        (task boundaries etc., count=0) are throttled by total queue
        depth so a stream of empty tasks cannot drain the whole
        dispatcher into the unbounded queue.  A put that had to wait
        for its budget is on the timeline as ``produce_blocked``."""
        marker_cap = 2 * self._max_batches + 8
        blocked_from = None
        with self._credit:
            while not self._stop.is_set():
                if count == 0:
                    if self._q.qsize() < marker_cap:
                        self._q.put(item)
                        break
                elif (
                    self._buffered_batches < self._max_batches
                    and self._buffered_bytes < self._max_bytes
                ):
                    self._buffered_batches += count
                    self._buffered_bytes += nbytes
                    self._q.put(item)
                    break
                if blocked_from is None:
                    blocked_from = time.perf_counter_ns()
                self._credit.wait(timeout=0.1)
            else:
                return False
        if blocked_from is not None:
            TIMELINE.record(PHASE_PRODUCE_BLOCKED, blocked_from)
        return True

    def _release(self, count: int, nbytes: int):
        with self._credit:
            self._buffered_batches -= count
            self._buffered_bytes -= nbytes
            self._credit.notify()

    def _timed_batches(self, task):
        """``make_batches(task)`` with each batch's making — read,
        decode, shuffle, stack: the time inside ``next()`` — on the
        timeline as ``produce_batch``, with the thread's CPU time beside
        the wall time (equal: the thread ran; CPU short of wall: it
        waited, for the disk, the interpreter lock or a core) and the
        batch's bytes.  Yields the batch, its bytes and the batch
        ordinal, which the consumer's ``host_fetch`` of it carries too."""
        it = iter(self._make_batches(task))
        while True:
            t0, cpu0 = time.perf_counter_ns(), time.thread_time_ns()
            try:
                batch = next(it)
            except StopIteration:
                return
            cpu_ns = time.thread_time_ns() - cpu0
            nbytes = max(1, self._batch_bytes(batch))
            yield batch, nbytes, TIMELINE.record_batch(
                PHASE_PRODUCE_BATCH, t0, cpu_ns, nbytes
            )

    def _produce(self):
        try:
            while not self._stop.is_set():
                t0 = time.perf_counter_ns()
                tid, task = self._next_task()
                TIMELINE.record(PHASE_PRODUCE_NEXT_TASK, t0)
                if task is None:
                    break
                if not self._put((_TASK, (tid, task))):
                    return
                for batch, nbytes, ordinal in self._timed_batches(task):
                    count = (
                        batch.num_steps
                        if isinstance(batch, PreStacked)
                        else 1
                    )
                    if not self._put(
                        (_BATCH, (batch, count, nbytes, ordinal)),
                        count=count,
                        nbytes=nbytes,
                    ):
                        return
                if not self._put((_END_TASK, tid)):
                    return
        except BaseException as e:  # noqa: BLE001 — re-raised by consumer
            self._put((_ERROR, e))
            return
        self._put((_DONE, None))

    # ---- consumer ---------------------------------------------------------

    def __iter__(self) -> Iterator:
        if not self._started:
            self._started = True
            self._thread.start()
        while True:
            kind, payload = self._q.get()
            if kind == _DONE:
                return
            if kind == _ERROR:
                raise payload
            assert kind == _TASK, f"protocol error: {kind} outside a task"
            tid, task = payload
            batches = self._task_batches(tid)
            yield tid, task, batches
            # the runtimes drain `batches` inside the loop body; guard
            # against a partial consumer (e.g. an exception path) by
            # draining the remainder so the stream stays aligned
            for _ in batches:
                pass

    def _task_batches(self, expect_tid) -> Iterator:
        while True:
            kind, payload = self._q.get()
            if kind == _BATCH:
                batch, count, nbytes, ordinal = payload
                self._release(count, nbytes)
                # the seam above records this fetch under the number
                # the producer made the batch under
                TIMELINE.set_batch_ordinal(ordinal)
                yield batch
            elif kind == _END_TASK:
                assert payload == expect_tid
                return
            elif kind == _ERROR:
                raise payload
            else:  # pragma: no cover — protocol violation
                raise AssertionError(f"unexpected {kind} inside task")

    def close(self):
        """Stop the producer and release it if blocked on a full queue."""
        self._stop.set()
        try:
            while True:
                self._q.get_nowait()
        except queue.Empty:
            pass
        if self._started:
            self._thread.join(timeout=5)
        # drop the ledger callback so a closed prefetcher (and the
        # batches it pins) is not kept alive by the component registry
        from elasticdl_tpu.telemetry import memory as memory_mod

        memory_mod.unregister_component(
            memory_mod.COMPONENT_TASK_PREFETCHER, self._ledger_cb
        )
