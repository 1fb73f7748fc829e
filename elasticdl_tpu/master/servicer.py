"""Master service logic, transport-agnostic.

Reference: ``elasticdl/python/master/servicer.py`` — get_task (with the
WAIT sentinel while eval tasks drain), report_task_result,
report_evaluation_metrics, report_version.  The TPU build adds a heartbeat
RPC: with no Kubernetes watch stream in local/managed deployments, worker
liveness is detected by heartbeat timeout (SURVEY §5 failure detection),
and the master uses the same channel to signal a quiesce for mesh
re-formation.

The servicer takes and returns the plain dataclasses of
:mod:`elasticdl_tpu.rpc.messages`; the gRPC adapter in
``elasticdl_tpu.rpc.service`` does serialization only.  That split is what
enables the reference's in-process-master test pattern
(``tests/in_process_master.py``): tests wire a worker directly to this
object with zero transport.
"""

from __future__ import annotations

import heapq
import threading
import time
from collections import deque

from elasticdl_tpu.rpc import messages as msg
from elasticdl_tpu.utils.constants import TaskType
from elasticdl_tpu.utils.log_utils import default_logger as logger
from elasticdl_tpu.utils.merge import (
    last_merge_counters,
    max_merge_counters,
    max_merge_phase_stats,
)

# the outage-class RPC counters whose RISE (vs the previous beat) flips
# the /healthz degraded-network flag
_OUTAGE_CLASS_COUNTERS = frozenset({"deadline_exceeded", "unavailable"})


class MasterServicer:
    def __init__(
        self,
        minibatch_size: int,
        task_dispatcher,
        evaluation_service=None,
        instance_manager=None,
        clock=time.monotonic,
    ):
        self._task_d = task_dispatcher
        self._minibatch_size = minibatch_size
        self._evaluation_service = evaluation_service
        self._instance_manager = instance_manager
        # injectable monotonic clock: the fleet simulator
        # (elasticdl_tpu.fleetsim) drives this REAL servicer on a
        # virtual clock; production always passes the default
        self._clock = clock
        self._lock = threading.Lock()
        # GIL-atomic int: unlocked reads (get_task responses, the
        # get_model_version/cluster_version properties) are the
        # documented pattern; every WRITE takes the lock
        self._version = 0  # guarded-by: _lock (writes)
        # worker_id -> last heartbeat wall-clock
        self._heartbeats: dict[int, float] = {}  # guarded-by: _lock
        # expiry-ordered (beat_time, worker_id) min-heap over the SAME
        # beats: the dead-worker sweep pops only entries at/past the
        # timeout cutoff instead of scanning every worker per poll.
        # Entries are lazily invalidated — a newer beat makes the old
        # entry stale, detected by comparing against _heartbeats
        self._hb_heap: list[tuple[float, int]] = []  # guarded-by: _lock
        # heartbeat fan-in coalescing: handlers ENQUEUE (GIL-atomic
        # deque append, no lock) and one drainer at a time applies the
        # whole backlog under ONE _lock acquisition — per-call lock work
        # is O(1) amortized at any world size.  Readers of heartbeat-fed
        # state drain first (blocking), so visibility is unchanged:
        # a beat enqueued before a read is applied before it.
        self._hb_pending: deque = deque()
        self._hb_drain_lock = threading.Lock()
        # fan-in shape observability: beats applied, batches drained,
        # largest batch (mirrored onto the elasticdl_heartbeat_*
        # metrics; the fleetsim scale budgets read them too)
        self._hb_stats = {"beats": 0, "batches": 0, "max_batch": 0}  # guarded-by: _lock
        # dead-worker sweep cost (real time, perf_counter): count,
        # total ms, max ms — the sweep-latency scaling budget's source
        self._sweep_stats = {"count": 0, "ms": 0.0, "max_ms": 0.0}  # guarded-by: _lock
        # externally-reported failures (pod events); cleared only by
        # forget_worker so a racing in-flight heartbeat can't erase them
        self._marked_dead: set[int] = set()  # guarded-by: _lock
        self._cluster_version = 0  # guarded-by: _lock (writes)
        self._quiesce = False  # guarded-by: _lock (writes)
        # lockstep step-task stream: seq -> memoized TaskResponse.  Every
        # process of a multi-process world pulls the same seq and must see
        # the same answer (the lockstep invariant); WAIT is the only
        # non-final answer and is never memoized.
        self._step_stream: dict[int, msg.TaskResponse] = {}  # guarded-by: _stream_lock
        self._stream_lock = threading.Lock()
        self._first_stream_pull_at: float | None = None  # guarded-by: _stream_lock
        # hot-standby world assignments addressed by standby id (the
        # RPC-transported analogue of the local backend's stdin line:
        # pods cannot receive stdin, so k8s standbys poll for these)
        self._world_assignments: dict[str, dict] = {}  # guarded-by: _lock
        self._standby_drain = False  # guarded-by: _lock
        # (worker_id, model_version) observers — chaos invariant checking
        self._version_observers: list = []
        # worker-shipped RPC outcome totals (heartbeat `rpc` field,
        # rpc/stats.py): monotone per worker, summed onto /metrics.
        # Never cleared by forget_worker — an evicted worker's failures
        # happened and the exposed totals must stay monotone
        self._worker_rpc_stats: dict[int, dict[str, int]] = {}  # guarded-by: _lock
        # worker-shipped step-anatomy phase totals (heartbeat `phases`
        # field, telemetry/anatomy.py): same monotone max-merge
        # discipline, mirrored onto the elasticdl_step_phase_* families
        self._worker_phase_stats: dict[int, dict] = {}  # guarded-by: _lock
        self._worker_prefetch_stats: dict[int, dict] = {}  # guarded-by: _lock
        # worker-shipped memory-ledger snapshots (heartbeat `memory`
        # field, telemetry/memory.py).  Memory goes DOWN as well as up,
        # so "current" merges timestamped last-writer-wins (per-key
        # stamps alongside the values) while the peak watermarks keep
        # the monotone max rule
        self._worker_memory: dict[int, dict[str, int]] = {}  # guarded-by: _lock
        self._worker_memory_stamps: dict[int, dict[str, float]] = {}  # guarded-by: _lock
        self._worker_memory_peaks: dict[int, dict[str, int]] = {}  # guarded-by: _lock
        # fleet-wide aggregates maintained INCREMENTALLY by the merge
        # rule (utils/merge.py ``totals=``): scrape-time reads are
        # O(keys), not an O(world_size) walk under the lock
        self._rpc_totals: dict[str, int] = {}  # guarded-by: _lock
        self._phase_totals: dict[str, dict] = {}  # guarded-by: _lock
        self._prefetch_totals: dict[str, int] = {}  # guarded-by: _lock
        self._memory_totals: dict[str, int] = {}  # guarded-by: _lock
        self._memory_peak_totals: dict[str, int] = {}  # guarded-by: _lock
        # on-demand profiler command (request_profile): the latest armed
        # window, redistributed on every heartbeat response until its
        # TTL lapses.  Published as an immutable dict so responses can
        # read it GIL-atomically without the lock
        self._profile_command: dict | None = None  # guarded-by: _lock (writes)
        self._profile_window_seq = 0  # guarded-by: _lock
        # liveness-vs-progress split (/healthz): when any worker last
        # ADVANCED its step sample (heartbeat `step` / version report) —
        # a hung-but-alive job heartbeats forever but this stops moving
        self._last_step_sample = 0  # guarded-by: _lock
        self._last_step_sample_at: float | None = None  # guarded-by: _lock
        # when a heartbeat last raised an outage-class RPC counter
        # (deadline_exceeded / unavailable): the /healthz
        # degraded_network flag's timestamp.  Only a rise RELATIVE TO A
        # PREVIOUS BEAT counts — a worker's first beat to THIS master
        # seeds silently, since rpc/stats.py totals are process-
        # lifetime and a restarted master would otherwise re-learn
        # hours-old failures as a fresh degradation
        self._net_degraded_at: float | None = None  # guarded-by: _lock
        self._rpc_seen: set[int] = set()  # guarded-by: _lock
        # eval-metrics dedup: lease ids whose metrics were already
        # accumulated.  The is_active guard alone only covers RECLAIMED
        # leases — a duplicate delivery (lost reply + retry) arrives
        # while the lease is still active and would double-count the
        # accumulated metrics.  Lease ids are never reused, so the set
        # needs no generation reset.
        self._eval_metrics_seen: set[int] = set()  # guarded-by: _lock
        self._duplicate_eval_drops = 0  # guarded-by: _lock (writes)
        # telemetry event sink: ``fn(event_name, **fields)`` for quiesce
        # lifecycle records; never raises into an RPC
        self._event_sink = None
        # trace-context provider: ``fn(task_id) -> dict`` supplying the
        # dispatch span's {"trace_id", "span_id"} so every TaskResponse
        # carries the trace it belongs to (telemetry/tracing.py)
        self._trace_provider = None
        # peer state replication (elasticdl_tpu.replication): heartbeat
        # advertisements feed the directory; the harvested restore stage
        # is served to the generation it was staged for
        self._replica_directory = None
        self._restore_stage: dict | None = None  # guarded-by: _lock
        # master high availability (master/journal.py): the journal sink
        # records generation bumps and step-stream memo resolutions; the
        # boot id identifies THIS master process so re-homing workers
        # can tell a restart from a blip; the rehome sink lets the
        # Master adopt re-homed orphans
        self._journal = None
        self._boot_id = ""
        self._rehome_sink = None
        self._stage_released_sink = None
        if evaluation_service is not None:
            evaluation_service.set_master_servicer(self)

    def add_version_observer(self, callback):
        """``callback(worker_id, model_version)`` on every version
        report; must not call back into the servicer."""
        self._version_observers.append(callback)

    def set_event_sink(self, sink):
        """``sink(event, **fields)`` — the telemetry event log."""
        self._event_sink = sink

    def set_trace_provider(self, provider):
        """``provider(task_id) -> dict`` — the task's trace context."""
        self._trace_provider = provider

    def set_replica_directory(self, directory):
        """Attach the replication subsystem's master-side directory;
        heartbeats then carry advertisements up and peer maps down."""
        self._replica_directory = directory

    def set_journal(self, journal):
        """Attach the control-plane journal (master/journal.py):
        generation bumps and lockstep stream resolutions are recorded
        from here — the two transitions only the servicer sees."""
        self._journal = journal

    def set_boot_id(self, boot_id: str):
        self._boot_id = boot_id

    @property
    def boot_id(self) -> str:
        return self._boot_id

    def set_stage_released_sink(self, sink):
        """``sink(generation)`` fires once when a staged replica set has
        been fetched by every process of its generation (journal hook)."""
        self._stage_released_sink = sink

    def set_rehome_sink(self, sink):
        """``sink(worker_id, pid, kept, requeued)`` after a successful
        re-home — the Master adopts the orphan and emits telemetry."""
        self._rehome_sink = sink

    def _trace_for(self, task_id: int) -> dict:
        if self._trace_provider is None:
            return {}
        try:
            return self._trace_provider(task_id) or {}
        except Exception:  # noqa: BLE001 — tracing never breaks RPCs
            logger.exception("Trace provider failed")
            return {}

    def _emit(self, event: str, **fields):
        if self._event_sink is None:
            return
        try:
            self._event_sink(event, **fields)
        except Exception:  # noqa: BLE001 — telemetry never breaks RPCs
            logger.exception("Telemetry event sink failed")

    # ---- model version ----------------------------------------------------

    def get_model_version(self) -> int:
        return self._version

    # ---- RPC handlers -----------------------------------------------------

    def get_task(self, request: msg.GetTaskRequest) -> msg.TaskResponse:
        """Lease the next task for ``worker_id``.

        Contract: a WAIT response means "new work may appear later —
        poll again after a short sleep".  Callers MUST NOT busy-spin on
        WAIT: the servicer runs in-process for local jobs, and a spin
        loop starves the thread that holds the last re-queued lease
        (worker/worker.py sleeps between polls; reference
        worker.py:498-505 does the same).
        """
        # every task pull is a liveness signal (cheap implicit heartbeat;
        # the worker's background heartbeat covers long compute gaps)
        with self._lock:
            self._note_beat_locked(request.worker_id, self._clock())
        if request.task_type == int(TaskType.EVALUATION):
            task_id, task = self._task_d.get_eval_task(request.worker_id)
        else:
            task_id, task = self._task_d.get(request.worker_id)

        if task is not None:
            return msg.task_to_response(
                task_id,
                task,
                self._version,
                self._minibatch_size,
                trace=self._trace_for(task_id),
            )
        if (not self._task_d.finished()) or (
            self._task_d.invoke_deferred_callback()
        ):
            # in-flight tasks may fail and re-queue, or a deferred callback
            # (SAVE_MODEL) just created new work: tell the worker to wait
            # (reference servicer.py:53-62)
            return msg.TaskResponse(
                type=int(TaskType.WAIT),
                model_version=self._version,
                minibatch_size=self._minibatch_size,
            )
        return msg.TaskResponse(
            model_version=self._version, minibatch_size=self._minibatch_size
        )

    def get_step_task(
        self, request: msg.GetStepTaskRequest
    ) -> msg.TaskResponse:
        """Resolve one lockstep stream position (multi-process SPMD).

        The first request for an unresolved ``seq`` leases the next task
        (eval tasks interleave ahead of training, like the reference's
        worker-side interleave) and memoizes the response; all other
        processes replay it.  End-of-job is memoized too, so every
        process terminates at the same seq.
        """
        with self._lock:
            if request.cluster_version != self._cluster_version:
                # stale world (pre-re-formation): tell it to exit WITHOUT
                # recording a heartbeat — a forgotten worker's last pull
                # must not re-register it as a ghost liveness entry
                return msg.TaskResponse(
                    model_version=self._version,
                    minibatch_size=self._minibatch_size,
                )
            self._note_beat_locked(request.worker_id, self._clock())
        with self._stream_lock:
            if request.cluster_version != self._cluster_version:
                # re-checked here because the fence test above runs under
                # a DIFFERENT lock: a reform landing in the gap would let
                # this stale request lease from the just-recovered queue
                # and memoize into the new world's stream (the int read
                # is GIL-atomic; _lock is not needed to compare it)
                return msg.TaskResponse(
                    model_version=self._version,
                    minibatch_size=self._minibatch_size,
                )
            if self._first_stream_pull_at is None:
                self._first_stream_pull_at = self._clock()
            memo = self._step_stream.get(request.seq)
            if memo is not None:
                return memo
            task_id, task = self._task_d.get_eval_task(request.worker_id)
            if task is None:
                task_id, task = self._task_d.get(request.worker_id)
            if task is not None:
                resp = msg.task_to_response(
                    task_id,
                    task,
                    self._version,
                    self._minibatch_size,
                    trace=self._trace_for(task_id),
                )
                self._memoize_stream(
                    request.seq, resp, request.cluster_version
                )
                return resp
            if (not self._task_d.finished()) or (
                self._task_d.invoke_deferred_callback()
            ):
                return msg.TaskResponse(
                    type=int(TaskType.WAIT),
                    model_version=self._version,
                    minibatch_size=self._minibatch_size,
                )
            resp = msg.TaskResponse(
                model_version=self._version,
                minibatch_size=self._minibatch_size,
            )
            self._memoize_stream(request.seq, resp, request.cluster_version)
            return resp

    # keep this many newest memoized seqs (RAM and journal snapshots).
    # Lockstep processes cannot diverge by more than one dispatch group
    # — every step's collectives need all of them — so hundreds of seqs
    # of slack is unreachable; without a bound a long single-generation
    # run makes each journal snapshot O(steps) (quadratic on disk)
    STREAM_MEMO_KEEP = 512

    # lock-holding: _stream_lock
    def _memoize_stream(
        self, seq: int, resp: msg.TaskResponse, generation: int
    ):
        """Memoize + journal one stream resolution (under _stream_lock),
        pruning memos far behind the frontier.  ``generation`` is the
        fence the request passed — journaled with the record so replay
        can drop a resolution that raced a reform's generation bump
        (its record may land AFTER the ``generation`` record, where the
        live master's reset no longer has a replay analogue)."""
        self._step_stream[seq] = resp
        self._journal_stream(seq, resp, generation)
        if len(self._step_stream) > self.STREAM_MEMO_KEEP + 64:
            for old in sorted(self._step_stream)[
                : len(self._step_stream) - self.STREAM_MEMO_KEEP
            ]:
                del self._step_stream[old]

    def _journal_stream(
        self, seq: int, resp: msg.TaskResponse, generation: int
    ):
        """Journal a memoized stream resolution: a restarted master must
        answer already-resolved seqs identically or the lockstep worlds
        desync across the outage."""
        if self._journal is None:
            return
        from dataclasses import asdict

        try:
            self._journal.record_stream(seq, asdict(resp), generation)
        except Exception:  # noqa: BLE001 — journaling never breaks RPCs
            logger.exception("Journal stream record failed")

    def stream_snapshot(self) -> dict:
        """JSON-safe copy of the memoized step stream (journal
        snapshots; keys stringified — JSON would coerce them anyway and
        replay expects str)."""
        with self._stream_lock:
            return self._stream_snapshot_locked()

    # lock-holding: _stream_lock
    def _stream_snapshot_locked(self) -> dict:
        from dataclasses import asdict

        return {
            str(seq): asdict(resp)
            for seq, resp in self._step_stream.items()
        }

    def journal_stream_snapshot(self):
        """Journal a full stream-memo capture from UNDER the stream lock,
        so the record's file position IS its capture point.  The master
        writes one right after each main snapshot: the main snapshot's
        stream field was captured before the (dispatcher-atomic) append,
        and a memo resolved in that window would otherwise replay as
        ordered-before-the-snapshot and be lost."""
        if self._journal is None:
            return
        with self._stream_lock:
            try:
                self._journal.record_stream_snapshot(
                    self._stream_snapshot_locked()
                )
            except Exception:  # noqa: BLE001 — journaling never breaks RPCs
                logger.exception("Journal stream snapshot failed")

    def reset_step_stream(self):
        """Drop all memoized stream state (mesh re-formation: the new
        world restarts at seq 0 and re-pulls from the recovered queue)."""
        with self._stream_lock:
            self._step_stream.clear()
            self._first_stream_pull_at = None

    def bump_cluster_version(self) -> int:
        """Advance the world generation; stale workers are fenced out of
        the step stream from this point on."""
        with self._lock:
            self._cluster_version += 1
            version = self._cluster_version
        if self._journal is not None:
            # the fence record is flushed inline: a restarted master
            # resurrecting a fenced generation would un-fence stale
            # workers (version monotonicity would break silently)
            self._journal.record_generation(version)
        return version

    def first_stream_pull_at(self) -> float | None:
        """Monotonic time of the first step-task resolution since the last
        stream reset — the 'new world is training again' signal used to
        measure re-formation latency."""
        with self._stream_lock:
            return self._first_stream_pull_at

    def report_task_result(self, request: msg.ReportTaskResultRequest):
        if request.err_message:
            logger.warning("Worker reported error: %s", request.err_message)
        self._task_d.report(
            request.task_id,
            success=not request.err_message,
            exec_counters=request.exec_counters,
        )

    def report_version(self, request: msg.ReportVersionRequest):
        """Workers ping their step count; drives step-based eval triggers
        (reference servicer.py:79-85, where the PS did the pinging)."""
        with self._lock:
            self._version = max(self._version, request.model_version)
            if request.model_version > self._last_step_sample:
                # a version report is the strongest progress signal —
                # it advances the /healthz staleness clock too
                self._last_step_sample = int(request.model_version)
                self._last_step_sample_at = self._clock()
        for callback in self._version_observers:
            try:
                callback(request.worker_id, request.model_version)
            except Exception:  # noqa: BLE001 — observers never break RPCs
                logger.exception("Version observer failed")
        if self._evaluation_service is not None:
            self._evaluation_service.add_evaluation_task_if_needed(
                master_locking=False, model_version=request.model_version
            )

    def report_evaluation_metrics(
        self, request: msg.ReportEvaluationMetricsRequest
    ):
        if request.task_id >= 0 and not self._task_d.is_active(
            request.task_id
        ):
            # the lease was reclaimed (timeout) or already re-queued; the
            # re-run will report — accepting this copy would double-count
            logger.warning(
                "Dropping eval metrics for inactive task %d", request.task_id
            )
            return
        if request.task_id >= 0:
            # duplicate delivery (lost reply + client retry): the lease
            # is STILL active — the is_active guard above cannot see the
            # duplicate, so metric accumulation dedups by lease id here.
            # This is what makes report_evaluation_metrics honest in
            # MASTER_RETRYABLE_METHODS' "task_id-deduplicated" claim.
            with self._lock:
                if request.task_id in self._eval_metrics_seen:
                    self._duplicate_eval_drops += 1
                    duplicate = True
                else:
                    self._eval_metrics_seen.add(request.task_id)
                    duplicate = False
            if duplicate:
                logger.warning(
                    "Dropping duplicate eval metrics for task %d "
                    "(re-delivered report)",
                    request.task_id,
                )
                return
        if self._evaluation_service is not None:
            self._evaluation_service.report_evaluation_metrics(
                request.model_outputs,
                request.labels,
                evaluated_version=request.evaluated_version,
            )

    def heartbeat(self, request: msg.HeartbeatRequest) -> msg.HeartbeatResponse:
        """Coalesced heartbeat fan-in.

        The handler ENQUEUES the beat (a GIL-atomic deque append) and
        triggers a drain; whichever thread wins the drain lock applies
        the WHOLE backlog under one ``_lock`` acquisition, so at fleet
        scale the per-beat lock work amortizes to O(1) instead of a
        lock handshake per RPC.  Losers return immediately — their beat
        is already enqueued and the holder's post-release re-check (or
        any reader's blocking drain) applies it.  The response needs
        only GIL-atomic reads (``_quiesce``/``_cluster_version``/
        ``_boot_id`` are writes-guarded), so it never waits on the lock
        either.  ``utils/merge.py`` max-merge makes batched application
        order-insensitive: a drained batch produces the same totals as
        per-request application (test-pinned).
        """
        self._hb_pending.append((request, self._clock()))
        self._drain_heartbeats()
        # per-beat side effects that take OTHER locks stay per-request
        # (the instance manager and replica directory synchronize
        # themselves; folding them into the _lock batch would nest locks)
        if self._instance_manager is not None:
            self._instance_manager.on_heartbeat(request.worker_id)
        generation = self._cluster_version
        replica_peers: dict = {}
        if self._replica_directory is not None:
            if request.replica:
                self._replica_directory.update(
                    request.worker_id, request.replica
                )
            replica_peers = self._replica_directory.peers(generation)
        return msg.HeartbeatResponse(
            should_quiesce=self._quiesce,
            cluster_version=generation,
            replica_peers=replica_peers,
            boot_id=self._boot_id,
            profile=self._live_profile_command(),
        )

    def _drain_heartbeats(self, block: bool = False):
        """Apply the pending heartbeat backlog: one ``_lock``
        acquisition per drained batch.  ``block=True`` (readers of
        heartbeat-fed state) ALWAYS acquires the drain lock — even when
        the deque looks empty — because a concurrent drainer may have
        popped a beat it has not yet applied; batches are applied while
        the drain lock is held, so acquiring it synchronizes with every
        in-flight drain and the guarantee holds: a beat whose handler
        enqueued it before the read is applied before the read."""
        if block:
            while True:
                self._hb_drain_lock.acquire()
                try:
                    self._drain_batch_locked()
                finally:
                    self._hb_drain_lock.release()
                if not self._hb_pending:
                    return
        while self._hb_pending:
            if not self._hb_drain_lock.acquire(blocking=False):
                # another thread is draining; it re-checks the deque
                # after releasing, so the beat this caller enqueued
                # cannot be stranded
                return
            try:
                self._drain_batch_locked()
            finally:
                self._hb_drain_lock.release()

    # lock-holding: _hb_drain_lock
    def _drain_batch_locked(self):
        batch = []
        while True:
            try:
                batch.append(self._hb_pending.popleft())
            except IndexError:
                break
        if batch:
            self._apply_heartbeat_batch(batch)

    def _apply_heartbeat_batch(self, batch: list):
        """One lock acquisition for the whole drained batch, FIFO."""
        with self._lock:
            self._hb_stats["beats"] += len(batch)
            self._hb_stats["batches"] += 1
            if len(batch) > self._hb_stats["max_batch"]:
                self._hb_stats["max_batch"] = len(batch)
            for request, now in batch:
                self._apply_heartbeat_locked(request, now)

    # lock-holding: _lock
    def _apply_heartbeat_locked(self, request, now: float):
        self._note_beat_locked(request.worker_id, now)
        if request.step > self._last_step_sample:
            # progress, not mere liveness: the /healthz staleness
            # clock resets only when the fleet's step ADVANCES
            self._last_step_sample = int(request.step)
            self._last_step_sample_at = now
        first_contact = request.worker_id not in self._rpc_seen
        self._rpc_seen.add(request.worker_id)
        if request.rpc:
            # worker-shipped RPC outcome totals: max-merge (one
            # shared rule, utils/merge.py) so a reordered beat can
            # never walk a counter backward; the fleet aggregate is
            # maintained incrementally for O(keys) scrapes
            rose = max_merge_counters(
                self._worker_rpc_stats.setdefault(request.worker_id, {}),
                request.rpc,
                watch=_OUTAGE_CLASS_COUNTERS,
                totals=self._rpc_totals,
            )
            if rose and not first_contact:
                # an outage-class counter moved SINCE THE LAST beat:
                # the link is degraded as of now (the /healthz flag)
                self._net_degraded_at = now
        if request.phases:
            # step-anatomy phase totals: nested max-merge (ms,
            # count, and each log bucket are all monotone per
            # worker), aggregated across workers incrementally
            max_merge_phase_stats(
                self._worker_phase_stats.setdefault(request.worker_id, {}),
                request.phases,
                totals=self._phase_totals,
            )
        if request.prefetch:
            # device-prefetch staging totals: the same monotone
            # max-merge rule as the RPC outcome counters
            max_merge_counters(
                self._worker_prefetch_stats.setdefault(
                    request.worker_id, {}
                ),
                request.prefetch,
                totals=self._prefetch_totals,
            )
        if request.memory and isinstance(request.memory, dict):
            # memory-ledger snapshot: current values are NON-monotone
            # (a swap releases, a queue drains) so they merge by the
            # sender's sample stamp — newest wins, reordered/duplicate
            # beats absorbed — while peaks keep the max rule.  Both
            # aggregates are incremental: the current total carries
            # signed deltas (it goes down on release)
            try:
                at = float(request.memory.get("at", 0.0))
            except (TypeError, ValueError):
                at = None
            if at is not None:
                wid = request.worker_id
                current = request.memory.get("current")
                if isinstance(current, dict):
                    # complete=True: the ledger ships its WHOLE current
                    # map each beat, so a component the snapshot no
                    # longer carries (its owner unregistered — a closed
                    # stager, a drained queue) is deleted from the
                    # merged view instead of ratcheting at its last
                    # nonzero reading
                    last_merge_counters(
                        self._worker_memory.setdefault(wid, {}),
                        current,
                        at,
                        self._worker_memory_stamps.setdefault(wid, {}),
                        totals=self._memory_totals,
                        complete=True,
                    )
                peaks = request.memory.get("peak")
                if isinstance(peaks, dict):
                    max_merge_counters(
                        self._worker_memory_peaks.setdefault(wid, {}),
                        peaks,
                        totals=self._memory_peak_totals,
                    )

    # lock-holding: _lock
    def _note_beat_locked(self, worker_id: int, now: float):
        """Record one liveness signal: the latest-beat map AND the
        expiry-ordered heap the incremental dead-worker sweep pops.

        The heap self-compacts when stale (superseded) entries dominate:
        the sweep only removes entries when heartbeat-timeout detection
        is ON (``dead_workers(timeout > 0)``), so a deployment running
        on external failure events alone (``--heartbeat_timeout_secs
        0``) would otherwise leak one tuple per beat forever.  The
        rebuild is O(live workers) and runs at most once per ~3n
        pushes — amortized O(1) per beat.
        """
        self._heartbeats[worker_id] = now
        heapq.heappush(self._hb_heap, (now, worker_id))
        if len(self._hb_heap) > 64 and (
            len(self._hb_heap) > 4 * len(self._heartbeats)
        ):
            # every live worker's newest beat is in _heartbeats, and
            # the sweep's re-pushed expired entries carry exactly that
            # time too — the rebuilt heap preserves sweep semantics
            self._hb_heap = [
                (at, wid) for wid, at in self._heartbeats.items()
            ]
            heapq.heapify(self._hb_heap)

    # ---- on-demand profiler windows -----------------------------------------

    # how long a request_profile command keeps riding heartbeat
    # responses.  Sized to cover a few beats from every worker; while
    # unexpired, a second request_profile is ABSORBED (returns the same
    # window id) — that plus the workers' window_id dedup is what makes
    # the method safe under RPC re-delivery
    PROFILE_COMMAND_TTL_SECS = 30.0

    def _live_profile_command(self) -> dict:
        """The unexpired profile command for heartbeat responses ({}
        otherwise).  Lock-free: the command dict is published immutably
        (writes-guarded), so this is a GIL-atomic reference read plus a
        clock compare — the heartbeat response path never waits."""
        cmd = self._profile_command
        if cmd is None:
            return {}
        if self._clock() - cmd["issued_at"] >= self.PROFILE_COMMAND_TTL_SECS:
            return {}
        return {
            "window_id": cmd["window_id"],
            "num_steps": cmd["num_steps"],
            "out_dir": cmd["out_dir"],
        }

    def request_profile(
        self, request: msg.RequestProfileRequest
    ) -> msg.RequestProfileResponse:
        """Arm an on-demand XLA profiler window: the command rides down
        on every heartbeat response until the TTL lapses, and each
        worker opens one capture into its telemetry dir at runtime — a
        live degraded job gets profiled without a relaunch.  Arming
        while a command is still being distributed returns the EXISTING
        window id (the absorbed-replay contract the idempotency
        registry claims)."""
        with self._lock:
            now = self._clock()
            cmd = self._profile_command
            if cmd is not None and (
                now - cmd["issued_at"] < self.PROFILE_COMMAND_TTL_SECS
            ):
                return msg.RequestProfileResponse(
                    accepted=True,
                    window_id=cmd["window_id"],
                    reason="window already being distributed (absorbed)",
                )
            self._profile_window_seq += 1
            try:
                num_steps = max(1, int(request.num_steps))
            except (TypeError, ValueError):
                num_steps = 5
            try:
                seconds = max(0.0, float(request.seconds))
            except (TypeError, ValueError):
                seconds = 0.0
            self._profile_command = {
                "window_id": self._profile_window_seq,
                "num_steps": num_steps,
                "out_dir": str(request.out_dir or ""),
                # > 0: the window is sized by the clock, not in steps
                "seconds": seconds,
                "issued_at": now,
            }
            window_id = self._profile_window_seq
        logger.info(
            "On-demand profile window %d armed (%d steps)",
            window_id,
            num_steps,
        )
        return msg.RequestProfileResponse(accepted=True, window_id=window_id)

    # ---- master high availability: the re-homing handshake -----------------

    def rehome_worker(
        self, request: msg.RehomeRequest
    ) -> msg.RehomeResponse:
        """A worker that outlived a master outage reconnects: fence its
        generation, reconcile its in-flight leases against the
        journal-restored active set (re-accept what it presents, requeue
        what it does not), and hand it to the master for adoption."""
        started_at = time.monotonic()
        with self._lock:
            generation = self._cluster_version
        if request.cluster_version != generation:
            # stale world: reject WITHOUT recording a heartbeat, exactly
            # like the step-stream fence
            return msg.RehomeResponse(
                accepted=False,
                cluster_version=generation,
                boot_id=self._boot_id,
            )
        presented = {int(t) for t in request.lease_ids}
        kept, requeued = self._task_d.reconcile_leases(
            request.worker_id, presented
        )
        with self._lock:
            self._note_beat_locked(request.worker_id, self._clock())
        if self._rehome_sink is not None:
            try:
                self._rehome_sink(
                    request.worker_id, request.pid, kept, requeued,
                    started_at,
                )
            except Exception:  # noqa: BLE001 — adoption/telemetry must
                # not fail the handshake the worker depends on
                logger.exception("Rehome sink failed")
        return msg.RehomeResponse(
            accepted=True,
            cluster_version=generation,
            boot_id=self._boot_id,
            accepted_leases=sorted(kept),
        )

    def restore_control_state(
        self,
        cluster_version: int,
        model_version: int,
        stream: dict | None = None,
    ):
        """Install journal-replayed control state (master restart):
        the generation fence, the model-version floor, and the memoized
        lockstep step-stream (so already-resolved seqs replay
        identically to the pre-outage answers)."""
        with self._lock:
            self._cluster_version = int(cluster_version)
            self._version = max(self._version, int(model_version))
        memos = {}
        for seq, resp in (stream or {}).items():
            try:
                memos[int(seq)] = msg.TaskResponse(**resp)
            except TypeError:
                logger.warning(
                    "Dropping unreplayable stream memo for seq %s", seq
                )
        if len(memos) > self.STREAM_MEMO_KEEP:
            # same bound the live memo keeps (journals written before the
            # bound existed can replay more)
            for old in sorted(memos)[: len(memos) - self.STREAM_MEMO_KEEP]:
                del memos[old]
        with self._stream_lock:
            self._step_stream = memos

    # ---- replica restore stage ---------------------------------------------

    def set_restore_stage(self, stage: dict | None):
        """Install (or clear, with None) the harvested replica state the
        NEXT generation restores from (Master._reform_lockstep)."""
        with self._lock:
            self._restore_stage = stage

    def get_restore_state(
        self, request: msg.GetRestoreStateRequest
    ) -> msg.RestoreStateResponse:
        """Serve the staged replica set — only to the generation it was
        harvested FOR (any other asker gets the disk-fallback answer).
        Once every process of that generation has fetched its copy, the
        stage is released: the payload is a full model-state copy and
        must not sit in master RAM for the rest of the run."""
        with self._lock:
            stage = self._restore_stage
            if (
                stage is None
                or stage["generation"] != request.cluster_version
            ):
                return msg.RestoreStateResponse()
            response = msg.RestoreStateResponse(
                has=True,
                version=stage["version"],
                checksum=stage["checksum"],
                payload=stage["payload"],
            )
            served = stage.setdefault("served", set())
            served.add(request.process_id)
            world_size = stage.get("world_size", 0)
            released = bool(world_size and len(served) >= world_size)
            if released:
                self._restore_stage = None
        if released and self._stage_released_sink is not None:
            # outside the lock: the sink appends to the journal so a
            # later restart doesn't report this fully-served stage as a
            # lost replica set (a false disk-fallback)
            try:
                self._stage_released_sink(stage["generation"])
            except Exception:  # noqa: BLE001 — bookkeeping must not
                # fail the restore RPC the worker depends on
                logger.exception("Stage-released sink failed")
        return response

    # ---- hot-standby world assignments ------------------------------------

    def post_world_assignment(self, standby_id: str, assignment: dict):
        """Instance manager -> standby mailbox: ``assignment`` carries the
        same keys the local backend writes on stdin (worker_id,
        coordinator_addr, num_processes, process_id, cluster_version)."""
        with self._lock:
            self._world_assignments[standby_id] = dict(assignment)

    def get_world_assignment(
        self, request: msg.GetWorldAssignmentRequest
    ) -> msg.WorldAssignmentResponse:
        """Standby poll.  Deliberately NOT a liveness signal: a waiting
        standby is invisible to failure detection until activated."""
        with self._lock:
            assignment = self._world_assignments.pop(
                request.standby_id, None
            )
            if assignment is None:
                return msg.WorldAssignmentResponse(
                    shutdown=self._standby_drain
                )
        return msg.WorldAssignmentResponse(has=True, **assignment)

    def drain_standbys(self):
        """Job shutdown: polling standbys are told to exit."""
        with self._lock:
            self._standby_drain = True
            self._world_assignments.clear()

    # ---- failure detection / mesh re-formation hooks ----------------------

    def mark_worker_dead(self, worker_id: int):
        """External failure signal (e.g. a k8s pod DELETED event): the
        worker is reported by the next ``dead_workers`` call regardless
        of heartbeat timing — events beat timeouts at detection speed.
        One-shot: only ``forget_worker`` clears it (a racing in-flight
        heartbeat must not erase the signal)."""
        with self._lock:
            self._marked_dead.add(worker_id)

    def dead_workers(self, timeout_secs: float) -> list[int]:
        """Workers externally marked dead, plus (when ``timeout_secs >
        0``) workers whose last heartbeat is older than the timeout.

        Incremental: the sweep pops the expiry-ordered heap only down
        to the cutoff — stale entries (a newer beat exists) are
        discarded, expired ones are reported AND re-pushed so every
        subsequent sweep keeps reporting them until ``forget_worker``.
        Cost is O(beats since the last sweep + expired), not
        O(world_size), per poll."""
        sweep_started = time.perf_counter()
        self._drain_heartbeats(block=True)
        now = self._clock()
        with self._lock:
            dead = set(self._marked_dead)
            if timeout_secs > 0:
                cutoff = now - timeout_secs
                repush: list[tuple[float, int]] = []
                seen: set[int] = set()
                while self._hb_heap and self._hb_heap[0][0] < cutoff:
                    at, wid = heapq.heappop(self._hb_heap)
                    current = self._heartbeats.get(wid)
                    if current is None or current > at:
                        # forgotten, or beat again later: entry stale
                        # (the newer beat pushed its own heap entry)
                        continue
                    dead.add(wid)
                    if wid not in seen:
                        seen.add(wid)
                        repush.append((at, wid))
                for entry in repush:
                    heapq.heappush(self._hb_heap, entry)
            elapsed_ms = (time.perf_counter() - sweep_started) * 1000.0
            self._sweep_stats["count"] += 1
            self._sweep_stats["ms"] += elapsed_ms
            if elapsed_ms > self._sweep_stats["max_ms"]:
                self._sweep_stats["max_ms"] = elapsed_ms
            return sorted(dead)

    def forget_worker(self, worker_id: int):
        with self._lock:
            # the heap entry is left to die lazily: the next sweep pops
            # it, sees no _heartbeats entry, and discards it
            self._heartbeats.pop(worker_id, None)
            self._marked_dead.discard(worker_id)
            # retire the worker's memory CURRENT contribution: unlike
            # the lifetime RPC counters (monotone, deliberately kept),
            # the memory gauge is "sum of live workers' newest-stamped
            # bytes" — a dead worker's RAM is freed with its process,
            # and leaving it would ratchet the fleet gauge upward
            # across preemptions.  Peaks stay: the watermark happened,
            # and the per-worker peak map is kept so a REUSED worker id
            # max-merges against it instead of double-counting totals.
            current = self._worker_memory.pop(worker_id, None)
            self._worker_memory_stamps.pop(worker_id, None)
            if current:
                for key, value in current.items():
                    remaining = self._memory_totals.get(key, 0) - value
                    if remaining:
                        self._memory_totals[key] = remaining
                    else:
                        self._memory_totals.pop(key, None)
        if self._replica_directory is not None:
            self._replica_directory.forget_worker(worker_id)

    def live_workers(self) -> list[int]:
        """Workers with a recorded heartbeat that are not marked dead
        (the /healthz liveness view)."""
        self._drain_heartbeats(block=True)
        with self._lock:
            return sorted(set(self._heartbeats) - self._marked_dead)

    def heartbeat_ages(self) -> dict[int, float]:
        """Seconds since each live worker's last beat (scrape-time
        source of the cardinality-bounded per-worker age series)."""
        self._drain_heartbeats(block=True)
        now = self._clock()
        with self._lock:
            return {
                wid: max(0.0, now - at)
                for wid, at in self._heartbeats.items()
                if wid not in self._marked_dead
            }

    def heartbeat_stats(self) -> dict:
        """Fan-in shape: ``{"beats", "batches", "max_batch"}`` (beats
        applied, drain batches, largest single batch)."""
        self._drain_heartbeats(block=True)
        with self._lock:
            return dict(self._hb_stats)

    def sweep_stats(self) -> dict:
        """Dead-worker sweep cost: ``{"count", "ms", "max_ms"}`` (real
        perf_counter time, monotone totals)."""
        with self._lock:
            return dict(self._sweep_stats)

    def rpc_stats_totals(self) -> dict[str, int]:
        """Fleet-wide RPC outcome totals (retries, deadline_exceeded,
        unavailable): per-worker monotone maxima summed across every
        worker ever heard from — what /metrics mirrors.  Maintained
        incrementally by the merge rule, so this is O(keys), never an
        O(world_size) walk under the lock."""
        self._drain_heartbeats(block=True)
        with self._lock:
            return dict(self._rpc_totals)

    def prefetch_stats_totals(self) -> dict[str, int]:
        """Fleet-wide device-prefetch staging totals (groups staged,
        consumer stall ms, overlapped staging ms) — what /metrics
        mirrors onto the ``elasticdl_device_prefetch_*`` counters."""
        self._drain_heartbeats(block=True)
        with self._lock:
            return dict(self._prefetch_totals)

    def memory_stats_totals(self) -> dict[str, dict]:
        """Fleet-wide memory-ledger aggregates — ``{"current": {key:
        bytes}, "peak": {key: bytes}}``.  ``current`` is the sum over
        workers of each worker's NEWEST-stamped sample (it goes down on
        release — last-writer-wins, not a ratchet); ``peak`` is the sum
        of per-worker watermark maxima.  Both maintained incrementally;
        O(keys) under the lock."""
        self._drain_heartbeats(block=True)
        with self._lock:
            return {
                "current": dict(self._memory_totals),
                "peak": dict(self._memory_peak_totals),
            }

    def phase_stats_totals(self) -> dict[str, dict]:
        """Fleet-wide step-anatomy phase totals — ``{phase: {"ms":
        float, "count": int, "buckets": {str(bound): int}}}``, what
        /metrics mirrors onto the ``elasticdl_step_phase_*`` families.
        Incrementally aggregated; the copy is per-phase deep."""
        self._drain_heartbeats(block=True)
        with self._lock:
            return {
                phase: {
                    "ms": agg["ms"],
                    "count": agg["count"],
                    "buckets": dict(agg["buckets"]),
                }
                for phase, agg in self._phase_totals.items()
            }

    def last_step_age_secs(self) -> float | None:
        """Seconds since any worker last ADVANCED its step sample
        (heartbeat step / version report); None before the first
        advance.  The /healthz field that tells a hung-but-alive job
        (heartbeats flowing, this growing) from a progressing one."""
        self._drain_heartbeats(block=True)
        with self._lock:
            at = self._last_step_sample_at
        return None if at is None else max(0.0, self._clock() - at)

    # how recently an outage-class RPC counter must have moved for
    # /healthz to flag the network as degraded
    NETWORK_DEGRADED_WINDOW_SECS = 60.0

    def network_degraded(self, window_secs: float | None = None) -> bool:
        """True when a worker-shipped deadline_exceeded / unavailable
        total rose within the window (PR-8's gray-failure counters,
        surfaced as a point-in-time /healthz flag)."""
        self._drain_heartbeats(block=True)
        with self._lock:
            at = self._net_degraded_at
        if at is None:
            return False
        window = (
            self.NETWORK_DEGRADED_WINDOW_SECS
            if window_secs is None
            else window_secs
        )
        return (self._clock() - at) <= window

    @property
    def duplicate_eval_drops(self) -> int:
        """Eval-metric reports dropped by the lease-id dedup (duplicate
        delivery of a still-active lease's metrics)."""
        return self._duplicate_eval_drops

    @property
    def cluster_version(self) -> int:
        return self._cluster_version

    @property
    def is_quiescing(self) -> bool:
        return self._quiesce

    def begin_quiesce(self):
        """Ask all workers to pause at the next task boundary (first phase
        of mesh re-formation)."""
        with self._lock:
            self._quiesce = True
            generation = self._cluster_version
        from elasticdl_tpu.telemetry.events import EVENT_QUIESCE_BEGIN

        self._emit(EVENT_QUIESCE_BEGIN, generation=generation)

    def clear_quiesce(self):
        """Drop the quiesce flag WITHOUT bumping the generation (the
        graceful-degradation unpark: the relaunching re-formation
        already bumped it)."""
        with self._lock:
            self._quiesce = False

    def end_quiesce(self):
        with self._lock:
            self._quiesce = False
            self._cluster_version += 1
            generation = self._cluster_version
        if self._journal is not None:
            self._journal.record_generation(generation)
        from elasticdl_tpu.telemetry.events import EVENT_QUIESCE_END

        self._emit(EVENT_QUIESCE_END, generation=generation)
