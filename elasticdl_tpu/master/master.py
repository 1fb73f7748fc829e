"""The master: job orchestrator and control plane.

Reference: ``elasticdl/python/master/master.py`` — loads the model module,
decides the JobType (:233-262), builds the task dispatcher / evaluation
service / gRPC server (:301-324) / instance manager, registers the
SAVE_MODEL deferred callback (:122-129), and polls ``task_d.finished()``
(:179-199).  The TPU differences:

- workers are SPMD processes over a device mesh, not eager-TF pods; the
  master starts them through a pluggable instance manager (local
  subprocesses here; a k8s backend where pods exist);
- there is no PS fleet to start;
- worker liveness is heartbeat-based (servicer) with task recovery on
  timeout, complementing (or replacing) the k8s watch stream.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import threading
import time

from elasticdl_tpu.data.factory import create_data_reader
from elasticdl_tpu.master.evaluation_service import EvaluationService
from elasticdl_tpu.master.servicer import MasterServicer
from elasticdl_tpu.master.task_dispatcher import TaskDispatcher
from elasticdl_tpu.master.tensorboard_service import TensorboardService
from elasticdl_tpu.utils.args import derive_job_type
from elasticdl_tpu.utils.constants import JobType, TaskType
from elasticdl_tpu.utils.log_utils import default_logger as logger
from elasticdl_tpu.utils.model_utils import get_model_spec


class SimulatedMasterCrash(BaseException):
    """Raised by the chaos harness's in-process master kill: unwinds the
    run loop PAST every cleanup path (``stop()`` is never reached), the
    in-process analogue of SIGKILL.  BaseException so blanket
    ``except Exception`` recovery code cannot accidentally survive it."""


class Master:
    def __init__(self, args, instance_manager_factory=None):
        self._args = args
        self.job_type = derive_job_type(args)
        self._stop_requested = False
        self._job_failed = False
        # resolved ONCE (shared fallback constant lives next to the RPC
        # retry budget it is tuned against); the run loop's failure
        # detector and the rehome-grace computation both read this
        from elasticdl_tpu.rpc.retry import DEFAULT_HEARTBEAT_TIMEOUT_SECS

        self._heartbeat_timeout_secs = (
            getattr(
                args, "heartbeat_timeout_secs", DEFAULT_HEARTBEAT_TIMEOUT_SECS
            )
            or DEFAULT_HEARTBEAT_TIMEOUT_SECS
        )
        self.reform_events: list[dict] = []
        # callbacks(cluster_version, dead_workers, reason) invoked on
        # every re-formation — chaos invariant checking, metrics
        self.reform_callbacks: list = []
        # elective re-formation (capacity change, chaos): the run loop
        # owns re-formation, so external threads request, never perform.
        # Lock-guarded: an unsynchronized read-then-clear could drop a
        # request that lands between the load and the store.
        # writes-guarded: the run loop's unlocked peek is re-checked by
        # the locked swap that actually consumes the request
        self._reform_requested: str | None = None  # guarded-by: _reform_request_lock (writes)
        self._reform_request_lock = threading.Lock()

        self._spec = get_model_spec(
            getattr(args, "model_zoo", "") or "",
            args.model_def,
            model_params=getattr(args, "model_params_dict", {}) or {},
        )

        # ---- task dispatcher over data-reader shards (master.py:35-66)
        reader_params = getattr(args, "data_reader_params_dict", {}) or {}
        create = self._spec.custom_data_reader or create_data_reader

        def shards_for(origin):
            if not origin:
                return {}
            return create(data_origin=origin, **reader_params).create_shards()

        # ---- streaming (watermark-lease) mode: --streaming flips the
        # dispatcher from epoch-sliced shards to windows minted lazily
        # up to the source watermark.  Training shards are skipped
        # entirely (the stream has no create_shards view); validation /
        # prediction origins keep the classic path alongside
        training_data = getattr(args, "training_data", "")
        self.stream_source = None
        if bool(getattr(args, "streaming", False)):
            from elasticdl_tpu.streaming.source import build_stream_source

            self.stream_source = build_stream_source(training_data)

        self.task_d = TaskDispatcher(
            {} if self.stream_source is not None else shards_for(training_data),
            shards_for(getattr(args, "validation_data", "")),
            shards_for(getattr(args, "prediction_data", "")),
            records_per_task=args.records_per_task,
            num_epochs=args.num_epochs,
            task_timeout_secs=getattr(args, "task_timeout_secs", 0.0),
            shuffle_seed=getattr(args, "shuffle_seed", None),
            stream_source=self.stream_source,
            stream_origin=training_data if self.stream_source is not None else "",
        )

        # ---- tensorboard + evaluation services
        self.tb_service = None
        tb_dir = getattr(args, "tensorboard_log_dir", "") or ""
        if tb_dir:
            self.tb_service = TensorboardService(tb_dir)
        self.evaluation_service = None
        if (
            self.job_type
            in (JobType.TRAINING_WITH_EVALUATION, JobType.EVALUATION_ONLY)
            and self._spec.eval_metrics_fn is not None
        ):
            eval_only = self.job_type == JobType.EVALUATION_ONLY
            self.evaluation_service = EvaluationService(
                self.tb_service,
                self.task_d,
                self._spec.eval_metrics_fn,
                start_delay_secs=getattr(
                    args, "evaluation_start_delay_secs", 0
                ),
                # the time-based trigger is meaningful only while training
                # runs; an eval-only job evaluates exactly once
                throttle_secs=0
                if eval_only
                else getattr(args, "evaluation_throttle_secs", 0),
                evaluation_steps=getattr(args, "evaluation_steps", 0),
                eval_only=eval_only,
            )
            # (eval-only jobs: set_evaluation_service inside the service's
            # constructor already initialized the job from the dispatcher)
            if (
                self.job_type == JobType.TRAINING_WITH_EVALUATION
                and not getattr(args, "evaluation_steps", 0)
                and not getattr(args, "evaluation_throttle_secs", 0)
            ):
                # neither trigger configured: guarantee one final evaluation
                # when training drains (before the SAVE_MODEL callback below)
                self.task_d.add_deferred_callback(
                    lambda: self.evaluation_service.add_evaluation_task()
                )

        # ---- SAVE_MODEL deferred callback (master.py:122-129)
        output = getattr(args, "output", "") or ""
        if output and self.job_type in (
            JobType.TRAINING_ONLY,
            JobType.TRAINING_WITH_EVALUATION,
        ):
            self.task_d.add_deferred_callback_create_save_model_task(output)

        # ---- servicer + transport
        self.servicer = MasterServicer(
            args.minibatch_size,
            self.task_d,
            evaluation_service=self.evaluation_service,
        )
        self._server = None
        self._port = None

        # ---- worker lifecycle
        self.instance_manager = (
            instance_manager_factory(self) if instance_manager_factory else None
        )

        # ---- slice-granular elasticity + autoscaler (off by default:
        # with no --num_slices/--autoscale_* flag every path below is
        # dormant and behavior is byte-identical to a slice-blind build)
        self._min_slices = getattr(args, "min_slices", None) or 1
        # parked = gracefully degraded below --min_slices: tasks are
        # re-queued and fenced, no world runs, the job waits quiesced
        # for a capacity grant (or autoscale grow) instead of crashing
        self._parked = False
        # the replica stage harvested when parking, held so the
        # eventual unpark world can still hot-restore from peer RAM
        self._parked_stage: dict | None = None
        from elasticdl_tpu.master.autoscaler import build_autoscaler

        self.autoscaler = build_autoscaler(
            args, getattr(self.instance_manager, "fleet_slices", 1)
        )
        if self.autoscaler is not None:
            # p95 step time rides the version-report channel the chaos
            # checker and telemetry already observe — no new RPC
            self.servicer.add_version_observer(self.autoscaler.note_version)

        # ---- telemetry (registry + event log + /metrics endpoint)
        from elasticdl_tpu.telemetry.master_hooks import MasterTelemetry

        self.telemetry = MasterTelemetry(
            getattr(args, "telemetry_dir", "") or "",
            trace_sample_rate=getattr(args, "trace_sample_rate", None),
        )
        self.telemetry.attach(
            self.task_d, self.servicer, tb_service=self.tb_service
        )
        self._telemetry_server = None

        # ---- SLO watchdog plane (off by default: with --slo_config
        # unset nothing below is constructed — no engine, no observer,
        # no /healthz block — and behavior is byte-identical)
        self.slo_engine = None
        if getattr(args, "slo_config", None):
            from elasticdl_tpu.telemetry import slo as slo_mod
            from elasticdl_tpu.telemetry.incident import IncidentManager

            incidents = IncidentManager(
                telemetry_dir=getattr(args, "telemetry_dir", "") or "",
                emit=self.telemetry.events.emit,
                context_fn=self._slo_context,
            )
            self.slo_engine = slo_mod.install_if_enabled(
                getattr(args, "slo_config", None),
                emit=self.telemetry.events.emit,
                tracer=self.telemetry.tracer,
                arm_profiler=self._slo_arm_profiler,
                incidents=incidents,
            )
            if self.autoscaler is not None:
                # one percentile definition site AND one instance: the
                # watchdog's step-time objective reads the tracker the
                # autoscaler already feeds from version reports
                self.slo_engine.tracker = self.autoscaler.tracker
            else:
                self.servicer.add_version_observer(
                    self.slo_engine.tracker.note_version
                )
            self.telemetry.set_slo_engine(self.slo_engine)

        # ---- peer state replication (off by default: behavior and wire
        # payloads are then byte-identical to a replication-less build)
        self.replica_directory = None
        if bool(getattr(args, "replication", False)):
            from elasticdl_tpu.replication.directory import ReplicaDirectory
            from elasticdl_tpu.rpc.deadline import DeadlinePolicy

            deadline_secs = getattr(args, "rpc_deadline_secs", None)
            self.replica_directory = ReplicaDirectory(
                # the harvest adopts the job's deadline policy (state-
                # transfer tier); None keeps the historical fixed timeout
                deadlines=DeadlinePolicy.from_secs(deadline_secs)
                if deadline_secs is not None
                else None
            )
            self.servicer.set_replica_directory(self.replica_directory)

        # ---- live train->serve push (streaming subsystem; off by
        # default: with no --live_push_addr nothing is constructed).
        # Rides the replica ring — without --replication there is no
        # state to harvest, so the pusher is skipped with a warning
        self.live_pusher = None
        live_push_addr = getattr(args, "live_push_addr", None) or ""
        if live_push_addr:
            if self.replica_directory is None:
                logger.warning(
                    "--live_push_addr set without --replication; live "
                    "push disabled (the push harvests the replica ring)"
                )
            else:
                from elasticdl_tpu.rpc.deadline import DeadlinePolicy
                from elasticdl_tpu.streaming.live_push import LivePusher

                deadline_secs = getattr(args, "rpc_deadline_secs", None)
                self.live_pusher = LivePusher(
                    live_push_addr,
                    self.replica_directory,
                    telemetry=self.telemetry,
                    deadlines=DeadlinePolicy.from_secs(deadline_secs)
                    if deadline_secs is not None
                    else None,
                )

        # ---- master high availability (off by default: with no
        # --master_journal_dir every path below is dormant and behavior
        # is byte-identical to a journal-less build)
        self.journal = None
        self._journal_dir = getattr(args, "master_journal_dir", None) or ""
        # the pending set is mutated by gRPC handler threads (a re-home
        # discards) while the run loop iterates it — every access goes
        # through the lock or CPython raises mid-``sorted()``
        self._rehome_lock = threading.Lock()
        self._rehome_pending: set[int] = set()  # guarded-by: _rehome_lock
        self._rehome_deadline: float | None = None
        self._restored_world: dict | None = None
        self._restored = False
        self._restart_at: float | None = None
        # chaos kill hook (harness MASTER_KILL): the armed site name, or
        # None.  Checked only at two explicit points, so a non-chaos
        # master pays one attribute read per run-loop tick.
        self._crash_armed: str | None = None
        self.crashed_at: float | None = None
        if self._journal_dir:
            from elasticdl_tpu.master import journal as journal_mod

            restored = journal_mod.load_state(self._journal_dir)
            restored_callbacks = 0
            if restored is not None and not restored.get("clean_shutdown"):
                restored_callbacks = self._restore_from_journal(restored)
            self.journal = journal_mod.MasterJournal(self._journal_dir)
            self.journal.set_callbacks_invoked(restored_callbacks)
            self.servicer.set_journal(self.journal)
            self.servicer.set_rehome_sink(self._on_worker_rehomed)
            self.servicer.set_stage_released_sink(
                self.journal.record_stage_released
            )
            import uuid

            self.servicer.set_boot_id(uuid.uuid4().hex)
            # attach UNARMED (the backlog replay below is state the
            # initial snapshot already carries), then snapshot + arm
            self.task_d.add_observer(self.journal)
            self.servicer.add_version_observer(
                self.journal.on_version_report
            )
            self.journal.set_snapshot_provider(self._journal_snapshot)
            self.journal.start()

    # ---- master high availability ------------------------------------------

    # single-threaded: journal replay runs from __init__, before the RPC
    # server and the run loop exist — no other thread can touch the
    # re-home set yet
    def _restore_from_journal(self, state: dict) -> int:
        """Install the journal-replayed control plane: dispatcher
        todo/doing sets, generation fence, model-version floor, the
        memoized lockstep step-stream, and consumed deferred callbacks.
        Returns the consumed-callback count (the journal writer resumes
        from it)."""
        from elasticdl_tpu.telemetry.tracing import SPAN_JOURNAL_REPLAY

        control = state.get("servicer", {})
        generation = int(control.get("cluster_version", 0))
        self._restart_at = time.monotonic()
        self._restored = True
        self.telemetry.master_restart(generation)
        with self.telemetry.tracer.span(
            SPAN_JOURNAL_REPLAY, generation=generation
        ):
            self.task_d.restore_state(state["dispatcher"])
            self.servicer.restore_control_state(
                cluster_version=generation,
                model_version=int(control.get("model_version", 0)),
                stream=control.get("stream"),
            )
            consumed = int(state.get("callbacks_invoked", 0))
            self.task_d.drop_deferred_callbacks(consumed)
        world = state.get("world")
        if world:
            self._restored_world = world
            self._rehome_pending = set(world["worker_ids"])
            if world.get("parked"):
                # the previous life parked below --min_slices: this one
                # must come back parked too (prepare() skips the world
                # launch; the parked replica stage died with the old
                # master's RAM, so the eventual unpark restores from
                # disk)
                self._parked = True
        # replica-stage metadata: the staged payload was the previous
        # life's RAM and died with it — a complete stage for a still-
        # restoring generation means those workers now take the disk
        # fallback, which the outage report should attribute
        stage = state.get("stage")
        stage_lost = bool(
            stage and stage.get("complete") and stage["generation"] >= generation
        )
        if stage_lost:
            logger.warning(
                "Journal records a staged replica set (generation %d, "
                "version %s) lost with the previous master; restoring "
                "workers fall back to disk",
                stage["generation"],
                stage.get("version"),
            )
        snap = self.task_d.snapshot()
        self.telemetry.journal_replay(
            generation=generation,
            duration_secs=time.monotonic() - self._restart_at,
            pending=snap["pending"] + snap["pending_eval"],
            active=len(snap["active"]),
            epoch=snap["epoch"],
            stage_lost=stage_lost,
        )
        logger.warning(
            "Master restored from journal: generation %d, epoch %d, "
            "%d pending / %d active task(s), expecting %s to re-home",
            generation,
            snap["epoch"],
            snap["pending"] + snap["pending_eval"],
            len(snap["active"]),
            sorted(self._rehome_pending) or "no workers",
        )
        return consumed

    def _journal_snapshot(self, append):
        """Assemble the full control-plane state and ``append`` it as a
        journal ``snapshot`` record (run loop only, never from an
        observer).  The dispatcher capture and the append happen under
        the dispatcher transition lock (``atomic_state_snapshot``), so
        no lease/report/callback delta can land between the capture and
        the record's file position.  The servicer fields captured just
        before are safe: replay applies generation/version deltas with
        monotone (max) guards, and the stream field is superseded by the
        ``stream_snapshot`` record journaled right after — under the
        stream lock, so ITS position is exact too."""
        servicer_state = {
            "cluster_version": self.servicer.cluster_version,
            "model_version": self.servicer.get_model_version(),
            "stream": self.servicer.stream_snapshot(),
        }
        world = self._restored_world
        self.task_d.atomic_state_snapshot(
            lambda dispatcher_state: append(
                {
                    "dispatcher": dispatcher_state,
                    "servicer": servicer_state,
                    "callbacks_invoked": self.journal.callbacks_invoked
                    if self.journal is not None
                    else 0,
                    "world": world,
                }
            )
        )
        self.servicer.journal_stream_snapshot()

    def _record_world(self):
        """Journal the live worker-world composition — what a restarted
        master waits on for re-homing."""
        im = self.instance_manager
        if im is None:
            return
        ids = im.worker_ids()
        slices = im.worker_slices() if hasattr(im, "worker_slices") else {}
        world = {
            "cluster_version": self.servicer.cluster_version,
            "worker_ids": sorted(ids),
            "world_size": getattr(im, "world_size", len(ids)),
            "num_slices": getattr(im, "world_num_slices", 1),
            "slices": {str(k): int(v) for k, v in slices.items()},
            # graceful degradation: a restarted master must come back
            # PARKED, not relaunch a fleet the capacity cannot run
            "parked": self._parked,
        }
        self._restored_world = world
        if self.journal is not None:
            self.journal.record_world(
                world["cluster_version"], world["worker_ids"],
                world["world_size"],
                num_slices=world["num_slices"],
                slices=world["slices"],
                parked=world["parked"],
            )

    def _on_worker_rehomed(
        self,
        worker_id: int,
        pid: int,
        kept: list,
        requeued: list,
        started_at: float,
    ):
        """Servicer rehome sink: adopt the orphaned process (the dead
        master spawned it; this one holds no handle) and settle the
        re-home wait.  ``started_at`` is the servicer's handshake entry
        time, so the worker_rehome span covers the fence check and
        lease reconciliation, not just this adoption tail."""
        im = self.instance_manager
        adopt = getattr(im, "adopt_worker", None) if im is not None else None
        if adopt is not None and pid:
            adopt(worker_id, pid)
        with self._rehome_lock:
            self._rehome_pending.discard(worker_id)
        self.telemetry.worker_rehome(
            worker_id,
            self.servicer.cluster_version,
            kept=len(kept),
            requeued=len(requeued),
            started_at=started_at,
        )

    def _check_rehome_deadline(self):
        """Run-loop tick: a restored master waits a bounded grace for
        its journaled world to re-home; workers that never do are dead —
        recover their leases and re-form."""
        if self._rehome_deadline is None:
            return
        with self._rehome_lock:
            if not self._rehome_pending:
                self._rehome_deadline = None
                logger.info("All restored workers re-homed")
                return
            if time.monotonic() < self._rehome_deadline:
                return
            pending = sorted(self._rehome_pending)
            self._rehome_pending = set()
        self._rehome_deadline = None
        # a pending worker that heartbeated THIS life is alive even if
        # it never presented the handshake (it may never have seen the
        # previous boot id — spawned just before the outage): its
        # journaled leases stay valid and its reports ride normally, so
        # settle it rather than requeue a live worker's tasks
        alive = set(self.servicer.live_workers())
        settled = [w for w in pending if w in alive]
        missing = [w for w in pending if w not in alive]
        if settled:
            logger.info(
                "Workers %s heartbeated without re-homing; settled",
                settled,
            )
        if not missing:
            return
        logger.warning(
            "Workers %s never re-homed after the master restart; "
            "recovering their tasks",
            missing,
        )
        self.telemetry.worker_dead(missing, self.servicer.cluster_version)
        self._handle_dead_workers(missing)

    # ---- lifecycle ---------------------------------------------------------

    @property
    def port(self):
        return self._port

    @property
    def metrics_port(self) -> int | None:
        """Bound port of the /metrics + /healthz endpoint (None when
        disabled via a negative ``--metrics_port``)."""
        return (
            self._telemetry_server.port
            if self._telemetry_server is not None
            else None
        )

    def prepare(self, port: int | None = None):
        """Start services + control-plane server
        (reference master.py:150-177)."""
        from elasticdl_tpu.rpc.service import create_server

        if self.evaluation_service is not None:
            self.evaluation_service.start()
        port = port if port is not None else getattr(self._args, "port", 0)
        self._server = create_server(self.servicer, port)
        self._server.start()
        self._port = self._server._edl_bound_port
        if self.journal is not None:
            # publish the (possibly new) control-plane address: workers
            # that outlived a previous master re-resolve from this file
            from elasticdl_tpu.master.journal import write_master_addr

            write_master_addr(self._journal_dir, f"localhost:{self._port}")
        metrics_port = getattr(self._args, "metrics_port", 0)
        if metrics_port is not None and metrics_port >= 0:
            from elasticdl_tpu.telemetry.httpd import TelemetryHTTPServer

            self._telemetry_server = TelemetryHTTPServer(
                self.telemetry.registry,
                health_fn=self.telemetry.build_health_fn(
                    self.job_type.value, lambda: self.instance_manager
                ),
                port=metrics_port,
                host=getattr(self._args, "metrics_host", "127.0.0.1")
                or "127.0.0.1",
            )
            self._telemetry_server.start()
        self.telemetry.job_start(
            self.job_type.value, getattr(self._args, "num_workers", 0) or 0
        )
        if self.tb_service is not None:
            self.tb_service.start()
        if self.instance_manager is not None:
            with self._rehome_lock:
                rehome_wait = sorted(self._rehome_pending)
            if self._restored and rehome_wait:
                # the journaled world may still be alive (the workers
                # outlived the dead master): do NOT spawn a second world
                # on top of it — wait for re-homing instead; the grace
                # deadline recovers whatever never comes back
                im = self.instance_manager
                if self._restored_world is not None and hasattr(
                    im, "set_world_size"
                ):
                    restored = self._restored_world
                    if restored.get("num_slices", 1) > 1 and hasattr(
                        im, "set_world_slices"
                    ):
                        im.set_world_slices(restored["num_slices"])
                    else:
                        im.set_world_size(restored["world_size"])
                    if restored.get("slices") and hasattr(
                        im, "restore_worker_slices"
                    ):
                        # the re-homed world keeps its slice map so a
                        # post-restart slice loss still shrinks correctly
                        im.restore_worker_slices(restored["slices"])
                grace = getattr(self._args, "rehome_grace_secs", None)
                if grace is None:
                    grace = max(10.0, 3.0 * self._heartbeat_timeout_secs)
                self._rehome_deadline = time.monotonic() + grace
                logger.warning(
                    "Waiting up to %.1fs for workers %s to re-home",
                    grace,
                    rehome_wait,
                )
            elif self._restored and self._parked:
                # restored PARKED: capacity was below --min_slices when
                # the previous master died — relaunching the fleet would
                # crash-loop on hardware that is not there.  Stay
                # quiesced; a capacity grant / autoscale grow unparks.
                im = self.instance_manager
                restored = self._restored_world or {}
                if hasattr(im, "set_world_slices"):
                    im.set_world_slices(restored.get("num_slices", 1))
                self.servicer.begin_quiesce()
                logger.warning(
                    "Master restored PARKED (capacity below "
                    "--min_slices %d); waiting quiesced for a capacity "
                    "grant",
                    self._min_slices,
                )
            else:
                self.instance_manager.start_workers()
                self._record_world()
        if self._restart_at is not None:
            from elasticdl_tpu.telemetry.tracing import SPAN_MASTER_RESTART

            self.telemetry.tracer.record_span(
                SPAN_MASTER_RESTART,
                self._restart_at,
                time.monotonic(),
                generation=self.servicer.cluster_version,
            )
            self.telemetry.tracer.flush()

    def run(self, poll_secs: float = 1.0) -> int:
        """Poll until all tasks (incl. deferred SAVE_MODEL) are done
        (reference master.py:179-199, 30s poll shortened — local workers
        finish in seconds)."""
        try:
            while True:
                self._crash_if_armed("tick")
                if self.task_d.finished() and not (
                    self.task_d.invoke_deferred_callback()
                ):
                    break
                if self._stop_requested:
                    break
                # a restored master first waits for its journaled world
                # to re-home (bounded by the grace deadline)
                self._check_rehome_deadline()
                if self.journal is not None:
                    self.journal.maybe_snapshot()
                if self.instance_manager is not None:
                    # local process-exit events (the subprocess analogue
                    # of the k8s pod watch): an abnormal exit is detected
                    # in one poll tick instead of a heartbeat timeout
                    poll_failed = getattr(
                        self.instance_manager, "poll_failed_workers", None
                    )
                    if poll_failed is not None:
                        for worker_id in poll_failed():
                            self.servicer.mark_worker_dead(worker_id)
                dead = self.servicer.dead_workers(
                    self._heartbeat_timeout_secs
                )
                if dead and self.instance_manager is not None:
                    # a killed stale worker's last in-flight RPC can
                    # re-register its id after forget_worker; ids the
                    # instance manager no longer tracks are ghosts, not
                    # failures — drop them instead of re-forming a
                    # healthy world
                    live = set(self.instance_manager.worker_ids())
                    for ghost in [w for w in dead if w not in live]:
                        self.servicer.forget_worker(ghost)
                    dead = [w for w in dead if w in live]
                if dead:
                    self.telemetry.worker_dead(
                        dead, self.servicer.cluster_version
                    )
                    self._handle_dead_workers(dead)
                elif self._reform_requested is not None:
                    # elective re-formation (world size changed): same
                    # fence/recover/relaunch sequence, no dead workers
                    with self._reform_request_lock:
                        reason, self._reform_requested = (
                            self._reform_requested,
                            None,
                        )
                    im = self.instance_manager
                    if im is not None and getattr(im, "lockstep", False):
                        if len(im.worker_ids()) == getattr(
                            im, "world_size", len(im.worker_ids())
                        ):
                            # a failure-driven re-formation between the
                            # requester's set_world_size and its
                            # request already realized this size:
                            # tearing down the fresh, correctly-sized
                            # world again would be pure downtime
                            logger.info(
                                "Skipping elective re-formation (%s): "
                                "world already at target size",
                                reason,
                            )
                        else:
                            self._reform_lockstep([], reason=reason)
                if self.autoscaler is not None and not dead:
                    # telemetry-driven elasticity: the autoscaler only
                    # REQUESTS a resize; the run loop (above, next tick)
                    # performs it through the same elective-reform path
                    self._autoscale_tick()
                if self.slo_engine is not None and not dead:
                    # SLO watchdog: judge the tick's signals through the
                    # burn-rate detectors (violations emit, auto-arm the
                    # profiler, and open incidents from inside evaluate)
                    self._slo_tick()
                if self.task_d.streaming:
                    # watermark-lease mode: publish the watermark pair +
                    # lag (deduped inside — an idle tick emits nothing)
                    status = self.task_d.stream_status()
                    if status is not None:
                        self.telemetry.stream_tick(status)
                if self.live_pusher is not None and not dead:
                    self._live_push_tick()
                if (
                    self.reform_events
                    and "latency_secs" not in self.reform_events[-1]
                ):
                    # re-form latency = detection -> first step-task pull
                    # of the new world (BASELINE.md config 5 metric)
                    pull_at = self.servicer.first_stream_pull_at()
                    if pull_at is not None:
                        event = self.reform_events[-1]
                        event["latency_secs"] = (
                            pull_at - event["detected_at"]
                        )
                        logger.info(
                            "World re-formed in %.2fs (cluster version %d)",
                            event["latency_secs"],
                            event["cluster_version"],
                        )
                        self.telemetry.reform_latency(
                            event["cluster_version"], event["latency_secs"]
                        )
                        if self.slo_engine is not None:
                            # the downtime-budget objective sums these
                            # over its slow window
                            self.slo_engine.note_reform_downtime(
                                event["latency_secs"]
                            )
                time.sleep(poll_secs)
        except KeyboardInterrupt:
            logger.warning("Interrupted; shutting down")
        if self.task_d.streaming:
            # the run loop can break on finished() before the tick that
            # would record the terminal pair — emit it explicitly so the
            # event log's last stream_watermark shows the drained state
            # (the bounded-lag checker's final-drain evidence)
            status = self.task_d.stream_status()
            if status is not None:
                self.telemetry.stream_tick(status)
        self.stop()
        return 1 if self._job_failed else 0

    def _handle_dead_workers(self, dead: list[int]):
        """Failure recovery (reference k8s_instance_manager.py:198-281).

        Task-stream workers are independent: re-queue the dead worker's
        tasks and relaunch it with a new id.  A lockstep world is one SPMD
        program: losing any process stalls every collective, so the whole
        world is re-formed — kill survivors, re-queue every leased task,
        reset the step stream, and relaunch a fresh world (new cluster
        version, new coordinator) that resumes from the newest checkpoint.
        """
        im = self.instance_manager
        if im is not None and getattr(im, "lockstep", False):
            self._reform_lockstep(dead, reason="worker_failure")
            return
        for worker_id in dead:
            logger.warning("Worker %d timed out; recovering", worker_id)
            self.task_d.recover_tasks(worker_id)
            self.servicer.forget_worker(worker_id)
            if im is not None:
                im.restart_worker(worker_id)

    def _reform_lockstep(self, dead: list[int], reason: str):
        """Fence, recover, relaunch — the whole-world re-formation.
        ``dead`` may be empty (elective re-formation: capacity change).

        Slice-granular: when the fleet spans TPU slices, a WHOLE-slice
        death shrinks the next world to the surviving slice set (the
        dp axis contracts across DCN), a capacity grant grows it back,
        and a shrink below ``--min_slices`` parks the job quiesced
        instead of crashing."""
        im = self.instance_manager
        t0 = time.monotonic()
        if self._parked and not dead:
            target = getattr(im, "world_num_slices", 1)
            if target < self._min_slices:
                # parked below the floor: only a request that restores
                # at least --min_slices may relaunch a world
                logger.warning(
                    "Job parked below --min_slices %d; ignoring "
                    "re-formation request (%s) targeting %d slice(s)",
                    self._min_slices,
                    reason,
                    target,
                )
                return
        logger.warning(
            "Re-forming the distributed world (%s; dead workers: %s)",
            reason,
            dead or "none",
        )
        # coalesce: ANY re-formation satisfies a pending elective request
        # (the relaunch below already uses the latest world size) — a
        # leftover request would tear down the fresh world a tick later
        # and burn a unit of the reform budget for nothing
        with self._reform_request_lock:
            self._reform_requested = None
        # a re-formation supersedes any outstanding re-home wait: the
        # world being fenced and relaunched IS the recovery
        self._rehome_deadline = None
        with self._rehome_lock:
            self._rehome_pending = set()
        # fence FIRST: from here every stale worker's get_step_task is
        # rejected, so none can re-lease a task we are about to recover
        new_version = self.servicer.bump_cluster_version()
        all_ids = set(dead) | set(im.worker_ids())
        old_world_size = len(all_ids)
        worker_slices = (
            im.worker_slices() if hasattr(im, "worker_slices") else {}
        )
        # the LIVE world's slice count comes from its worker->slice map
        # ({} = single slice): ``world_num_slices`` is the NEXT world's
        # target, which a capacity grant / autoscale decision already
        # moved before requesting this re-formation
        old_slices = len(set(worker_slices.values())) or 1
        self.telemetry.reform_start(
            new_version, dead, reason, old_world_size
        )
        reform_trace = self.telemetry.reform_trace_context()
        from elasticdl_tpu.telemetry.tracing import (
            SPAN_REFORM_FENCE,
            SPAN_REFORM_RELAUNCH,
        )

        # slice-granular re-plan: a fully-dead slice is LOST CAPACITY —
        # the next world shrinks to the surviving slice set (and parks
        # when that drops below --min_slices)
        park = self._plan_slice_topology(
            new_version, dead, old_slices, worker_slices, reform_trace, t0
        )
        # harvest the survivors' replica shards BEFORE the fence loop
        # forgets them (the directory loses their addresses there) and
        # before the relaunch kills them (their RAM dies there).  Stale
        # task leases are already fenced by the version bump above.
        stage = self._stage_replica_restore(
            new_version, dead, old_world_size, reform_trace
        )
        with self.telemetry.tracer.span(
            SPAN_REFORM_FENCE, trace_ctx=reform_trace, generation=new_version
        ):
            for worker_id in all_ids:
                self.task_d.recover_tasks(worker_id)
                self.servicer.forget_worker(worker_id)
            self.servicer.reset_step_stream()
        # MASTER_KILL trigger="reform": die in the nastiest window —
        # generation bumped and journaled, old world fenced and its
        # tasks recovered, no new world launched yet
        self._crash_if_armed("reform")
        if park:
            self._park(new_version, old_world_size, stage, reason)
            for callback in self.reform_callbacks:
                try:
                    callback(new_version, sorted(dead), reason)
                except Exception:  # noqa: BLE001 — observers never
                    # break recovery
                    logger.exception("Reform callback failed")
            return
        new_world_size = getattr(im, "world_size", old_world_size)
        new_slices = getattr(im, "world_num_slices", old_slices)
        if new_world_size != old_world_size or new_slices != old_slices:
            # re-plan the hybrid mesh for the new slice set (the workers
            # re-derive the same layout from their slice coordinates at
            # join — this is the master's validation + telemetry record)
            self._announce_mesh_resize(
                new_version,
                old_world_size,
                new_world_size,
                old_slices,
                new_slices,
                reform_trace,
            )
        # the relaunched world's workers link their world_join spans
        # into this re-formation's trace (argv spawns get it by env,
        # standbys in the stdin/RPC assignment payload)
        im.pending_world_trace = reform_trace
        try:
            with self.telemetry.tracer.span(
                SPAN_REFORM_RELAUNCH,
                trace_ctx=reform_trace,
                generation=new_version,
            ):
                im.reform_world(
                    new_version,
                    # only failure recovery spends the crash-loop budget;
                    # an elective resize is planned work, not a crash
                    count_against_budget=reason == "worker_failure",
                )
        except RuntimeError as ex:
            logger.error("Giving up on the job: %s", ex)
            self.telemetry.reform_failed(new_version)
            self._job_failed = True
            self.request_stop()
            return
        if self._parked:
            # a world is running again: the graceful-degradation park is
            # over (capacity grant or autoscale grow realized)
            self._parked = False
            self.servicer.clear_quiesce()
            logger.warning(
                "Job UNPARKED: world relaunched with %d slice(s)",
                new_slices,
            )
        if self.autoscaler is not None:
            self.autoscaler.note_reform()
        if self.slo_engine is not None:
            # same baseline-invalidation contract as the autoscaler
            # (idempotent when they share the tracker)
            self.slo_engine.note_reform()
        self.telemetry.reform_complete(
            new_version,
            old_world_size,
            getattr(im, "world_size", old_world_size),
        )
        self._record_world()
        self.reform_events.append(
            {
                "detected_at": t0,
                "cluster_version": new_version,
                "dead_workers": sorted(dead),
                "reason": reason,
            }
        )
        for callback in self.reform_callbacks:
            try:
                callback(new_version, sorted(dead), reason)
            except Exception:  # noqa: BLE001 — observers never break recovery
                logger.exception("Reform callback failed")

    def _plan_slice_topology(
        self,
        new_version: int,
        dead: list[int],
        old_slices: int,
        worker_slices: dict[int, int],
        reform_trace: dict,
        detected_at: float,
    ) -> bool:
        """Slice-loss accounting: slices whose EVERY process died are
        lost capacity — shrink the next world to the survivors.  A
        partially-dead slice is a software crash (capacity presumed
        intact): relaunch at full size, as before.  Returns True when
        the shrink would drop below ``--min_slices`` (the caller parks
        instead of relaunching)."""
        if not dead or old_slices <= 1 or not worker_slices:
            return False
        im = self.instance_manager
        dead_set = set(dead)
        lost = sorted(
            {
                s
                for s in set(worker_slices.values())
                if all(
                    w in dead_set
                    for w, ws in worker_slices.items()
                    if ws == s
                )
            }
        )
        if not lost:
            return False
        if len(lost) >= old_slices:
            # the whole world died at once: indistinguishable from a
            # deterministic software crash — relaunch at full size (the
            # reform budget bounds a crash loop) rather than shrinking
            # to nothing on ambiguous evidence
            logger.warning(
                "All %d slices report dead; treating as a whole-world "
                "crash (full-size relaunch), not a capacity loss",
                old_slices,
            )
            return False
        new_slices = old_slices - len(lost)
        park = new_slices < self._min_slices
        self.telemetry.slice_loss(
            generation=new_version,
            lost_slices=lost,
            dead_workers=sorted(dead),
            old_slices=old_slices,
            new_slices=new_slices,
            parked=park,
            started_at=detected_at,
            trace_ctx=reform_trace,
        )
        logger.warning(
            "Slice loss: slice(s) %s fully dead — shrinking the next "
            "world from %d to %d slice(s)%s",
            lost,
            old_slices,
            new_slices,
            " (BELOW --min_slices: parking)" if park else "",
        )
        if hasattr(im, "set_world_slices"):
            im.set_world_slices(max(1, new_slices))
        return park

    def _announce_mesh_resize(
        self,
        new_version: int,
        old_world_size: int,
        new_world_size: int,
        old_slices: int,
        new_slices: int,
        reform_trace: dict,
    ):
        """Validate + record the resized hybrid mesh plan: the dp axis
        contracts/expands across the DCN slice dimension.  Advisory on
        the master (workers re-derive the layout from their slice
        coordinates); the telemetry record is the contract CI gates on
        (``mesh_resize`` span in the multislice smoke)."""
        from elasticdl_tpu.parallel.mesh import plan_dcn_axes
        from elasticdl_tpu.utils.constants import MeshAxis

        t0 = time.monotonic()
        dcn: dict = {}
        if new_slices > 1:
            try:
                # 1 process : N devices — dp scales with processes, so
                # divisibility by the slice count is the invariant that
                # matters and it is process-count-exact
                dcn = plan_dcn_axes(
                    {MeshAxis.DP: new_world_size}, new_slices, None
                )
            except ValueError:
                logger.exception(
                    "Resized mesh plan invalid (dp=%d over %d slices); "
                    "workers will fail loudly at join",
                    new_world_size,
                    new_slices,
                )
        self.telemetry.mesh_resize(
            generation=new_version,
            old_world_size=old_world_size,
            new_world_size=new_world_size,
            old_slices=old_slices,
            new_slices=new_slices,
            dcn=dcn,
            started_at=t0,
            trace_ctx=reform_trace,
        )

    def _park(
        self,
        new_version: int,
        old_world_size: int,
        stage: dict | None,
        reason: str,
    ):
        """Graceful degradation: the surviving capacity is below
        ``--min_slices``.  Tear the world down (tasks are already
        re-queued and the generation fenced), hold the harvested replica
        stage for the eventual unpark world, and wait quiesced — the
        next capacity grant or autoscale grow relaunches."""
        im = self.instance_manager
        self._parked = True
        # the stage was staged for THIS generation, which will never
        # run: hold it master-side; the unpark reform re-stamps it
        self._parked_stage = stage
        self.servicer.set_restore_stage(None)
        self.servicer.begin_quiesce()
        if hasattr(im, "teardown_world"):
            im.teardown_world()
        else:  # no dedicated teardown: a hard stop is the close analogue
            im.stop_workers(grace_secs=0.0)
        if self.autoscaler is not None:
            self.autoscaler.note_reform()
        if self.slo_engine is not None:
            self.slo_engine.note_reform()
        self.telemetry.reform_complete(new_version, old_world_size, 0)
        self._record_world()
        logger.warning(
            "Job PARKED quiesced (generation %d, %s): surviving "
            "capacity is below --min_slices %d; waiting for a capacity "
            "grant",
            new_version,
            reason,
            self._min_slices,
        )

    def _autoscale_tick(self):
        """Run-loop tick: evaluate the autoscaler's SLOs and turn a
        decision into an elective re-formation request."""
        im = self.instance_manager
        if im is None or not getattr(im, "lockstep", False):
            return
        snap = self.task_d.snapshot()
        backlog = snap["pending"] + snap["pending_eval"]
        if self.task_d.streaming:
            # watermark-lease mode: pending counts only the windows
            # already MINTED, which is bounded by what workers lease —
            # the true backlog is the lag behind the source watermark,
            # expressed in task-window units so one threshold flag
            # (--stream_lag_tasks / --autoscale_backlog_tasks) covers
            # both modes
            status = self.task_d.stream_status()
            if status is not None:
                per_task = max(
                    1, int(getattr(self._args, "records_per_task", 1) or 1)
                )
                backlog = int(status["lag"]) // per_task
        current = getattr(im, "world_num_slices", 1)
        decision = self.autoscaler.evaluate(backlog, current)
        if decision is None:
            return
        t0 = time.monotonic()
        if hasattr(im, "set_world_slices"):
            im.set_world_slices(decision["to_slices"])
        self.telemetry.autoscale_decision(
            generation=self.servicer.cluster_version,
            started_at=t0,
            **decision,
        )
        logger.warning(
            "Autoscale %s: %d -> %d slice(s) (%s)",
            decision["action"],
            decision["from_slices"],
            decision["to_slices"],
            decision["reason"],
        )
        self.request_reform(f"autoscale:{decision['action']}")

    def _live_push_tick(self):
        """Run-loop tick: fan the replica ring's freshest complete
        snapshot into serving when the model version advanced (the
        pusher itself gates on version + attempt interval, so an idle
        tick costs two integer compares)."""
        im = self.instance_manager
        if im is None:
            return
        ids = im.worker_ids()
        self.live_pusher.tick(
            model_version=self.servicer.get_model_version(),
            generation=self.servicer.cluster_version,
            num_sources=getattr(im, "world_size", len(ids)),
            live_worker_ids=ids,
            stream_status=self.task_d.stream_status(),
        )

    # ---- SLO watchdog plumbing ----------------------------------------------

    def _slo_context(self) -> dict:
        """Correlatable state snapshotted at incident open/close: the
        servicer's fleet-wide anatomy, memory, and rpc aggregates."""
        return {
            "anatomy": self.servicer.phase_stats_totals(),
            "memory": self.servicer.memory_stats_totals(),
            "rpc": self.servicer.rpc_stats_totals(),
        }

    def _slo_arm_profiler(self, num_steps: int):
        """Violation hook: arm the PR-14 on-demand profiler for a
        capture window (the servicer absorbs re-arms within the command
        TTL, so repeated violations cannot storm the workers)."""
        from elasticdl_tpu.rpc import messages as msg

        response = self.servicer.request_profile(
            msg.RequestProfileRequest(num_steps=num_steps)
        )
        if getattr(response, "accepted", False):
            incidents = self.slo_engine.incidents
            if incidents is not None:
                incidents.note_profile_window(
                    {"window_id": response.window_id}
                )

    def _slo_tick(self):
        """Run-loop tick: derive this tick's signals from state the
        master already holds and judge them through the detectors."""
        from elasticdl_tpu.telemetry import slo as slo_mod
        from elasticdl_tpu.telemetry.memory import host_memory_health

        engine = self.slo_engine
        signals: dict = {}
        step_age = self.servicer.last_step_age_secs()
        if step_age is not None:
            signals[slo_mod.SIGNAL_LAST_STEP_AGE_SECS] = step_age
        signals.update(
            slo_mod.signals_from_phase_totals(
                self.servicer.phase_stats_totals()
            )
        )
        headroom = host_memory_health().get("headroom_share")
        if headroom is not None:
            signals[slo_mod.SIGNAL_MEMORY_HEADROOM_SHARE] = headroom
        signals[slo_mod.SIGNAL_RPC_OUTAGE_RISE] = engine.ingest_rpc_totals(
            self.servicer.rpc_stats_totals()
        )
        engine.evaluate(signals)

    def _stage_replica_restore(
        self, new_version: int, dead: list[int], old_world_size: int,
        reform_trace: dict,
    ) -> dict | None:
        """Harvest the freshest complete replica set from surviving
        workers' RAM and stage it for the relaunched generation; stages
        None (disk fallback) when replication is off or coverage is
        incomplete.  Returns the stage so a parking caller can hold it
        for the unpark world."""
        if self.replica_directory is None:
            return None
        if self._parked_stage is not None:
            # unparking: the world that died parked left its harvest in
            # master RAM — re-stamp it for the relaunching generation
            # instead of harvesting from (nonexistent) survivors
            stage = dict(self._parked_stage)
            self._parked_stage = None
            stage["generation"] = new_version
            stage.pop("served", None)
            stage["world_size"] = getattr(
                self.instance_manager, "world_size", old_world_size
            )
            self.servicer.set_restore_stage(stage)
            if self.journal is not None:
                self.journal.record_stage(
                    new_version, stage["version"], complete=True
                )
            self.telemetry.replica_harvest(
                generation=new_version,
                complete=True,
                version=stage["version"],
                sources=stage.get("sources", old_world_size),
            )
            logger.info(
                "Unpark: serving the parked replica stage (version %s) "
                "to generation %d",
                stage["version"],
                new_version,
            )
            return stage
        from elasticdl_tpu.telemetry.tracing import SPAN_REPLICA_HARVEST

        live = [
            w
            for w in self.instance_manager.worker_ids()
            if w not in set(dead)
        ]
        stage = None
        with self.telemetry.tracer.span(
            SPAN_REPLICA_HARVEST,
            trace_ctx=reform_trace,
            generation=new_version,
        ) as span:
            try:
                stage = self.replica_directory.harvest(
                    live_worker_ids=live,
                    num_sources=old_world_size,
                    generation=new_version - 1,
                    staged_for=new_version,
                )
            except Exception:  # noqa: BLE001 — harvest must never take
                # down recovery; disk restore is always available
                logger.exception("Replica harvest failed; disk fallback")
            span.set(
                complete=stage is not None,
                version=stage["version"] if stage else None,
            )
        if stage is not None:
            # how many processes will fetch this stage — once all have,
            # the servicer releases the payload from master RAM
            stage["world_size"] = getattr(
                self.instance_manager, "world_size", old_world_size
            )
        self.servicer.set_restore_stage(stage)
        if self.journal is not None:
            # metadata only: the staged payload is master RAM and dies
            # with the process — a restarted master serves disk fallback
            self.journal.record_stage(
                new_version,
                stage["version"] if stage else None,
                complete=stage is not None,
            )
        self.telemetry.replica_harvest(
            generation=new_version,
            complete=stage is not None,
            version=stage["version"] if stage else None,
            sources=old_world_size,
        )
        return stage

    def request_crash(self, site: str = "tick"):
        """Chaos hook (MASTER_KILL): arm an in-process master kill at a
        named site — ``"tick"`` dies at the next run-loop tick,
        ``"reform"`` dies inside the next re-formation after the fence
        (generation journaled, world fenced, no new world launched).
        The kill has SIGKILL semantics: the gRPC server stops instantly,
        the journal's unflushed tail is dropped, and no cleanup runs."""
        self._crash_armed = site

    def _crash_if_armed(self, site: str):
        if self._crash_armed != site:
            return
        self._crash_armed = None
        logger.warning(
            "CHAOS: simulating master kill at %r (SIGKILL semantics)", site
        )
        self.crashed_at = time.monotonic()
        if self._server is not None:
            self._server.stop(grace=0)
            self._server = None
        if self.journal is not None:
            self.journal.abort()
        if self._telemetry_server is not None:
            self._telemetry_server.stop()
            self._telemetry_server = None
        raise SimulatedMasterCrash(site)

    def request_reform(self, reason: str = "elective"):
        """Ask the run loop to re-form the lockstep world at its next
        tick (e.g. after ``instance_manager.set_world_size``).  Safe
        from any thread; coalesces with failure-driven re-formation."""
        with self._reform_request_lock:
            self._reform_requested = reason

    def request_stop(self):
        self._stop_requested = True

    def stop(self):
        if self.evaluation_service is not None:
            self.evaluation_service.stop()
        # any RPC-polling standby must learn the job is over
        self.servicer.drain_standbys()
        if self.instance_manager is not None:
            # voluntary-exit grace ONLY when the queue actually drained:
            # on failure the world hangs in collectives, and on an
            # interrupt workers are still mid-stream — both would eat
            # the full window and get terminated anyway
            clean_finish = (
                not self._job_failed and self.task_d.finished()
            )
            self.instance_manager.stop_workers(
                grace_secs=15.0 if clean_finish else 0.0
            )
        if self._server is not None:
            self._server.stop(grace=2)
            self._server = None
        if self.journal is not None:
            # a clean end is journaled so a relaunch-from-journal knows
            # there is nothing to recover (and doesn't wait for re-homes)
            self.journal.record_job_end(1 if self._job_failed else 0)
        self.telemetry.job_end(1 if self._job_failed else 0)
        if self._telemetry_server is not None:
            self._telemetry_server.stop()
            self._telemetry_server = None
        if self.tb_service is not None:
            # reference master.py:217-230 keeps TB alive after job end
            self.tb_service.close()

    # ---- summary ----------------------------------------------------------

    def job_summary(self) -> dict:
        out = {
            "job_type": self.job_type.value,
            "epoch": self.task_d.epoch,
        }
        for tt in (TaskType.TRAINING, TaskType.EVALUATION, TaskType.PREDICTION):
            c = self.task_d.counters(tt)
            if c.total_records:
                out[tt.name.lower()] = {
                    "total_records": c.total_records,
                    "failed_records": c.failed_records,
                }
                if c.exec_metrics:
                    # worker-reported per-job aggregates (DEBUG timing
                    # buckets, utils.timing_utils.exec_counters)
                    out[tt.name.lower()]["exec_metrics"] = dict(
                        c.exec_metrics
                    )
        summary = getattr(self.evaluation_service, "latest_summary", None)
        if summary:
            out["evaluation_metrics"] = summary
        if self.replica_directory is not None:
            out["replication"] = self.replica_directory.coverage_stats()
        events = getattr(self, "reform_events", None)
        if events:
            out["reforms"] = [
                {
                    k: v
                    for k, v in event.items()
                    if k
                    in (
                        "cluster_version",
                        "dead_workers",
                        "latency_secs",
                        "reason",
                    )
                }
                for event in events
            ]
        return out


class _AdoptedProcess:
    """Popen-alike handle for a worker process THIS master did not spawn:
    it survived a previous master's death (orphaned, re-parented to
    init) and re-homed with its pid.  Implements the subset of the Popen
    surface the instance manager uses (poll/kill/terminate/wait), signal
    based — the restarted master cannot ``waitpid`` a non-child.

    ``poll`` cannot observe the true exit code of a non-child; a
    vanished pid reports -1 (treated as failure).  A clean worker exit
    races the master's own ``finished()`` check exactly like spawned
    workers' rc-0 exits do, and the run loop breaks on ``finished()``
    before consulting ``poll_failed_workers``."""

    def __init__(self, pid: int):
        self.pid = pid
        self._rc: int | None = None

    def poll(self):
        if self._rc is not None:
            return self._rc
        try:
            os.kill(self.pid, 0)
        except ProcessLookupError:
            self._rc = -1
            return self._rc
        except PermissionError:
            # pid exists but belongs to someone else now (reuse): the
            # worker is gone
            self._rc = -1
            return self._rc
        return None

    def _signal(self, sig):
        try:
            os.kill(self.pid, sig)
        except (ProcessLookupError, PermissionError):
            self._rc = self._rc if self._rc is not None else -1

    def terminate(self):
        import signal

        self._signal(signal.SIGTERM)

    def kill(self):
        import signal

        self._signal(signal.SIGKILL)

    def wait(self, timeout: float | None = None):
        deadline = (
            time.monotonic() + timeout if timeout is not None else None
        )
        while self.poll() is None:
            if deadline is not None and time.monotonic() >= deadline:
                raise TimeoutError(
                    f"adopted worker pid {self.pid} still running"
                )
            time.sleep(0.05)
        return self._rc


class LocalInstanceManager:
    """Spawn workers as local subprocesses — the process analogue of the
    k8s InstanceManager (pods -> processes).  Each worker gets the master
    address and its id via argv (the reference master assembles worker
    argv the same way, master.py:331-384).

    With ``lockstep=True`` (``num_workers > 1``) the workers form one
    ``jax.distributed`` world: this manager allocates the coordinator
    port, assigns process ids 0..N-1, and re-forms the whole world on
    failure (``reform_world``) — the local equivalent of the reference's
    pod-relaunch elasticity (k8s_instance_manager.py:241-281), adapted to
    the SPMD constraint that a world is indivisible.
    """

    def __init__(
        self,
        master,
        num_workers: int,
        build_argv,
        envs: dict[str, str] | None = None,
        lockstep: bool = False,
        max_reforms: int = 3,
        standby_workers: int = -1,
        num_slices: int = 1,
    ):
        self._master = master
        self._num_workers = num_workers
        # (worker_id, master_addr, **world_kwargs) -> argv
        self._build_argv = build_argv
        self._envs = dict(envs or {})
        self.lockstep = lockstep and num_workers > 1
        self._max_reforms = max_reforms
        # slice topology (--num_slices): the fleet splits into this many
        # TPU slices; worlds resize in SLICE units (a whole-slice loss
        # shrinks to the survivors, a capacity grant grows back) and
        # every process learns its slice coordinates via world kwargs
        num_slices = max(1, int(num_slices or 1))
        if num_slices > 1 and not (lockstep and num_workers > 1):
            logger.warning(
                "--num_slices applies only to lockstep jobs "
                "(num_workers > 1); ignoring"
            )
            num_slices = 1
        if num_slices > 1 and num_workers % num_slices:
            raise ValueError(
                f"--num_workers {num_workers} not divisible by "
                f"--num_slices {num_slices}: the local backend needs "
                "equal processes per slice"
            )
        self._fleet_slices = num_slices
        self._procs_per_slice = num_workers // num_slices
        self._world_slices = num_slices
        # worker_id -> slice_id of the LIVE world (used by the master's
        # slice-loss accounting and the journal's world record)
        self._worker_slices: dict[int, int] = {}
        self._reforms = 0
        self._procs: dict[int, object] = {}
        self._next_worker_id = 0
        self._lock = threading.Lock()
        # hot-standby pool: processes spawned warm (imports done, blocked
        # on stdin) so reform_world skips the worker cold start — the
        # dominant term of re-formation latency.  Only a lockstep world
        # re-forms wholesale, so the pool exists only there.
        if standby_workers < 0:
            standby_workers = num_workers if self.lockstep else 0
        if standby_workers > 0 and not self.lockstep:
            logger.warning(
                "--standby_workers applies only to lockstep jobs "
                "(num_workers > 1); ignoring"
            )
        self._standby_target = standby_workers if self.lockstep else 0
        self._standbys: list = []
        self._draining = False
        self.standby_activations = 0
        # current lockstep world size: capacity faults/elasticity shrink
        # it below num_workers; the next (re)formation uses it
        self._world_size = num_workers
        # trace context of the re-formation the NEXT world belongs to
        # (set by Master._reform_lockstep, consumed by _start_world):
        # relaunched workers parent their world_join spans under it
        self.pending_world_trace: dict | None = None

    @property
    def world_size(self) -> int:
        return self._world_size

    @property
    def max_world_size(self) -> int:
        """The configured fleet size — what a full capacity restore
        grows back to (the live world may be smaller)."""
        return self._num_workers

    @property
    def fleet_slices(self) -> int:
        """Configured slice count of the full fleet (--num_slices)."""
        return self._fleet_slices

    @property
    def world_num_slices(self) -> int:
        """Slice count of the NEXT world (== the live one outside a
        resize window)."""
        return self._world_slices

    def set_world_size(self, n: int):
        """Resize the NEXT world (the live one is untouched until a
        re-formation — ask the master via ``request_reform``).  Clamped
        to [1, num_workers]: growth beyond the configured fleet would
        need new capacity this manager does not own.  On a multi-slice
        fleet the size snaps DOWN to a whole number of slices — worlds
        resize in slice units, never half a slice."""
        n = max(1, min(self._num_workers, int(n)))
        # getattr: partially-constructed test doubles predate slices
        if getattr(self, "_fleet_slices", 1) > 1:
            slices = max(1, n // self._procs_per_slice)
            self._world_slices = min(slices, self._fleet_slices)
            n = self._world_slices * self._procs_per_slice
        self._world_size = n

    def set_world_slices(self, n: int):
        """Resize the NEXT world in slice units (slice-granular
        elasticity: slice loss shrinks, capacity grant grows)."""
        n = max(1, min(self._fleet_slices, int(n)))
        self._world_slices = n
        self._world_size = min(
            self._num_workers, n * self._procs_per_slice
        )

    def worker_slices(self) -> dict[int, int]:
        """worker_id -> slice_id of the live world ({} when single
        slice): the master's slice-loss accounting input."""
        with self._lock:
            return dict(self._worker_slices)

    def restore_worker_slices(self, mapping: dict[int, int]):
        """Install a journal-restored world's slice map (the restarted
        master adopted workers it never spawned)."""
        with self._lock:
            self._worker_slices = {
                int(k): int(v) for k, v in (mapping or {}).items()
            }

    def worker_ids(self) -> list[int]:
        with self._lock:
            return list(self._procs)

    def adopt_worker(self, worker_id: int, pid: int):
        """Track a worker a PREVIOUS master spawned (it re-homed after a
        master restart): from here it is polled, fenced and killed like
        any spawned worker, so post-restart failure handling works."""
        with self._lock:
            if worker_id in self._procs:
                return
            self._procs[worker_id] = _AdoptedProcess(pid)
            self._next_worker_id = max(self._next_worker_id, worker_id + 1)
        logger.info(
            "Adopted re-homed worker %d (pid %d)", worker_id, pid
        )

    def start_workers(self):
        # build-or-fail BEFORE spawning: the workers share this checkout,
        # and one clear error beats N workers racing to build (or a
        # crash-loop burning the reform budget on a missing .so)
        from elasticdl_tpu.data.recordio import ensure_native_codec

        ensure_native_codec()
        if self.lockstep:
            self._start_world(cluster_version=0)
            self._replenish_standbys()
        else:
            for _ in range(self._num_workers):
                self._start(self._claim_worker_id())

    def _claim_worker_id(self) -> int:
        with self._lock:
            worker_id = self._next_worker_id
            self._next_worker_id += 1
            return worker_id

    def _start_world(self, cluster_version: int, num_processes: int | None = None):
        from elasticdl_tpu.parallel import elastic
        from elasticdl_tpu.parallel.mesh import slice_assignments

        n = num_processes if num_processes is not None else self._world_size
        coordinator = f"localhost:{elastic.pick_coordinator_port()}"
        trace, self.pending_world_trace = self.pending_world_trace, None
        # slice coordinates ride the world kwargs ONLY on a multi-slice
        # world: single-slice worker argv stays byte-identical to a
        # slice-blind build
        assign = (
            slice_assignments(n, self._world_slices)
            if self._world_slices > 1
            else None
        )
        with self._lock:
            self._worker_slices = {}
        for process_id in range(n):
            world = dict(
                coordinator_addr=coordinator,
                num_processes=n,
                process_id=process_id,
                cluster_version=cluster_version,
            )
            if assign is not None:
                world["slice_id"] = assign[process_id]
                world["num_slices"] = self._world_slices
            if trace:
                world["trace"] = dict(trace)
            worker_id = self._claim_worker_id()
            if assign is not None:
                with self._lock:
                    self._worker_slices[worker_id] = assign[process_id]
            if not self._activate_standby(worker_id, world):
                self._start(worker_id, **world)

    def _spawn(self, worker_id: int, stdin_pipe: bool = False, **world_kwargs):
        # the reform trace context travels by env, not argv (it is a
        # dict, and argv is the flag round-trip)
        trace = world_kwargs.pop("trace", None)
        argv = self._build_argv(
            worker_id, f"localhost:{self._master.port}", **world_kwargs
        )
        env = dict(os.environ)
        env.update(self._envs)
        if "process_id" in world_kwargs:
            # the local workers share ONE host, where a chip belongs to
            # one process at a time: each lockstep process is bound to
            # its own chip by world coordinates (inert on CPU).  Decided
            # HERE, not in the worker — a k8s worker owns its whole host.
            # A standby has no world yet: its binding rides the
            # assignment (_activate_standby)
            from elasticdl_tpu.parallel.elastic import chip_binding_env

            env.update(
                chip_binding_env(
                    world_kwargs["process_id"], world_kwargs["num_processes"]
                )
            )
        if trace:
            from elasticdl_tpu.telemetry.tracing import TRACE_PARENT_ENV

            env[TRACE_PARENT_ENV] = json.dumps(trace)
        # make the framework importable regardless of the master's cwd
        import elasticdl_tpu

        pkg_root = os.path.dirname(os.path.dirname(elasticdl_tpu.__file__))
        env["PYTHONPATH"] = os.pathsep.join(
            p for p in (pkg_root, env.get("PYTHONPATH", "")) if p
        )
        return subprocess.Popen(
            [sys.executable, "-m", *argv],
            env=env,
            stdin=subprocess.PIPE if stdin_pipe else None,
        )

    def _start(self, worker_id: int, **world_kwargs):
        proc = self._spawn(worker_id, **world_kwargs)
        with self._lock:
            self._procs[worker_id] = proc
        logger.info("Started worker %d (pid %d)", worker_id, proc.pid)

    # ---- hot-standby pool -------------------------------------------------

    def _replenish_standbys(self):
        with self._lock:
            if self._draining:
                return
            # prune corpses (a standby that died while waiting) so the
            # pool list cannot grow unboundedly across re-formations
            self._standbys = [p for p in self._standbys if p.poll() is None]
            missing = self._standby_target - len(self._standbys)
        for _ in range(max(0, missing)):
            try:
                proc = self._spawn(0, stdin_pipe=True, standby=1)
            except OSError:
                # refill runs on an unguarded daemon thread: one Popen
                # failure (fd exhaustion, fork limits) must not abort the
                # rest of the refill and leave the pool empty
                logger.exception(
                    "Failed to spawn standby process; continuing refill"
                )
                continue
            with self._lock:
                accepted = not self._draining
                if accepted:
                    self._standbys.append(proc)
            if not accepted:
                # stop_workers ran while we were spawning: this standby
                # would never be drained — reap it now
                try:
                    proc.stdin.close()
                except OSError:
                    pass
                proc.kill()
                return
            logger.info("Spawned standby worker (pid %d)", proc.pid)

    def _activate_standby(self, worker_id: int, world: dict) -> bool:
        """Hand a warm standby its world assignment; False = none usable
        (caller cold-starts instead)."""
        while True:
            with self._lock:
                if not self._standbys:
                    return False
                proc = self._standbys.pop(0)
            if proc.poll() is not None:
                continue  # died while waiting; try the next one
            try:
                # the standby was spawned before its world existed, so
                # its chip binding travels with the assignment (it has
                # imported but not initialized a backend)
                from elasticdl_tpu.parallel.elastic import chip_binding_env

                assignment = {
                    "worker_id": worker_id,
                    **world,
                    "env": chip_binding_env(
                        world["process_id"], world["num_processes"]
                    ),
                }
                line = json.dumps(assignment) + "\n"
                proc.stdin.write(line.encode("utf-8"))
                proc.stdin.flush()
            except (OSError, ValueError):
                proc.kill()
                continue
            with self._lock:
                self._procs[worker_id] = proc
                self.standby_activations += 1
            logger.info(
                "Activated standby pid %d as worker %d (process %d/%d)",
                proc.pid,
                worker_id,
                world["process_id"],
                world["num_processes"],
            )
            return True

    def _drain_standbys(self):
        with self._lock:
            self._draining = True  # fence concurrent _replenish_standbys
            standbys = list(self._standbys)
            self._standbys.clear()
        for proc in standbys:
            if proc.poll() is None:
                try:  # EOF on stdin is the clean shutdown signal
                    proc.stdin.close()
                except OSError:
                    pass
                try:
                    proc.wait(timeout=5)
                except Exception:  # noqa: BLE001
                    proc.kill()

    def poll_failed_workers(self) -> list[int]:
        """Worker ids whose subprocess exited abnormally (nonzero rc or
        signal) — the local analogue of the reference's k8s pod watch
        (k8s_client.py:84-98): events beat heartbeat timeouts at
        detection speed.  Normal exits (rc 0) are NOT failures: workers
        exit 0 at stream end, racing the master's own finished() check;
        a premature rc-0 exit is still caught by the heartbeat timeout."""
        with self._lock:
            return [
                wid
                for wid, proc in self._procs.items()
                if proc.poll() not in (None, 0)
            ]

    def restart_worker(self, worker_id: int):
        """Relaunch with a NEW worker id (reference
        k8s_instance_manager.py:266-275).  Task-stream workers only; a
        lockstep worker cannot be replaced individually (reform_world)."""
        with self._lock:
            proc = self._procs.pop(worker_id, None)
        if proc is not None and proc.poll() is None:
            proc.terminate()
        self._start(self._claim_worker_id())

    def reform_world(
        self, cluster_version: int, count_against_budget: bool = True
    ):
        """Kill the old world and launch a new one.  Survivors may be
        blocked inside a collective that will never complete — SIGKILL,
        not SIGTERM, is the correct mercy.  The old world is ALWAYS torn
        down; only the relaunch is subject to the reform budget (a
        deterministic crash must not loop forever, reference OOM
        blacklist k8s_instance_manager.py:225-240).
        ``count_against_budget=False`` for ELECTIVE re-formations
        (capacity changes): a planned resize is not a crash and must not
        eat into the failure-recovery allowance."""
        with self._lock:
            procs = list(self._procs.values())
            self._procs.clear()
        for proc in procs:
            if proc.poll() is None:
                proc.kill()
        for proc in procs:
            try:
                proc.wait(timeout=10)
            except Exception:  # noqa: BLE001
                pass
        if count_against_budget:
            self._reforms += 1
        if self._reforms > self._max_reforms:
            raise RuntimeError(
                f"world re-formed {self._reforms - 1} times "
                f"(--relaunch_on_worker_failure limit); giving up"
            )
        self._start_world(cluster_version=cluster_version)
        # refill the pool AFTER the new world is up, off the recovery
        # path (the spawns are exactly what re-formation must not wait on)
        threading.Thread(
            target=self._replenish_standbys, daemon=True
        ).start()

    def teardown_world(self, budget: bool = False):
        """Kill the live world WITHOUT relaunching — graceful
        degradation's park path (the master harvested replicas first;
        lingering crashed survivors end here).  ``budget=False``: a park
        is not a crash loop."""
        with self._lock:
            procs = list(self._procs.values())
            self._procs.clear()
            self._worker_slices = {}
        for proc in procs:
            if proc.poll() is None:
                proc.kill()
        for proc in procs:
            try:
                proc.wait(timeout=10)
            except Exception:  # noqa: BLE001
                pass
        if budget:
            self._reforms += 1

    def stop_workers(self, grace_secs: float = 15.0):
        """Stop worker subprocesses.  Workers exit on their own once the
        step stream ends, but their epilogue (final-state dump, async
        checkpoint flush) can still be mid-COLLECTIVE when the master's
        queue drains — terminating immediately kills one process and the
        JAX coordination service then fatals the others.  So first give
        the voluntary-exit window (the k8s analogue is the pod grace
        period), then terminate stragglers.  Failure paths pass
        ``grace_secs=0``: crashed worlds hang in collectives and would
        always eat the full window."""
        self._drain_standbys()
        with self._lock:
            procs = list(self._procs.values())
            self._procs.clear()
        deadline = time.monotonic() + max(0.0, grace_secs)
        for proc in procs:
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                break
            try:
                proc.wait(timeout=remaining)
            except Exception:  # noqa: BLE001 — still running
                pass
        for proc in procs:
            if proc.poll() is None:
                proc.terminate()
        for proc in procs:
            try:
                proc.wait(timeout=10)
            except Exception:  # noqa: BLE001
                proc.kill()
