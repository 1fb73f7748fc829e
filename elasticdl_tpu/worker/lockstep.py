"""Lockstep worker: the multi-process SPMD training runtime.

This is what makes ``--num_workers N`` train ONE model: all N worker
processes join one ``jax.distributed`` world (``parallel.elastic``), build
one global mesh, and execute the SAME sequence of jitted steps — the
lockstep invariant every multi-process XLA program must satisfy.  The
reference achieves N-workers-one-model with PS pull/push over gRPC
(``elasticdl/python/worker/worker.py:295-530``) or FTLib allreduce
(``:697-758``); here gradient sync is the psum GSPMD derives from
shardings, and the only cross-process coordination is the master's
memoized step-task stream (``MasterServicer.get_step_task``).

Data path: tasks are small, addressable record ranges, so EVERY process
reads the full range of each task and contributes the rows its devices
own (``SPMDTrainer.place_batch`` over ``elastic.local_batch_ranges``) —
no host-to-host data transfer, identical global batches to a
single-process run, and any process can be lost without losing data (the
task re-queues).

Per-task batching: each task's records are batched independently, every
batch padded to ONE canonical shape with a per-row weight mask (padded
rows contribute exactly zero gradient — ``trainer/stacking.py``), so the
number of steps per task AND the shape of every dispatch are pure
functions of the task — every process agrees on both without
communication, and a ragged tail can neither recompile the step nor
desync the collectives.  This deviates from the task-stream Worker's
batches-straddle-tasks pipelining (task_data_service.py), trading a few
zero-weighted rows for a communication-free lockstep schedule.
"""

from __future__ import annotations

import contextlib
import os
import time
import traceback

import jax
import numpy as np

from elasticdl_tpu.data.factory import create_data_reader
from elasticdl_tpu.data.fast_pipeline import build_task_batches
from elasticdl_tpu.master.task_dispatcher import FAIL_COUNT
from elasticdl_tpu.parallel import elastic
from elasticdl_tpu.parallel.distributed import SPMDTrainer, trim_pad
from elasticdl_tpu.parallel.mesh import MeshConfig
from elasticdl_tpu.rpc import messages as msg
from elasticdl_tpu.trainer.checkpointing import (
    PeriodicCheckpointer,
    restore_trainer_state,
)
from elasticdl_tpu.trainer.local_executor import build_optimizer
from elasticdl_tpu.trainer.state import Modes
from elasticdl_tpu.utils.args import derive_job_type
from elasticdl_tpu.utils.constants import JobType, TaskType
from elasticdl_tpu.utils.log_utils import default_logger as logger
from elasticdl_tpu.utils.model_utils import get_model_spec
from elasticdl_tpu.utils.timing_utils import Timing

# Debug hook: when set, each process dumps its final dense state to
# $ELASTICDL_TPU_DUMP_STATE/final_state_p{process_id}.npz — used by tests
# to assert bitwise-identical parameters across processes.
_DUMP_STATE_ENV = "ELASTICDL_TPU_DUMP_STATE"


class LockstepWorker:
    def __init__(self, args, master, devices=None):
        self._args = args
        self._master = master
        self._worker_id = int(getattr(args, "worker_id", 0) or 0)
        self._process_id = int(getattr(args, "process_id", 0) or 0)
        self._num_processes = int(getattr(args, "num_processes", 1) or 1)
        self._cluster_version = int(getattr(args, "cluster_version", 0) or 0)
        self._minibatch_size = args.minibatch_size
        self._job_type = derive_job_type(args)
        self._timing = Timing(
            enabled=getattr(args, "log_level", "INFO") == "DEBUG",
            logger=logger,
        )

        self._spec = get_model_spec(
            getattr(args, "model_zoo", "") or "",
            args.model_def,
            model_params=getattr(args, "model_params_dict", {}) or {},
            dataset_fn=getattr(args, "dataset_fn", "dataset_fn"),
            loss=getattr(args, "loss", "loss"),
            optimizer=getattr(args, "optimizer", "optimizer"),
            eval_metrics_fn=getattr(args, "eval_metrics_fn", "eval_metrics_fn"),
        )
        self._model = self._spec.build_model()

        data_origin = (
            args.prediction_data
            if self._job_type == JobType.PREDICTION_ONLY
            else args.training_data or args.validation_data
        )
        create = self._spec.custom_data_reader or create_data_reader
        self._reader = create(
            data_origin=data_origin,
            **(getattr(args, "data_reader_params_dict", {}) or {}),
        )

        mesh_shape = getattr(args, "mesh_shape", "") or ""
        dcn_shape = getattr(args, "dcn_mesh_shape", "") or ""
        # slice coordinates of a multi-slice world (assigned by the
        # instance manager per generation, like process_id).  On a
        # backend whose devices carry no slice_index (CPU) the canonical
        # process->slice map forces the hybrid ICI/DCN layout — the same
        # map the master used to assign --slice_id, so membership and
        # mesh can never disagree
        self._slice_id = int(getattr(args, "slice_id", 0) or 0)
        self._num_slices = int(getattr(args, "num_slices", 1) or 1)
        slice_fn = None
        if self._num_slices > 1:
            from elasticdl_tpu.parallel.mesh import resolved_slice_index_fn

            slice_fn = resolved_slice_index_fn(
                devices if devices is not None else jax.devices(),
                self._num_processes,
                self._num_slices,
            )
        self._mesh = MeshConfig.from_string(mesh_shape, dcn_shape).create(
            devices, slice_index_fn=slice_fn
        )
        # the PHYSICAL process->slice placement the mesh resolved (==
        # the canonical map on forced layouts; the hardware truth on
        # real multislice) — what the replica ring keys off
        self._mesh_slice_map: list[int] | None = None
        if self._num_slices > 1:
            from elasticdl_tpu.parallel.mesh import mesh_process_slice_map

            self._mesh_slice_map = mesh_process_slice_map(
                self._mesh, slice_fn
            )
        self._trainer: SPMDTrainer | None = None
        self._stopped = False
        # master HA: the lease currently in flight (presented in the
        # re-homing handshake) and the last master boot id seen on a
        # heartbeat — a CHANGED boot id means this process outlived a
        # master and must re-home
        self._current_task_id: int | None = None
        self._master_boot_id: str | None = None
        # shape-canonical batching: one dispatch shape per step kind, a
        # pure function of (minibatch_size, mesh) — identical on every
        # process, so the lockstep schedule AND shapes agree by
        # construction (a tail shape disagreement was a collective-
        # deadlock hazard)
        from elasticdl_tpu.parallel.mesh import batch_divisor
        from elasticdl_tpu.trainer.stacking import canonical_batch_rows

        self._canonical_rows = canonical_batch_rows(
            self._minibatch_size, batch_divisor(self._mesh)
        )
        # device-path pipelining: resolved from the master-forwarded env
        # (the flag never reaches worker argv).  Uniform across the
        # world by construction — it changes the compiled step program
        # (batch-buffer donation), so processes must not disagree; the
        # staging thread itself is lockstep-safe (dispatch order stays
        # on this thread, placement is process-local)
        from elasticdl_tpu.trainer.device_pipeline import (
            resolve_device_prefetch,
            resolve_pipeline_depth,
        )

        self._device_prefetch = resolve_device_prefetch(
            getattr(args, "device_prefetch", None)
        )
        # tunable retire window (--pipeline_depth, master-forwarded).
        # Cross-task staging (--boundary_fusion) is deliberately NOT
        # wired here: the lockstep schedule's reform fence quiesces at
        # task boundaries, and groups staged across a fence on some
        # processes but not others would be a world-divergence hazard —
        # the boundary-only barrier IS the lockstep safety argument.
        self._pipeline_depth = resolve_pipeline_depth(
            getattr(args, "pipeline_depth", None)
        )
        # deterministic fault injection (chaos subsystem): a no-op unless
        # the master exported a plan into this process's environment
        from elasticdl_tpu.chaos import hooks as chaos_hooks

        self._chaos = chaos_hooks.install_from_env(
            self._process_id,
            self._cluster_version,
            self._worker_id,
            slice_id=self._slice_id,
        )
        # telemetry step sampling (no-op unless the master exported
        # ELASTICDL_TPU_TELEMETRY_DIR): a re-formed world installs a
        # fresh recorder stamped with its generation
        from elasticdl_tpu.telemetry import tracing
        from elasticdl_tpu.telemetry import worker_hooks as telemetry_hooks

        telemetry_hooks.install_from_env(
            worker_id=self._worker_id,
            process_id=self._process_id,
            generation=self._cluster_version,
        )
        # per-dispatch phase anatomy (enabled by the master's forwarded
        # ELASTICDL_TPU_STEP_ANATOMY, never argv): phase totals ship on
        # the heartbeat like the PR-8 RPC counters
        from elasticdl_tpu.telemetry import anatomy as anatomy_mod

        self._anatomy_mod = anatomy_mod
        anatomy_mod.install_from_env(
            model_def=getattr(args, "model_def", "") or ""
        )
        # memory ledger (telemetry/memory.py): sampled on the heartbeat
        # cadence, shipped as HeartbeatRequest.memory; no-op without the
        # master-exported telemetry dir
        from elasticdl_tpu.telemetry import memory as memory_mod

        memory_mod.install_from_env()
        memory_mod.register_trainer_state(
            lambda: self._trainer.state if self._trainer is not None else None
        )
        # process-wide compile counter; the chief ships deltas to the
        # master as a `compile_count` exec counter with task reports
        from elasticdl_tpu.telemetry import compile_tracker

        compile_tracker.install()
        self._compile_deltas = compile_tracker.ExecCounterReporter()
        # span tracer (worker/main.py installs it for subprocess entry;
        # in-process harnesses construct the worker directly, so make
        # install idempotent here with the same world identity)
        if tracing.get_tracer() is None:
            tracing.install_from_env(
                worker_id=self._worker_id,
                process_id=self._process_id,
                generation=self._cluster_version,
            )
        self._tracing = tracing
        self._checkpointer = PeriodicCheckpointer(
            getattr(args, "checkpoint_dir", "") or "",
            getattr(args, "checkpoint_steps", 0) or 0,
            getattr(args, "keep_checkpoint_max", 3),
            process_id=self._process_id,
            num_parts=self._num_processes,
        )
        # peer state replication (elasticdl_tpu.replication): a replica
        # server + ring pusher per process, lockstep worlds only — a
        # single process has no surviving peer to restore from
        self._replicator = None
        self._replica_server = None
        self._replica_store = None
        # replication ON (the flag, not the ring): even a single-process
        # world — e.g. one shrunk to a lone surviving slice — must still
        # ASK the master for a staged replica harvest at restore time
        self._replication_on = bool(getattr(args, "replication", False))
        if self._replication_on and self._num_processes > 1:
            from elasticdl_tpu.replication.replicator import (
                PeerReplicator,
                replica_host,
            )
            from elasticdl_tpu.replication.service import (
                start_replica_server,
            )
            from elasticdl_tpu.replication.store import ReplicaStore

            store = ReplicaStore(generation=self._cluster_version)
            self._replica_store = store
            self._replica_server, replica_port = start_replica_server(store)
            self._replicator = PeerReplicator(
                store,
                process_id=self._process_id,
                num_processes=self._num_processes,
                generation=self._cluster_version,
                addr=f"{replica_host()}:{replica_port}",
                replication_steps=getattr(args, "replication_steps", 0) or 0,
                # slice-aware ring: the neighbor is repinned off-slice so
                # a whole-slice loss never takes a shard and its only
                # replica together; keyed by the MESH's physical
                # placement, not the canonical assignment
                num_slices=self._num_slices,
                slice_map=self._mesh_slice_map,
            )
        from elasticdl_tpu.utils.profiling import StepProfiler

        # per-process trace subdir: each host profiles its own devices
        profile_dir = getattr(args, "profile_dir", "") or ""
        self._profiler = StepProfiler(
            os.path.join(profile_dir, f"process_{self._process_id}")
            if profile_dir
            else "",
            num_steps=getattr(args, "profile_steps", 5),
        )

    # ---- process-0-only master reporting -----------------------------------

    @property
    def _is_chief(self) -> bool:
        return self._process_id == 0

    def _report_task_result(
        self, task_id, err_msg="", fail_count=0, include_timing=False,
        trace=None,
    ):
        if not self._is_chief:
            return
        counters = {FAIL_COUNT: fail_count} if fail_count else {}
        if include_timing:
            # chief's buckets; training reports only (same gating as the
            # task-stream Worker so eval/save never absorb train time)
            counters.update(self._timing.exec_counters())
        # compile DELTA since the last SUCCESSFUL report (every report
        # kind — eval/predict compiles count too): the master's
        # elasticdl_compile_total mirror sums these, so a mid-task
        # recompile shows up on /metrics within one task report
        compile_mark = self._compile_deltas.attach(counters)
        from elasticdl_tpu.telemetry.tracing import SPAN_REPORT_TASK

        t0 = time.monotonic()
        self._master.report_task_result(
            msg.ReportTaskResultRequest(
                task_id=task_id,
                err_message=err_msg,
                exec_counters=counters,
                trace=dict(trace or {}),
            )
        )
        self._compile_deltas.commit(compile_mark)
        tracer = self._tracing.get_tracer()
        if tracer is not None:
            tracer.record_span(
                SPAN_REPORT_TASK,
                t0,
                time.monotonic(),
                trace_ctx=trace,
                task_id=task_id,
                error=bool(err_msg),
            )

    def _report_version(self):
        if self._is_chief and self._trainer is not None:
            self._master.report_version(
                msg.ReportVersionRequest(
                    model_version=self._trainer.step,
                    worker_id=self._worker_id,
                )
            )

    # ---- trainer lifecycle -------------------------------------------------

    def _ensure_trainer(self, sample_features):
        if self._trainer is not None:
            return
        # reform-phase span: on a relaunched world the trainer build
        # (state init + placement) is a named downtime term, with the
        # checkpoint restore span nested inside it
        from elasticdl_tpu.telemetry.tracing import (
            SPAN_TRAINER_BUILD,
            trace_span,
        )

        with trace_span(SPAN_TRAINER_BUILD):
            rules = ()
            if self._spec.sharding_rules is not None:
                rules = tuple(self._spec.sharding_rules(self._mesh))
            tx = build_optimizer(
                self._spec, getattr(self._args, "learning_rate", None)
            )
            compute_dtype = getattr(self._args, "compute_dtype", "float32")
            from elasticdl_tpu.parallel import program_store
            from elasticdl_tpu.trainer.device_pipeline import (
                resolve_donate_state,
            )

            self._trainer = SPMDTrainer(
                self._mesh,
                self._model,
                self._spec.loss,
                tx,
                sample_features,
                rules=rules,
                compute_dtype=None
                if compute_dtype == "float32"
                else compute_dtype,
                remat=bool(getattr(self._args, "remat", False)),
                donate=resolve_donate_state(self._args),
                device_parse=self._spec.device_parse,
                donate_batch=self._device_prefetch,
                job_identity=program_store.job_identity(
                    self._args, self._spec.module
                ),
            )
            version = self._restore_state()
        if version is not None:
            self._checkpointer.note_restored_version(version)
            if self._replicator is not None:
                self._replicator.note_restored_version(version)

    def _restore_state(self) -> int | None:
        """Peer-RAM replica stage first (a reform the master harvested
        for), disk second.  The stage is fenced by generation and set
        before relaunch, so every process of this world resolves the
        same source — the restore itself stays process-local either
        way (lockstep invariant preserved)."""
        if self._replication_on:
            from elasticdl_tpu.replication.replicator import (
                restore_from_replica,
            )
            from elasticdl_tpu.utils import save_utils

            ckpt_dir = getattr(self._args, "checkpoint_dir", "") or ""
            disk_floor = (
                save_utils.latest_version(ckpt_dir) if ckpt_dir else None
            )
            version = restore_from_replica(
                self._trainer,
                self._master,
                self._cluster_version,
                self._process_id,
                min_version=disk_floor,
            )
            if version is not None:
                return version
        return restore_trainer_state(
            self._trainer, self._args, self._process_id
        )

    def _maybe_checkpoint(self):
        """Periodic checkpoint every ``checkpoint_steps`` (reference
        ps/servicer.py:216-231 checkpoints on the PS; here each process
        writes its own part).  Runs at task boundaries only, so every
        process agrees on when any gather collective happens."""
        self._checkpointer.maybe_save(self._trainer, self._mesh)
        if self._replicator is not None:
            # same boundary-only rule, same reason: the snapshot's
            # dense/parts split may contain a gather collective, and the
            # cadence decision is a pure function of the shared step
            self._replicator.maybe_replicate(self._trainer, self._mesh)

    # ---- batching ----------------------------------------------------------

    def _task_batches(self, task, mode: Modes):
        """Global minibatches of one task — identical on every process.

        The shared chooser picks the vectorized fast path when
        available; its permutation shuffle is a pure function of (module
        seed policy, task), so every process computes the same batch
        stream and the lockstep schedule agreement is preserved on
        either path (batch count is identical by construction).

        An EXPLICIT ``--steps_per_dispatch k`` additionally emits
        zero-copy PreStacked dispatch groups from the decode window
        (pure function of task data + k — identical everywhere, so the
        world agrees on every dispatch shape), skipping the per-batch
        pad/stack assembly run_stacked_steps would otherwise do on the
        training thread.  ``allow_auto=False``: see
        :func:`~elasticdl_tpu.trainer.stacking.choose_stack_k` — a
        per-process auto probe could deadlock the world."""
        from elasticdl_tpu.parallel.mesh import batch_divisor
        from elasticdl_tpu.trainer.stacking import choose_stack_k

        stack_k = choose_stack_k(
            getattr(self._args, "steps_per_dispatch", 1),
            mode == Modes.TRAINING,
            allow_auto=False,
        )

        return build_task_batches(
            self._reader,
            task,
            self._spec,
            mode,
            self._reader.metadata,
            self._minibatch_size,
            shuffle_records=mode == Modes.TRAINING,
            # a host missing the native codec must fail loudly, not
            # silently take the differently-shuffled classic path while
            # its peers vectorize (the probe half of the choice is
            # data-driven and therefore already identical everywhere)
            require_deterministic_choice=True,
            stack_k=stack_k,
            stack_divisor=batch_divisor(self._mesh),
        )

    def _place(self, tree):
        return self._trainer.place_canonical(tree, self._canonical_rows)

    # ---- task execution ----------------------------------------------------

    def _train_task(self, task):
        # shared grouping policy (trainer.stacking; k=1 is a group of
        # one): every process sees the same deterministic batch stream
        # per task, so all processes compute the same grouping — and
        # the scanned dispatch contains the same collectives
        from elasticdl_tpu.trainer.stacking import run_stacked_steps

        from elasticdl_tpu.telemetry.tracing import (
            SPAN_TASK_EXECUTE,
            record_step_span,
            trace_fetches,
            trace_span,
        )
        from elasticdl_tpu.telemetry.worker_hooks import record_step

        def _pre(features):
            self._ensure_trainer(features)
            self._profiler.on_step(self._trainer.step)
            # per-step telemetry sample (a single early-return when
            # telemetry is not installed); every process steps through
            # the full global batch, so records == global minibatch
            record_step(int(self._trainer.step), self._minibatch_size)
            # sampled jitted-step span (same early-return contract)
            record_step_span(int(self._trainer.step))
            if self._chaos is not None:
                # per-minibatch arming point: step-scheduled faults fire
                # at the exact model version the plan names
                self._chaos.on_step(int(self._trainer.step))

        # the task span joins the master's dispatch trace (one task =
        # one trace across master and workers) and is the implicit
        # parent of the fetch/step spans recorded inside it
        with trace_span(
            SPAN_TASK_EXECUTE,
            trace_ctx=task.trace,
            task_id=task.task_id,
            shard=task.shard_name,
        ) as task_span, self._crash_on_error(task):
            # build the stream INSIDE the crash protocol: a loud
            # deterministic-choice failure here must report-and-crash
            # like any other lockstep error, not escape unreported
            batches = self._task_batches(task, Modes.TRAINING)
            batches = trace_fetches(
                batches, trace_ctx=task.trace, span=task_span
            )
            if self._chaos is not None:
                batches = self._chaos.wrap_batches(batches)
            run_stacked_steps(
                lambda: self._trainer,
                batches,
                getattr(self._args, "steps_per_dispatch", 1) or 1,
                pre_batch=_pre,
                dispatch_ctx=lambda: self._timing.record("batch_process"),
                # 'auto' must resolve identically on every process (a k
                # disagreement compiles different stacked programs and
                # deadlocks the collectives): byte rule only, no
                # per-process wall-clock probe
                deterministic_auto=True,
                canonical_rows=self._canonical_rows,
                # anatomy changes TIMING only (an extra block on the
                # dispatch outputs), never shapes or dispatch count, so
                # the lockstep schedule agreement is preserved even if
                # only some processes had it enabled
                anatomy=self._anatomy_mod.get_recorder(),
                # staging/retire-behind also change only WHEN host work
                # happens — the dispatch sequence stays a pure function
                # of (task data, k), identical on every process
                device_prefetch=self._device_prefetch,
                pipeline_depth=self._pipeline_depth,
            )
        # boundary-stall instrumentation: arm the mark as soon as the
        # task's dispatches drained, so the boundary bookkeeping below
        # (report, version, checkpoint) is inside the measured gap; the
        # next task's first dispatch closes it (timing only — never
        # dispatch shapes or order)
        from elasticdl_tpu.trainer.device_pipeline import note_task_boundary

        note_task_boundary()
        self._report_task_result(
            task.task_id, include_timing=True, trace=task.trace
        )
        self._timing.report_timing(reset=True)
        self._report_version()
        self._maybe_checkpoint()

    @contextlib.contextmanager
    def _crash_on_error(self, task):
        """Lockstep error policy: an error on ONE process desyncs the
        world's collectives — peers may already be blocked in a psum this
        process will never join.  Catch-and-continue (the task-stream
        Worker's minibatch retry, reference worker.py:800-840) is
        therefore UNSAFE here; the only sound recovery is to report and
        crash, stopping the heartbeat so the master re-forms the world
        and re-queues the task.  A deterministic failure is bounded by
        the master's reform budget (--relaunch_on_worker_failure)."""
        try:
            yield
        except Exception as ex:  # noqa: BLE001
            traceback.print_exc()
            self._report_task_result(
                task.task_id,
                str(ex),
                fail_count=task.end - task.start,
                trace=getattr(task, "trace", None),
            )
            self._stopped = True
            logger.error(
                "Process %d crashing after task %d failed: %s",
                self._process_id,
                task.task_id,
                ex,
            )
            raise

    def _eval_task(self, task):
        from elasticdl_tpu.telemetry.tracing import (
            SPAN_TASK_EXECUTE,
            trace_span,
        )

        all_outputs, all_labels = [], []
        with trace_span(
            SPAN_TASK_EXECUTE,
            trace_ctx=task.trace,
            task_id=task.task_id,
            shard=task.shard_name,
            eval=True,
        ), self._crash_on_error(task):
            for features, labels in self._task_batches(task, Modes.EVALUATION):
                self._ensure_trainer(features)
                n = _batch_len(labels)
                outputs, _ = self._trainer.eval_step(
                    self._place(features),
                    self._place(labels),
                    self._trainer.place_mask(n, self._canonical_rows),
                )
                # collective gather so the chief holds full outputs, in
                # global batch order (matches the labels read host-side)
                host = elastic.replicate_to_hosts(outputs, self._mesh)
                all_outputs.append(trim_pad(host, n))
                all_labels.append(np.asarray(labels))
        if all_outputs and self._is_chief:
            outputs = jax.tree_util.tree_map(
                lambda *xs: np.concatenate(xs, axis=0), *all_outputs
            )
            labels = np.concatenate(all_labels, axis=0)
            self._report_eval_metrics(outputs, labels, task)
        self._report_task_result(task.task_id, trace=task.trace)

    def _report_eval_metrics(self, outputs, labels, task):
        from elasticdl_tpu.utils.tensor import ndarray_to_tensor

        if isinstance(outputs, dict):
            out_tensors = {
                k: ndarray_to_tensor(k, np.asarray(v))
                for k, v in outputs.items()
            }
        else:
            out_tensors = {
                "output": ndarray_to_tensor("output", np.asarray(outputs))
            }
        self._master.report_evaluation_metrics(
            msg.ReportEvaluationMetricsRequest(
                model_outputs=out_tensors,
                labels=ndarray_to_tensor("labels", labels),
                model_version=task.model_version,
                task_id=task.task_id,
                evaluated_version=self._trainer.step if self._trainer else -1,
            )
        )

    def _predict_task(self, task):
        with self._crash_on_error(task):
            for features in self._task_batches(task, Modes.PREDICTION):
                self._ensure_trainer(features)
                n = _batch_len(features)
                outputs = self._trainer.predict_step(self._place(features))
                host = trim_pad(
                    elastic.replicate_to_hosts(outputs, self._mesh), n
                )
                if (
                    self._is_chief
                    and self._spec.prediction_outputs_processor is not None
                ):
                    self._spec.prediction_outputs_processor.process(
                        host, self._worker_id
                    )
        self._report_task_result(task.task_id)

    def _save_model_task(self, task):
        with self._crash_on_error(task):
            if self._trainer is None:
                # export requested with no training step run (restart after
                # training drained): initialize from one example batch —
                # which with explicit --steps_per_dispatch arrives as a
                # PreStacked group, not a (features, labels) pair
                from elasticdl_tpu.trainer.stacking import PreStacked

                for item in self._task_batches(task, Modes.TRAINING):
                    features = (
                        item.sample_features
                        if isinstance(item, PreStacked)
                        else item[0]
                    )
                    self._ensure_trainer(features)
                    break
            if self._trainer is None:
                raise RuntimeError("no trained state to save")
            host_state = elastic.replicate_to_hosts(
                self._trainer.state, self._mesh
            )
            if self._is_chief:
                path = task.extended.get("saved_model_path", "") or getattr(
                    self._args, "output", ""
                )
                from elasticdl_tpu.utils.export_utils import export_model

                export_model(path, host_state, self._spec, self._args)
        self._report_task_result(task.task_id)

    # ---- main loop ---------------------------------------------------------

    def _start_heartbeats(self, interval_secs: float = 2.0):
        import threading

        from elasticdl_tpu.rpc import stats as rpc_stats
        from elasticdl_tpu.telemetry import memory as memory_mod
        from elasticdl_tpu.telemetry.anatomy import (
            heartbeat_snapshot as anatomy_snapshot,
        )
        from elasticdl_tpu.telemetry.worker_hooks import TELEMETRY_DIR_ENV
        from elasticdl_tpu.trainer.device_pipeline import (
            heartbeat_snapshot as prefetch_snapshot,
        )
        from elasticdl_tpu.utils.profiling import apply_profile_command

        telemetry_dir = os.environ.get(TELEMETRY_DIR_ENV, "")

        def beat():
            while not self._stopped:
                if (
                    self._chaos is not None
                    and self._chaos.heartbeat_suppressed()
                ):
                    # injected silence: the process lives on but the
                    # master must see a dead worker
                    time.sleep(interval_secs)
                    continue
                t0 = time.monotonic()
                # the beat IS the periodic memory sample cadence (no-op
                # without an installed ledger)
                memory_mod.sample()
                try:
                    # the heartbeat doubles as the replica directory's
                    # advertisement channel (up: addr + holdings; down:
                    # the ring-push peer map) — no extra RPC, no extra
                    # failure mode
                    resp = self._master.heartbeat(
                        msg.HeartbeatRequest(
                            worker_id=self._worker_id,
                            step=self._trainer.step if self._trainer else 0,
                            timestamp=time.time(),
                            replica=self._replicator.advertisement()
                            if self._replicator is not None
                            else {},
                            # RPC outcome totals ride the beat — the one
                            # RPC still flowing when reports stall
                            rpc=rpc_stats.snapshot(),
                            # step-anatomy phase totals ({} when off):
                            # the master mirrors them onto /metrics
                            phases=anatomy_snapshot(),
                            # device-prefetch staging totals ({} when
                            # off), mirrored the same way
                            prefetch=prefetch_snapshot(),
                            # memory-ledger snapshot ({} when off):
                            # non-monotone, merged last-writer-wins
                            memory=memory_mod.heartbeat_snapshot(),
                        )
                    )
                    if self._replicator is not None and resp is not None:
                        self._replicator.set_peers(resp.replica_peers)
                    if resp is not None:
                        self._note_master_boot(
                            getattr(resp, "boot_id", "")
                        )
                        profile_cmd = getattr(resp, "profile", None)
                        if profile_cmd:
                            # on-demand capture window (request_profile):
                            # replayed window ids are absorbed in arm()
                            apply_profile_command(
                                self._profiler,
                                profile_cmd,
                                telemetry_dir=telemetry_dir,
                                tag=f"p{self._process_id}",
                            )
                except Exception:  # noqa: BLE001 — master may be gone
                    pass
                tracer = self._tracing.get_tracer()
                if tracer is not None:
                    from elasticdl_tpu.telemetry.tracing import (
                        SPAN_HEARTBEAT,
                    )

                    tracer.record_span(
                        SPAN_HEARTBEAT, t0, time.monotonic(), sampled=True
                    )
                time.sleep(interval_secs)

        threading.Thread(target=beat, daemon=True).start()

    def _note_master_boot(self, boot_id: str):
        """Heartbeat-thread hook: a changed master boot id means the
        master restarted from its journal — re-home by presenting this
        process's generation and in-flight lease so the restarted
        dispatcher reconciles accounting (re-accept or requeue)."""
        if not boot_id:
            return
        previous = self._master_boot_id
        if previous is None or previous == boot_id:
            self._master_boot_id = boot_id
            return
        # NOTE: this deliberately diverges from the task-stream
        # Worker._note_master_boot — a lockstep process's generation is
        # fixed at spawn, so a fence rejection is terminal (no
        # adopt-and-retry) and the boot id advances even then.
        # _master_boot_id commits AFTER the handshake so any exception
        # (training thread racing _current_task_id, master flapping)
        # retries on the next beat instead of skipping re-home forever.
        try:
            task = self._current_task_id  # one read: the training
            # thread clears it concurrently
            leases = [task] if task is not None else []
            logger.warning(
                "Master restarted (boot %s -> %s); re-homing worker %d "
                "(generation %d, in-flight leases %s)",
                previous[:8],
                boot_id[:8],
                self._worker_id,
                self._cluster_version,
                leases,
            )
            resp = self._master.rehome_worker(
                msg.RehomeRequest(
                    worker_id=self._worker_id,
                    cluster_version=self._cluster_version,
                    pid=os.getpid(),
                    lease_ids=leases,
                )
            )
        except Exception:  # noqa: BLE001 — the next heartbeat's boot id
            # still differs from nothing new, but re-home retries ride
            # the normal beat cadence via the comparison below
            logger.exception("Re-home RPC failed; will retry")
            return
        self._master_boot_id = boot_id
        if resp is not None and not resp.accepted:
            # generation fence: this world is stale — exit like any
            # fenced worker (the step-stream pull confirms and ends us)
            logger.warning(
                "Re-home rejected: generation %d is fenced (master at %d)",
                self._cluster_version,
                resp.cluster_version,
            )

    def run(self, wait_sleep_secs: float = 1.0):
        self._stopped = False
        if hasattr(self._master, "heartbeat"):
            self._start_heartbeats()
        ok = False
        try:
            from elasticdl_tpu.telemetry.tracing import SPAN_GET_TASK

            seq = 0
            while True:
                t0 = time.monotonic()
                task = self._master.get_step_task(
                    msg.GetStepTaskRequest(
                        seq=seq,
                        worker_id=self._worker_id,
                        cluster_version=self._cluster_version,
                    )
                )
                tracer = self._tracing.get_tracer()
                if tracer is not None and task.shard_name:
                    # the lease RPC joins the task's trace (WAIT polls
                    # are not leases and record nothing)
                    tracer.record_span(
                        SPAN_GET_TASK,
                        t0,
                        time.monotonic(),
                        trace_ctx=task.trace,
                        task_id=task.task_id,
                        seq=seq,
                    )
                if task.is_wait:
                    time.sleep(wait_sleep_secs)
                    continue
                if not task.shard_name:
                    logger.info(
                        "Process %d: stream ended at seq %d",
                        self._process_id,
                        seq,
                    )
                    break
                seq += 1
                self._current_task_id = task.task_id
                try:
                    if task.type == int(TaskType.TRAINING):
                        self._train_task(task)
                    elif task.type == int(TaskType.EVALUATION):
                        self._eval_task(task)
                    elif task.type == int(TaskType.PREDICTION):
                        self._predict_task(task)
                    elif task.type == int(TaskType.SAVE_MODEL):
                        self._save_model_task(task)
                    else:
                        self._report_task_result(
                            task.task_id, f"unknown task type {task.type}"
                        )
                finally:
                    self._current_task_id = None
            self._dump_state_if_requested()
            ok = True
        finally:
            # a pending boundary mark must not survive the run loop (it
            # would attribute post-run idle time to a later dispatch in
            # the same process — tests and smokes share processes)
            from elasticdl_tpu.trainer.device_pipeline import (
                clear_boundary_mark,
            )

            clear_boundary_mark()
            try:
                # a job must not report complete with an unwritten (async)
                # checkpoint in flight — but a failed flush must not
                # REPLACE an exception already propagating from the body
                self._checkpointer.flush_on_unwind(clean_exit=ok)
            finally:
                # ...and neither outcome may leave the heartbeat
                # thread running (it polls self._stopped)
                self._profiler.stop()
                self._stopped = True
                self._tracing.flush()
                if self._replicator is not None:
                    self._replicator.close()
                if ok:
                    if self._replica_server is not None:
                        self._replica_server.stop(grace=0)
                    if self._replica_store is not None:
                        # clean exit: release the retained shard
                        # payloads from the ledger registry (the crash
                        # path keeps them — the linger exists so the
                        # master can still harvest this RAM)
                        self._replica_store.close()
                elif self._replica_server is not None or self._ha_mode():
                    # a lockstep crash means the world is about to
                    # re-form — LINGER rather than exit.  With
                    # replication on, the replica server stays up so the
                    # master can harvest this RAM's shards for the
                    # restoring generation.  With master HA on, the
                    # master may itself be MID-OUTAGE: gloo fails fast on
                    # CPU when a collective partner dies, and exiting now
                    # would beat the relaunched master to the fence — so
                    # stay until reform_world's SIGKILL (or the linger
                    # cap) ends the wait.  On TPU a survivor naturally
                    # hangs in the dead collective and gets both for
                    # free.
                    self._linger_for_harvest()

    _LINGER_ENV = "ELASTICDL_TPU_REPLICA_LINGER_SECS"

    def _ha_mode(self) -> bool:
        """Master HA is on for this job (the master exported the addr
        file the re-resolve hook reads)."""
        from elasticdl_tpu.master.journal import MASTER_ADDR_FILE_ENV

        return bool(os.environ.get(MASTER_ADDR_FILE_ENV, ""))

    def _linger_for_harvest(self):
        try:
            linger_secs = float(os.environ.get(self._LINGER_ENV, 300.0))
        except ValueError:
            linger_secs = 300.0
        if linger_secs <= 0:
            if self._replica_server is not None:
                self._replica_server.stop(grace=0)
            return
        logger.warning(
            "Process %d crashed (%s): lingering up to %.0fs so the "
            "(re-launched) master can fence this world%s",
            self._process_id,
            "replication on"
            if self._replica_server is not None
            else "master HA on",
            linger_secs,
            " and harvest replica shards"
            if self._replica_server is not None
            else "",
        )
        time.sleep(linger_secs)
        if self._replica_server is not None:
            self._replica_server.stop(grace=0)

    def _dump_state_if_requested(self):
        out_dir = os.environ.get(_DUMP_STATE_ENV, "")
        if not out_dir or self._trainer is None:
            return
        from elasticdl_tpu.trainer.state import state_to_checkpoint

        host_state = elastic.replicate_to_hosts(
            self._trainer.state, self._mesh
        )
        os.makedirs(out_dir, exist_ok=True)
        np.savez(
            os.path.join(out_dir, f"final_state_p{self._process_id}.npz"),
            **state_to_checkpoint(host_state),
        )

    @property
    def trainer(self):
        return self._trainer


def _batch_len(tree) -> int:
    leaves = jax.tree_util.tree_leaves(tree)
    return int(np.shape(leaves[0])[0]) if leaves else 0


