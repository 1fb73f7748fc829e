"""Single-process training/evaluation/prediction loop.

Reference: ``elasticdl/python/elasticdl/local_executor.py`` — the LOCAL
strategy executor: no master process, no RPC, but the same task-based data
traversal.  Deviations: where the reference mocks tasks with a namedtuple
(``_MockedTask``), we drive a real in-process :class:`TaskDispatcher`, so
the exact task lifecycle (epochs, SAVE_MODEL callback, counters) is
exercised even in local runs; and the compute plane is the same
:class:`SPMDTrainer` the distributed workers run — a jitted SPMD step over
a mesh of ALL local devices (a Local job on a v5e-8 host trains
data-parallel across its 8 chips), with the same sharding rules,
re-shardable periodic checkpoints, and async writes.
"""

from __future__ import annotations

import jax
import numpy as np

from elasticdl_tpu.data.dataset import Dataset
from elasticdl_tpu.data.factory import create_data_reader
from elasticdl_tpu.data.fast_pipeline import build_task_batches
from elasticdl_tpu.data.recordio import ensure_native_codec
from elasticdl_tpu.master.task_dispatcher import TaskDispatcher
from elasticdl_tpu.parallel.distributed import SPMDTrainer, trim_pad
from elasticdl_tpu.parallel.mesh import MeshConfig
from elasticdl_tpu.telemetry import router_load
from elasticdl_tpu.telemetry import worker_hooks as telemetry_hooks
from elasticdl_tpu.trainer import metrics as metrics_lib
from elasticdl_tpu.trainer.checkpointing import (
    PeriodicCheckpointer,
    restore_trainer_state,
)
from elasticdl_tpu.trainer.state import Modes, TrainState
from elasticdl_tpu.trainer.step import resolve_optimizer
from elasticdl_tpu.utils.log_utils import default_logger as logger
from elasticdl_tpu.utils.model_utils import get_model_spec
from elasticdl_tpu.utils.timing_utils import Timing


def build_optimizer(spec, learning_rate=None):
    """Resolve the optimizer, honoring ``learning_rate_scheduler``.

    The reference mutates ``optimizer.learning_rate`` per model version
    (``common/lr_scheduler.py``); optax expresses the same thing as a
    schedule callable of the step, which every optax factory accepts as its
    learning rate.
    """
    if learning_rate is None and spec.learning_rate_scheduler is not None:
        scheduler = spec.learning_rate_scheduler
        return resolve_optimizer(spec.optimizer, lambda step: scheduler(step))
    return resolve_optimizer(spec.optimizer, learning_rate)


class LocalExecutor:
    def __init__(self, args):
        self._args = args
        # build-or-fail: a checkout without _native.so must not train
        # through the Python codec in silence
        ensure_native_codec()
        self._spec = get_model_spec(
            args.model_zoo,
            args.model_def,
            model_params=args.model_params_dict,
            dataset_fn=args.dataset_fn,
            loss=args.loss,
            optimizer=args.optimizer,
            eval_metrics_fn=args.eval_metrics_fn,
        )
        self._model = self._spec.build_model()
        self._tx = build_optimizer(self._spec, args.learning_rate)
        reader_kwargs = dict(args.data_reader_params_dict)
        self._train_reader = (
            create_data_reader(
                args.training_data,
                records_per_task=args.records_per_task,
                custom_reader=self._spec.custom_data_reader,
                **reader_kwargs,
            )
            if args.training_data
            else None
        )
        self._eval_reader = (
            create_data_reader(
                args.validation_data,
                records_per_task=args.records_per_task,
                custom_reader=self._spec.custom_data_reader,
                **reader_kwargs,
            )
            if args.validation_data
            else None
        )
        self._predict_reader = (
            create_data_reader(
                args.prediction_data,
                records_per_task=args.records_per_task,
                custom_reader=self._spec.custom_data_reader,
                **reader_kwargs,
            )
            if args.prediction_data
            else None
        )
        if getattr(args, "jax_platform", ""):
            from elasticdl_tpu.parallel.elastic import configure_platform

            configure_platform(args.jax_platform)
        # all local devices; --mesh_shape picks the layout ('' = all on dp)
        self._mesh = MeshConfig.from_string(
            getattr(args, "mesh_shape", "") or ""
        ).create()
        self._trainer: SPMDTrainer | None = None
        # shape-canonical batching: every train/eval/predict batch is
        # padded to this fixed row count (mask-weighted), so each step
        # kind compiles exactly once — ragged tails reuse the program
        from elasticdl_tpu.parallel.mesh import batch_divisor
        from elasticdl_tpu.trainer.stacking import (
            canonical_batch_rows,
            warm_dispatch_overhead_async,
        )

        self._canonical_rows = canonical_batch_rows(
            args.minibatch_size, batch_divisor(self._mesh)
        )
        # device-path pipelining (--device_prefetch or the forwarded
        # env): resolved ONCE here; it selects the staged dispatch loop
        # and turns on batch-buffer donation in the trainer
        from elasticdl_tpu.trainer.device_pipeline import (
            resolve_boundary_fusion,
            resolve_device_prefetch,
            resolve_pipeline_depth,
        )

        self._device_prefetch = resolve_device_prefetch(
            getattr(args, "device_prefetch", None)
        )
        # cross-task staging (--boundary_fusion) and the tunable window
        # (--pipeline_depth): master-only, env-forwarded; defaults keep
        # the classic per-task drain at depth 2.  Fusion requires the
        # staged dispatch loop, so it is gated on device_prefetch.
        self._boundary_fusion = self._device_prefetch and resolve_boundary_fusion(
            getattr(args, "boundary_fusion", None)
        )
        self._pipeline_depth = resolve_pipeline_depth(
            getattr(args, "pipeline_depth", None)
        )
        if getattr(args, "steps_per_dispatch", 1) == "auto":
            # measure the per-dispatch overhead off the first dispatch's
            # critical path (the probe result feeds the auto-k sizing)
            warm_dispatch_overhead_async()
        self._checkpointer = PeriodicCheckpointer(
            getattr(args, "checkpoint_dir", "") or "",
            getattr(args, "checkpoint_steps", 0) or 0,
            keep_checkpoint_max=getattr(args, "keep_checkpoint_max", 3),
        )
        self._timing = Timing(
            enabled=args.log_level == "DEBUG", logger=logger
        )
        # per-step telemetry samples (events.jsonl for the report CLI);
        # --telemetry_dir or the inherited env enables it
        import os as _os

        from elasticdl_tpu.telemetry import tracing

        telemetry_dir = getattr(args, "telemetry_dir", "") or _os.environ.get(
            telemetry_hooks.TELEMETRY_DIR_ENV, ""
        )
        self._telemetry = telemetry_hooks.install(telemetry_dir)
        # process-wide compile counter (+ `compile` trace spans): the
        # observable face of the compile-once guarantee
        from elasticdl_tpu.telemetry import compile_tracker

        compile_tracker.install()
        # span tracer on the same run dir (sampled step spans, checkpoint
        # and profile-window spans) — the single-process path of the
        # distributed trace
        tracing.install(
            telemetry_dir,
            sample_rate=getattr(args, "trace_sample_rate", None),
        )
        self._tracing = tracing
        # per-dispatch phase anatomy (--step_anatomy or the forwarded
        # env): host_fetch/assemble/h2d/device_compute/bookkeeping
        # summing exactly to each dispatch's wall time — feeds the
        # report's goodput section and the goodput smoke
        from elasticdl_tpu.telemetry import anatomy as anatomy_mod

        self._anatomy_mod = anatomy_mod
        anatomy_mod.install_if_enabled(
            getattr(args, "step_anatomy", None),
            model_def=getattr(args, "model_def", "") or "",
        )
        # memory ledger (telemetry/memory.py): component byte accounting
        # sampled at task boundaries + phase edges; enabled exactly when
        # telemetry is (its surfaces all hang off the telemetry dir)
        from elasticdl_tpu.telemetry import memory as memory_mod

        self._memory_mod = memory_mod
        memory_mod.install_if_enabled(telemetry_dir)
        memory_mod.register_trainer_state(
            lambda: self._trainer.state if self._trainer is not None else None
        )
        self._last_eval_milestone = 0
        from elasticdl_tpu.utils.profiling import StepProfiler

        self._profiler = StepProfiler(
            getattr(args, "profile_dir", ""),
            num_steps=getattr(args, "profile_steps", 5),
        )

    # ---- plumbing ---------------------------------------------------------

    def _task_dataset(
        self, reader, task, mode: Modes, prefetch: int = 2
    ) -> Dataset:
        # prefetch=0 on the training path: TaskPrefetcher's producer
        # thread IS the overlap there; eval/predict (main-thread
        # consumers) keep the in-dataset prefetch.
        # stack_k: training batches arrive as ready-made PreStacked
        # dispatch groups (zero-copy reshapes built on the producer
        # thread) when --steps_per_dispatch > 1 — the per-batch group
        # assembly otherwise costs ~1-2ms x k on the consumer thread.
        from elasticdl_tpu.trainer.stacking import choose_stack_k

        stack_k = choose_stack_k(
            getattr(self._args, "steps_per_dispatch", 1),
            mode == Modes.TRAINING,
        )
        from elasticdl_tpu.parallel.mesh import batch_divisor

        return build_task_batches(
            reader,
            task,
            self._spec,
            mode,
            reader.metadata,
            self._args.minibatch_size,
            shuffle_records=mode == Modes.TRAINING,
            prefetch=prefetch,
            stack_k=stack_k,
            stack_divisor=batch_divisor(self._mesh),
        )

    def _ensure_trainer(self, sample_features):
        if self._trainer is not None:
            return
        from elasticdl_tpu.telemetry.tracing import (
            SPAN_TRAINER_BUILD,
            trace_span,
        )

        with trace_span(SPAN_TRAINER_BUILD):
            rules = ()
            if self._spec.sharding_rules is not None:
                rules = tuple(self._spec.sharding_rules(self._mesh))
            compute_dtype = getattr(self._args, "compute_dtype", "float32")
            from elasticdl_tpu.parallel import program_store
            from elasticdl_tpu.trainer.device_pipeline import (
                resolve_donate_state,
            )

            self._trainer = SPMDTrainer(
                self._mesh,
                self._model,
                self._spec.loss,
                self._tx,
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
            version = restore_trainer_state(self._trainer, self._args)
        if version is not None:
            self._checkpointer.note_restored_version(version)
            if self._args.evaluation_steps:
                # milestones evaluated before the restore point must not
                # re-fire on the first post-restore step (mirrors
                # note_restored_version for checkpoints)
                self._last_eval_milestone = (
                    version // self._args.evaluation_steps
                )

    def _place_canonical(self, tree):
        return self._trainer.place_canonical(tree, self._canonical_rows)

    @property
    def _version(self) -> int:
        return self._trainer.step if self._trainer is not None else 0

    # ---- phases -----------------------------------------------------------

    def _train_task(self, task, batches=None) -> int:
        """One implementation for every ``--steps_per_dispatch`` (k=1 is
        a group of one): the shared grouping policy in
        ``trainer.stacking.run_stacked_steps``.  Eval/checkpoint hooks
        run per dispatch group, so step-based triggers fire at dispatch
        granularity (D9a; identical to per-step at k=1).

        ``batches``: pre-built minibatch stream (the prefetching run
        loop passes one so host decode overlaps device compute); default
        builds the task's pipeline inline (retry paths, tests)."""
        from elasticdl_tpu.trainer.stacking import run_stacked_steps

        return run_stacked_steps(
            lambda: self._trainer,
            batches
            if batches is not None
            else self._task_dataset(self._train_reader, task, Modes.TRAINING),
            getattr(self._args, "steps_per_dispatch", 1) or 1,
            pre_batch=self._pre_batch,
            post_group=self._post_step_hooks,
            dispatch_ctx=lambda: self._timing.record("batch_process"),
            canonical_rows=self._canonical_rows,
            anatomy=self._anatomy_mod.get_recorder(),
            device_prefetch=self._device_prefetch,
            pipeline_depth=self._pipeline_depth,
        )

    def _pre_batch(self, features):
        from elasticdl_tpu.telemetry.tracing import record_step_span
        from elasticdl_tpu.telemetry.worker_hooks import record_step

        self._ensure_trainer(features)
        # the profiler counts CALLS, one per minibatch == one per
        # step; no version argument (the version only advances at
        # the dispatch, so it would repeat within a group — ADVICE
        # r3 finding 3)
        self._profiler.on_step()
        record_step(self._version, self._args.minibatch_size)
        record_step_span(self._version)

    def _post_step_hooks(self):
        # milestone-CROSSING, not exact-multiple: with steps_per_dispatch
        # the version advances k at a time, so an exact modulo check
        # would silently skip milestones (same rationale as the eval
        # service's add_evaluation_task_if_needed)
        if self._args.evaluation_steps:
            milestone = self._version // self._args.evaluation_steps
            if milestone > self._last_eval_milestone:
                self._last_eval_milestone = milestone
                self.evaluate(tag=f"step {self._version}")
        self._checkpointer.maybe_save(self._trainer, self._mesh)

    def evaluate(self, tag: str = "final") -> dict:
        if self._eval_reader is None or self._trainer is None:
            return {}
        eval_metrics = (
            self._spec.eval_metrics_fn()
            if self._spec.eval_metrics_fn
            else {"loss": metrics_lib.Mean()}
        )
        shards = self._eval_reader.create_shards()
        dispatcher = TaskDispatcher(
            None,
            evaluation_shards=shards,
            records_per_task=self._args.records_per_task,
        )
        loss_mean = metrics_lib.Mean()
        while True:
            tid, task = dispatcher.get_eval_task(0)
            if task is None:
                break
            for features, labels in self._task_dataset(
                self._eval_reader, task, Modes.EVALUATION
            ):
                n = _batch_size(labels)
                # mask-weighted in-step loss: exact over the REAL rows,
                # so no host-side loss recompute is needed — and the
                # canonical shape means the eval program compiles once
                outputs, loss = self._trainer.eval_step(
                    self._place_canonical(features),
                    self._place_canonical(labels),
                    self._trainer.place_mask(n, self._canonical_rows),
                )
                outputs = trim_pad(jax.device_get(outputs), n)
                metrics_lib.update_metric_tree(
                    eval_metrics, np.asarray(labels), outputs
                )
                loss_mean.update_value(float(jax.device_get(loss)), n)
            dispatcher.report(tid, True)
        results = metrics_lib.metric_tree_results(eval_metrics)
        results["loss"] = loss_mean.result()
        # the one place this runtime reads the loss back is where an expert
        # model's router load is read too (telemetry/router_load.py)
        load = router_load.read(self._trainer.state.model_state)
        if load is not None:
            results["router_load"] = load
            telemetry_hooks.emit_event("router_load", **load)
        parts = router_load.read_loss_parts(self._trainer.state.model_state)
        if parts is not None:
            results["train_loss_parts"] = parts
            telemetry_hooks.emit_event("train_loss_parts", **parts)
        logger.info("Evaluation (%s): %s", tag, results)
        return results

    def predict(self) -> list:
        if self._predict_reader is None:
            return []
        shards = self._predict_reader.create_shards()
        dispatcher = TaskDispatcher(
            None,
            prediction_shards=shards,
            records_per_task=self._args.records_per_task,
        )
        outputs_all = []
        while True:
            tid, task = dispatcher.get(0)
            if task is None:
                break
            for features in self._task_dataset(
                self._predict_reader, task, Modes.PREDICTION
            ):
                self._ensure_trainer(features)
                n = _batch_size(features)
                outputs = self._trainer.predict_step(
                    self._place_canonical(features)
                )
                processed = trim_pad(jax.device_get(outputs), n)
                if self._spec.prediction_outputs_processor is not None:
                    self._spec.prediction_outputs_processor.process(
                        processed, worker_id=0
                    )
                outputs_all.append(processed)
            dispatcher.report(tid, True)
        return outputs_all

    def run(self) -> dict:
        """Train (with periodic eval), then final eval; returns final
        metrics (reference local_executor.py:73-95)."""
        if self._train_reader is None:
            if self._eval_reader is not None:
                # evaluation-only job needs initialized state
                self._init_from_eval_data()
                return self.evaluate()
            self.predict()
            return {}
        shards = self._train_reader.create_shards()
        dispatcher = TaskDispatcher(
            shards,
            records_per_task=self._args.records_per_task,
            num_epochs=self._args.num_epochs,
            shuffle_seed=getattr(self._args, "shuffle_seed", None),
        )
        total = 0
        ok = False
        from elasticdl_tpu.trainer.host_pipeline import TaskPrefetcher

        # decode-ahead bounded to ~two dispatch groups of batches
        # ('auto' resolves per-batch inside run_stacked_steps; size the
        # buffer for the largest k auto can pick)
        k = getattr(self._args, "steps_per_dispatch", 1) or 1
        from elasticdl_tpu.trainer.stacking import MAX_AUTO_K

        k = MAX_AUTO_K if k == "auto" else int(k)
        prefetcher = TaskPrefetcher(
            lambda: dispatcher.get(0),
            lambda task: self._task_dataset(
                self._train_reader, task, Modes.TRAINING, prefetch=0
            ),
            max_buffered_batches=max(4, 2 * k),
        )
        from elasticdl_tpu.trainer.device_pipeline import (
            clear_boundary_mark,
            note_task_boundary,
        )

        try:
            if self._boundary_fusion:
                # cross-task staging (--boundary_fusion): one persistent
                # stager walks the whole task stream, and the per-task
                # bookkeeping below runs as the task_done callback after
                # each task's window drains (exactly-once preserved)
                from elasticdl_tpu.trainer.device_pipeline import (
                    run_pipelined_task_stream,
                )

                def _task_done(tid, task, records):
                    dispatcher.report(tid, True)
                    # task boundaries are the single-process run's
                    # periodic memory cadence (no heartbeat to ride)
                    self._memory_mod.sample()

                total = run_pipelined_task_stream(
                    lambda: self._trainer,
                    iter(prefetcher),
                    getattr(self._args, "steps_per_dispatch", 1) or 1,
                    pre_batch=self._pre_batch,
                    post_group=self._post_step_hooks,
                    dispatch_ctx=lambda: self._timing.record(
                        "batch_process"
                    ),
                    canonical_rows=self._canonical_rows,
                    anatomy=self._anatomy_mod.get_recorder(),
                    task_done=_task_done,
                    pipeline_depth=self._pipeline_depth,
                )
            else:
                for tid, task, batches in prefetcher:
                    with self._timing.record("task_process"):
                        total += self._train_task(task, batches)
                    # the training call drained its window: the device
                    # is idle from here until the next task's first
                    # dispatch — that whole gap (report + sample
                    # included) is the boundary_stall counter
                    note_task_boundary()
                    dispatcher.report(tid, True)
                    # task boundaries are the single-process run's
                    # periodic memory cadence (no heartbeat to ride)
                    self._memory_mod.sample()
            ok = True
        finally:
            # a pending mark must not leak into a later run in this
            # process (the smoke runs several windows back to back)
            clear_boundary_mark()
            prefetcher.close()
            try:
                # an in-flight async checkpoint (or a parked write error)
                # must not be abandoned by a mid-training exception — nor
                # may a failed flush replace that exception
                self._checkpointer.flush_on_unwind(clean_exit=ok)
            finally:
                # flush (or diagnose) the trace even on error — a leaked
                # active trace poisons later start_trace calls
                self._profiler.stop()
                self._tracing.flush()
        logger.info(
            "Training complete: %d records, %d steps", total, self._version
        )
        self._memory_mod.sample("job_end")
        from elasticdl_tpu.telemetry.worker_hooks import publish_timing

        publish_timing(self._timing)
        self._timing.report_timing(reset=True)
        if self._checkpointer.enabled and self._trainer is not None:
            self._checkpointer.save_now(
                self._trainer, self._mesh, skip_if_current=True
            )
            self._checkpointer.flush()
        results = self.evaluate()
        if self._args.output and self._trainer is not None:
            from elasticdl_tpu.utils.export_utils import export_model

            export_model(
                self._args.output,
                self._trainer.state,
                self._spec,
                self._args,
            )
        return results

    def _init_from_eval_data(self):
        shards = self._eval_reader.create_shards()
        dispatcher = TaskDispatcher(
            None,
            evaluation_shards=shards,
            records_per_task=self._args.records_per_task,
        )
        tid, task = dispatcher.get_eval_task(0)
        if task is None:
            return
        for features, _ in self._task_dataset(
            self._eval_reader, task, Modes.EVALUATION
        ):
            self._ensure_trainer(features)
            break

    @property
    def state(self) -> TrainState | None:
        return self._trainer.state if self._trainer is not None else None

    @property
    def trainer(self) -> SPMDTrainer | None:
        return self._trainer

    @property
    def mesh(self):
        return self._mesh


def _batch_size(tree) -> int:
    if isinstance(tree, dict):
        tree = next(iter(tree.values()))
    return int(np.shape(tree)[0]) if np.ndim(tree) else 1


