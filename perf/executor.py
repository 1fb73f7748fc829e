"""The system under test, driven as ``elasticdl_tpu train
--distribution_strategy Local`` drives it: the normal argument parser and
the same ``LocalExecutor``, subclassed only to mark task boundaries.

The probe closes every interval with a host readback of ``state.step``,
which data-depends on every dispatched optimizer step: each counted
record's update exists on the device when its interval is timed (a
window closed by a readback of the step counter, applied per interval)."""

from __future__ import annotations

import contextlib
import functools
import math
import time

import jax
import jax.numpy as jnp

# the scope ``SPMDTrainer`` enters around every step call, whatever the model
from elasticdl_tpu.ops.attention import attention_mesh_scope
from elasticdl_tpu.telemetry import compile_tracker
from elasticdl_tpu.trainer import local_executor

from perf.trace_reduce import SPAN_INTERVAL

# what the dispatching thread is doing, on the host's monotonic clock
# (``time.perf_counter_ns``): recorded by the harness itself during the
# traced intervals and put on the trace's clock afterwards
# (``trace_reduce.align_host_spans``).  Not ``TraceAnnotation``s: those
# need the profiler's host tracer, which made the ResNet cell's traced
# rate a third of its untraced one (PERF.md, Findings PR 23)
SPAN_INPUT_WAIT = "perf:input_wait"
SPAN_DISPATCH = "perf:dispatch"
SPAN_READBACK = "perf:readback"

# the traced process first measures untraced, by the reading rule, for half
# of --seconds and at least this many readings
TRACED_RUN_UNTRACED_READINGS = 5


class WindowClosed(Exception):
    """Raised into ``LocalExecutor.run`` at the first task boundary after
    the window: its ``finally`` closes the prefetcher and flushes."""


def build_argv(cell, counts: dict, seed: int, platform: str) -> list[str]:
    run = cell.config["run"]
    params = ";".join(f"{k}={v}" for k, v in run["model_params"].items())
    argv = [
        "--model_def", run["model_def"],
        "--model_params", params,
        "--minibatch_size", str(counts["minibatch_size"]),
        "--records_per_task", str(counts["records_per_task"]),
        # the job loops over its shards; the probe ends it
        "--num_epochs", "100000",
        "--shuffle_seed", str(seed % (2**31)),
        "--distribution_strategy", "Local",
        "--jax_platform", platform,
    ]
    if counts.get("data_dir"):
        argv += ["--training_data", counts["data_dir"]]
    if cell.chips > 1:
        argv += ["--mesh_shape", f"dp={cell.chips}"]
    return argv + list(run["train_args"]) + list(cell.traffic["train_args"])


class Probe:
    """Marks task boundaries, takes the readings, and owns the trace."""

    def __init__(self, cell, counts, seconds, trace_dir=None):
        self.seconds = float(seconds)
        self.trace_dir = trace_dir
        traffic = cell.traffic
        self.tasks_per_interval = int(traffic["tasks_per_interval"])
        self.warmup_tasks = int(traffic["warmup_tasks"])
        self.fill_intervals = max(3, int(traffic["fill_intervals"]))
        steps_per_interval = (
            int(traffic["steps_per_task"]) * self.tasks_per_interval
        )
        self.trace_intervals = max(
            4, math.ceil(int(traffic["trace_min_steps"]) / steps_per_interval)
        )
        self.phase = "warmup"
        self.trainer = None
        self.steps = 0
        self.first_loss = None
        self.last_loss = None
        self.tasks_seen = 0
        self.records_seen = 0
        self.readings: list[tuple[int, float]] = []
        self.traced_readings: list[tuple[int, float]] = []
        self.window_start = None
        self.warmup_end = None
        self.compiles_at_window_start = None
        self.trace_started = False
        # the dispatching thread's time, for the untraced and the traced
        # intervals apart
        self.host = {"measure": _HostTimes(), "trace": _HostTimes()}
        self._interval_tasks = 0
        self._interval_records = 0
        self._interval_start = None
        self._dropped = 0
        # [name, start_ns, duration_ns] on perf_counter_ns, traced phase only
        self.host_spans: list[list] = []

    # ---- hooks the executor calls ---------------------------------------

    def attach(self, trainer):
        """Count optimizer steps and keep the first and the latest loss (as
        device arrays: nothing is read until an interval closes)."""
        self.trainer = trainer
        probe = self

        def counted(original, steps_of):
            @functools.wraps(original)
            def call(*args, **kwargs):
                metrics = original(*args, **kwargs)
                probe.steps += steps_of(args)
                if probe.first_loss is None:
                    probe.first_loss = metrics["loss"]
                probe.last_loss = metrics["loss"]
                return metrics

            return call

        trainer.train_step = counted(trainer.train_step, lambda args: 1)
        trainer.train_steps_stacked = counted(
            trainer.train_steps_stacked,
            lambda args: jax.tree_util.tree_leaves(args[0])[0].shape[0],
        )

    def before_task(self):
        if self.phase == "done":
            raise WindowClosed

    def watch(self, batches):
        """The batch iterator handed to ``_train_task``, timed: time inside
        ``next()`` is the dispatching thread waiting for input, time
        between two ``next()`` calls is assemble + place + enqueue."""
        if batches is None:
            return None
        return self._watch(iter(batches))

    def _watch(self, iterator):
        while True:
            t0 = time.perf_counter_ns()
            try:
                item = next(iterator)
            except StopIteration:
                return
            t1 = time.perf_counter_ns()
            yield item
            self.note_dispatch(t1, time.perf_counter_ns(), waited_from=t0)

    def note_dispatch(self, start_ns: int, end_ns: int, waited_from=None):
        """One dispatch's host time on the dispatching thread, and the wait
        for its input before it where there was one."""
        times = self.host.get(self.phase)
        if times is not None:
            times.dispatch_s += (end_ns - start_ns) / 1e9
            times.batches += 1
            if waited_from is not None:
                times.input_wait_s += (start_ns - waited_from) / 1e9
        if self.phase == "trace":
            if waited_from is not None:
                self.host_spans.append(
                    [SPAN_INPUT_WAIT, waited_from, start_ns - waited_from]
                )
            self.host_spans.append([SPAN_DISPATCH, start_ns, end_ns - start_ns])

    def after_task(self, records: int):
        self.tasks_seen += 1
        self.records_seen += records
        self._interval_tasks += 1
        self._interval_records += records
        if self.phase == "warmup":
            if self.tasks_seen < self.warmup_tasks:
                return
        elif self._interval_tasks < self.tasks_per_interval:
            return
        t0 = time.perf_counter()
        step_on_device = int(jax.device_get(self.trainer.state.step))
        now = time.perf_counter()
        if step_on_device != self.steps:
            raise RuntimeError(
                f"state.step on the device is {step_on_device} after "
                f"{self.steps} dispatched steps"
            )
        self._close_interval(now, now - t0)

    # ---- the phase machine ------------------------------------------------

    def _close_interval(self, now: float, readback_s: float):
        records, self._interval_records = self._interval_records, 0
        self._interval_tasks = 0
        start, self._interval_start = self._interval_start, now
        if self.phase == "warmup":
            self.phase = "fill"
            self.warmup_end = now
        elif self.phase == "fill":
            self._dropped += 1
            if self._dropped >= self.fill_intervals:
                self.phase = "measure"
                self.window_start = now
                self.compiles_at_window_start = compile_tracker.compile_count()
                self.host["measure"].steps_before = self.steps
        elif self.phase == "measure":
            self.readings.append((records, now - start))
            self._account(self.host["measure"], now - start, readback_s)
            if self.trace_dir is None:
                if now - self.window_start >= self.seconds:
                    self.phase = "done"
            elif (
                len(self.readings) >= TRACED_RUN_UNTRACED_READINGS
                and now - self.window_start >= self.seconds / 2
            ):
                self._start_trace()
                self.phase = "trace"
                # start_trace takes seconds: the interval starts after it
                self._interval_start = time.perf_counter()
        elif self.phase == "trace":
            self.traced_readings.append((records, now - start))
            self._account(self.host["trace"], now - start, readback_s)
            for name, t0, t1 in (
                (SPAN_READBACK, now - readback_s, now),
                (SPAN_INTERVAL, start, now),
            ):
                self.host_spans.append(
                    [name, int(t0 * 1e9), int((t1 - t0) * 1e9)]
                )
            if len(self.traced_readings) >= self.trace_intervals:
                jax.profiler.stop_trace()
                self.phase = "done"

    def _account(self, times, wall_s, readback_s):
        times.wall_s += wall_s
        times.readback_s += readback_s
        times.steps = self.steps - times.steps_before
        times.intervals += 1

    def _start_trace(self):
        options = jax.profiler.ProfileOptions()
        # the device planes are all the trace is read for: no Python
        # tracer, and no host tracer either (at any level above 0 the
        # transfer threads' events hold every 19 MB batch back)
        options.python_tracer_level = 0
        options.host_tracer_level = 0
        jax.profiler.start_trace(self.trace_dir, profiler_options=options)
        self.trace_started = True
        self.host["trace"].steps_before = self.steps

    def abort_trace(self):
        """A trace left running poisons the process's exit."""
        if self.trace_started and self.phase != "done":
            with contextlib.suppress(Exception):
                jax.profiler.stop_trace()


class _HostTimes:
    def __init__(self):
        self.input_wait_s = 0.0
        self.dispatch_s = 0.0
        self.readback_s = 0.0
        self.wall_s = 0.0
        self.batches = 0
        self.steps = 0
        self.steps_before = 0
        self.intervals = 0

    def as_dict(self) -> dict:
        return dict(vars(self))


class MeasuredExecutor(local_executor.LocalExecutor):
    def __init__(self, args, probe: Probe):
        self._probe = probe
        super().__init__(args)

    def _ensure_trainer(self, sample_features):
        # the weights are the trainer's own seeded init, the same for every
        # --seed: SPMDTrainer bakes rng_seed into its init program, so a
        # seed of the run's own would compile a new program in every run
        # (21 s in the ResNet cell; PERF.md, Findings PR 23)
        if self._trainer is None:
            super()._ensure_trainer(sample_features)
            self._probe.attach(self._trainer)

    def _train_task(self, task, batches=None) -> int:
        self._probe.before_task()
        records = super()._train_task(task, self._probe.watch(batches))
        self._probe.after_task(records)
        return records

    def release_optimizer_state(self):
        """Frees the optimizer's moments on the device, two thirds of an
        Adam state.  For after the window alone: no step can follow.  The
        comparison with the plain reference reads the parameters and the
        model's buffers, and a reference's float32 temporaries beside two
        gradient trees do not fit next to a whole state (PERF.md)."""
        for leaf in jax.tree_util.tree_leaves(self._trainer.state.opt_state):
            if isinstance(leaf, jax.Array):
                leaf.delete()

    def model_loss_and_grads(self, features, labels):
        """``(params, model_state, loss, grads)`` of the model this executor
        trains, at the trainer's parameters as they stand, on one unweighted
        batch; ``model_state`` is the collections outside ``params`` as the
        window left them (a router's selection bias sits there).  The
        model's ``apply`` in its configured dtype through its normal kernels
        under the trainer's mesh, in training mode (BatchNorm on the batch's
        own statistics), the model module's ``loss`` plus any sown losses, as
        ``trainer/step.py::forward_loss`` composes them, under
        ``jax.value_and_grad``.  What ``perf/reference.py`` compares with the
        plain reference; no step is taken and the state is left alone."""
        spec, trainer = self._spec, self._trainer
        state = trainer.state

        def loss_of(params, model_state, features, labels):
            if spec.device_parse is not None:
                features = spec.device_parse(features)
            variables = {"params": params, **model_state}
            rngs = {"dropout": jax.random.PRNGKey(0)}
            sown = {}
            if model_state:
                outputs, sown = state.apply_fn(
                    variables, features, training=True,
                    mutable=list(model_state), rngs=rngs,
                )
            else:
                outputs = state.apply_fn(
                    variables, features, training=True, rngs=rngs
                )
            loss = spec.loss(labels, outputs)
            for leaf in jax.tree_util.tree_leaves(sown.get("losses", {})):
                loss = loss + jnp.sum(leaf)
            return loss.astype(jnp.float32)

        with trainer.mesh, attention_mesh_scope(trainer.mesh):
            loss, grads = jax.jit(jax.value_and_grad(loss_of))(
                state.params, state.model_state,
                trainer.place_batch(features), trainer.place_batch(labels),
            )
        return state.params, state.model_state, loss, grads
