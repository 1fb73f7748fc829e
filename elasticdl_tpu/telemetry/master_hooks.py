"""Master-side telemetry: one object owning the registry + event log.

Wired by :class:`~elasticdl_tpu.master.master.Master` as a
``TaskDispatcher`` observer, a servicer version observer and the re-form
path's direct collaborator, so the elastic lifecycle is measured with NO
new plumbing through the hot loop — the observers the chaos checker
already rides (PR 1) are the same ones telemetry rides.

Registry refresh happens at scrape time via a collect callback (queue
depths, epoch, live workers, the workers' ``time_<bucket>_ms`` wall
clock buckets mirrored from the dispatcher's exec-counter sums), so the
run loop pays nothing for ``/metrics`` being up.
"""

from __future__ import annotations

import os
import time

from elasticdl_tpu.telemetry.events import (
    EVENT_JOB_END,
    EVENT_JOB_START,
    EVENT_REFORM_COMPLETE,
    EVENT_REFORM_LATENCY,
    EVENT_REFORM_START,
    EVENT_TASK_DISPATCH,
    EVENT_TASK_DONE,
    EVENT_TASK_RECOVERED,
    EVENT_WORKER_DEAD,
    EVENTS_FILENAME,
    EventLog,
)
from elasticdl_tpu.telemetry.registry import MetricsRegistry
from elasticdl_tpu.telemetry.tracing import (
    SPAN_REFORM,
    SPAN_TASK_LIFECYCLE,
    SPANS_FILENAME,
    SpanRecorder,
    gen_trace_id,
    sample_rate_from_env,
)

# family names referenced from more than one code path live here so each
# is REGISTERED at exactly one call site (scripts/check_telemetry_names.py)
_TASKS_DISPATCHED = "elasticdl_tasks_dispatched_total"
_TASKS_COMPLETED = "elasticdl_tasks_completed_total"
_WORKER_TIME_MS = "elasticdl_worker_time_ms_total"
_WORKER_HB_AGE = "elasticdl_worker_heartbeat_age_secs"
_MEMORY_BYTES = "elasticdl_memory_bytes"

# per-worker label-cardinality budget for /metrics: a fleet at or under
# this size exposes one heartbeat-age series per worker; above it the
# individual series collapse into aggregate children (worker="max" /
# worker="p50") so a 1000-worker scrape renders O(1) series for this
# family instead of O(world_size).  The env override exists for
# deployments whose scrape budget differs from the default.
WORKER_SERIES_MAX_ENV = "ELASTICDL_TPU_WORKER_SERIES_MAX"
DEFAULT_WORKER_SERIES_MAX = 64


def worker_series_budget() -> int:
    raw = os.environ.get(WORKER_SERIES_MAX_ENV, "")
    try:
        return int(raw) if raw else DEFAULT_WORKER_SERIES_MAX
    except ValueError:
        return DEFAULT_WORKER_SERIES_MAX


class MasterTelemetry:
    def __init__(
        self,
        telemetry_dir: str = "",
        registry=None,
        trace_sample_rate: float | None = None,
    ):
        self.registry = registry if registry is not None else MetricsRegistry()
        # async: master emits happen inside TaskDispatcher observer
        # callbacks (under the dispatcher lock) — the control plane must
        # never queue worker RPCs behind a disk write
        self.events = EventLog(
            os.path.join(telemetry_dir, EVENTS_FILENAME)
            if telemetry_dir
            else "",
            async_writes=True,
        )
        # span tracer: buffered in memory (the observer callbacks run
        # under the dispatcher lock, so spans batch to disk, never write
        # inline); path='' disables persistence but keeps the object
        # usable so the reform path never branches
        self.tracer = SpanRecorder(
            os.path.join(telemetry_dir, SPANS_FILENAME)
            if telemetry_dir
            else "",
            role="master",
            sample_rate=trace_sample_rate
            if trace_sample_rate is not None
            else sample_rate_from_env(),
        )
        # task_id -> open dispatch (root) span; id(task) -> the latest
        # root span's context so a RECOVERED task's new span links back
        # into the original trace (the re-queued Task object survives
        # the re-lease, so identity is stable while the task is alive)
        self._task_spans: dict[int, object] = {}
        self._task_trace_links: dict[int, dict] = {}
        r = self.registry

        def per_type(name, help_text):
            # pre-create the training child so every family is visible
            # on /metrics from the first scrape, before any task flows
            return r.counter(name, help_text, labels={"type": "training"})

        per_type(_TASKS_DISPATCHED, "Task leases handed to workers")
        per_type(_TASKS_COMPLETED, "Tasks reported successfully")
        self._tasks_recovered = r.counter(
            "elasticdl_tasks_recovered_total",
            "Tasks re-queued after failure, lease timeout or worker death",
        )
        self._records = r.counter(
            "elasticdl_records_processed_total",
            "Records covered by successfully completed tasks",
        )
        self._model_version = r.gauge(
            "elasticdl_model_version", "Highest model version reported"
        )
        self._generation = r.gauge(
            "elasticdl_cluster_generation",
            "World generation (bumped by every re-formation)",
        )
        self._workers_live = r.gauge(
            "elasticdl_workers_live", "Workers with a live heartbeat"
        )
        self._workers_dead = r.counter(
            "elasticdl_workers_dead_total",
            "Workers declared dead (heartbeat miss or process exit)",
        )
        self._reforms = r.counter(
            "elasticdl_reforms_total", "World re-formations"
        )
        self._reform_downtime = r.histogram(
            "elasticdl_reform_downtime_seconds",
            "Failure detection to first step-task pull of the new world",
        )
        self._tasks_pending = r.gauge(
            "elasticdl_tasks_pending", "Tasks queued, not leased"
        )
        self._tasks_active = r.gauge(
            "elasticdl_tasks_active", "Tasks currently leased"
        )
        self._epoch = r.gauge("elasticdl_epoch", "Current training epoch")
        # shape-canonical batching's regression gauge: XLA programs
        # compiled (this process + worker-reported exec-counter deltas);
        # steady state should be flat after warmup — see
        # telemetry/compile_tracker.py and scripts/compile_smoke.py
        # beside it, the program store's traffic
        # (parallel/program_store.py): a relaunched worker that hits
        # loads its step instead of tracing it
        from elasticdl_tpu.telemetry import compile_tracker

        self._exec_counters = {
            compile_tracker.COMPILE_COUNT_KEY: r.counter(
                "elasticdl_compile_total",
                "XLA backend compiles (master process + worker-reported)",
            ),
            compile_tracker.PROGRAM_STORE_HITS_KEY: r.counter(
                "elasticdl_program_store_hits_total",
                "Programs loaded from the program store",
            ),
            compile_tracker.PROGRAM_STORE_MISSES_KEY: r.counter(
                "elasticdl_program_store_misses_total",
                "Programs the program store had no entry for",
            ),
            compile_tracker.PROGRAM_STORE_REJECTS_KEY: r.counter(
                "elasticdl_program_store_rejects_total",
                "Program store entries found and refused",
            ),
        }
        # gray-failure RPC plane (rpc/stats.py ships the worker-side
        # totals by heartbeat; the dedup counters are master-observed)
        self._rpc_retries = r.counter(
            "elasticdl_rpc_retries_total",
            "Worker RPC backoff re-sends (heartbeat-shipped totals)",
        )
        self._rpc_deadline_exceeded = r.counter(
            "elasticdl_rpc_deadline_exceeded_total",
            "Worker RPC attempts that expired their deadline",
        )
        self._rpc_unavailable = r.counter(
            "elasticdl_rpc_unavailable_total",
            "Worker RPC attempts that failed UNAVAILABLE",
        )
        self._rpc_reports_deduped = r.counter(
            "elasticdl_rpc_reports_deduped_total",
            "Task reports dropped by task-id dedup (duplicate delivery "
            "or stale lease)",
        )
        self._rpc_eval_deduped = r.counter(
            "elasticdl_rpc_eval_reports_deduped_total",
            "Eval-metric reports dropped as duplicate deliveries of a "
            "still-active lease",
        )
        # per-method server-side handler latency; children created
        # lazily per observed method (one family, one registration site)
        self._rpc_latency_children: dict = {}
        from elasticdl_tpu.telemetry import compile_tracker

        compile_tracker.install()
        self._compile_tracker = compile_tracker
        # master-side memory ledger: samples at reform edges + scrape
        # time; its components (master journal buffers) fold into the
        # same elasticdl_memory_bytes family the heartbeat-fed worker
        # components land in.  Enabled exactly when telemetry is
        from elasticdl_tpu.telemetry import memory as memory_mod

        self._memory_mod = memory_mod
        memory_mod.install_if_enabled(telemetry_dir, emit=self.events.emit)

        self._task_d = None
        self._servicer = None
        self._tb_service = None
        self._tb_mirrored_version = -1
        self._reform_span = None
        # last (source, trained) watermark pair emitted, so an idle
        # stream's poll ticks do not flood the event log
        self._last_stream_emit: tuple | None = None
        # the SLO watchdog engine, when --slo_config armed one (set by
        # the master via set_slo_engine; None = plane off, and every
        # surface below skips it so behavior is byte-identical)
        self.slo_engine = None
        r.add_collect_callback(self._collect)

    # ---- wiring ------------------------------------------------------------

    def set_slo_engine(self, engine):
        """Hook the armed SLO engine into the scrape mirror and the
        /healthz ``slo`` block."""
        self.slo_engine = engine

    def attach(self, task_dispatcher, servicer, tb_service=None):
        self._task_d = task_dispatcher
        self._servicer = servicer
        self._tb_service = tb_service
        task_dispatcher.add_observer(self)
        servicer.add_version_observer(self.on_version_report)
        servicer.set_event_sink(self.events.emit)
        servicer.set_trace_provider(self.trace_for_task)
        # per-method handler latency rides the transport's server seam
        # (module-global observer: the latest attached master wins,
        # which is exactly the in-process-harness sequencing)
        from elasticdl_tpu.rpc import service as rpc_service

        rpc_service.set_server_rpc_observer(self.observe_rpc)

    def observe_rpc(self, method: str, seconds: float):
        """Server-seam hook: one handler execution of ``method``."""
        hist = self._rpc_latency_children.get(method)
        if hist is None:
            hist = self.registry.histogram(
                "elasticdl_rpc_latency_seconds",
                "Server-side RPC handler latency by method",
                labels={"method": method},
            )
            self._rpc_latency_children[method] = hist
        hist.observe(seconds)

    def trace_for_task(self, task_id: int) -> dict:
        """The dispatch span's trace context for an active lease — what
        the servicer stamps onto the TaskResponse."""
        span = self._task_spans.get(task_id)
        return span.context if span is not None else {}

    def _collect(self, _registry):
        """Scrape-time refresh of point-in-time values."""
        # this process's totals, plus what the workers shipped
        counted = {
            key: read()
            for key, read in self._compile_tracker.EXEC_COUNTERS.items()
        }
        if self._task_d is not None:
            snap = self._task_d.snapshot()
            self._tasks_pending.set(snap["pending"] + snap["pending_eval"])
            self._tasks_active.set(len(snap["active"]))
            self._epoch.set(snap["epoch"])
            from elasticdl_tpu.utils.constants import TaskType

            # workers ship compile deltas with EVERY report kind, so the
            # mirror sums the exec counters of all task types (keeping
            # the TRAINING snapshot for the time buckets below — one
            # dispatcher-lock copy per type per scrape)
            exec_metrics = {}
            for task_type in TaskType:
                snapshot = self._task_d.exec_metrics_snapshot(task_type)
                for key in counted:
                    counted[key] += snapshot.get(key, 0)
                if task_type == TaskType.TRAINING:
                    exec_metrics = snapshot
            for key, value in exec_metrics.items():
                if key.startswith("time_") and key.endswith("_ms"):
                    self.registry.counter(
                        _WORKER_TIME_MS,
                        "Worker wall-clock buckets (utils.timing_utils)",
                        labels={"bucket": key[len("time_") : -len("_ms")]},
                    ).set_total(value)
            # streaming (watermark-lease) backlog signal — the one
            # registration site of the elasticdl_stream_{lag,watermark}
            # gauges; absent entirely in epoch mode
            if getattr(self._task_d, "streaming", False):
                status = self._task_d.stream_status()
                if status is not None:
                    self.registry.gauge(
                        "elasticdl_stream_lag_records",
                        "Streaming backlog: source watermark minus "
                        "trained watermark, in records",
                    ).set(status["lag"])
                    for role in ("source", "trained"):
                        self.registry.gauge(
                            "elasticdl_stream_watermark",
                            "Stream watermarks by role (source=records "
                            "published, trained=gap-free trained prefix)",
                            labels={"role": role},
                        ).set(status[f"{role}_watermark"])
        # set_total is monotone (max), so a re-formed generation's fresh
        # per-process counters can never walk the exposed total backward
        for key, total in counted.items():
            self._exec_counters[key].set_total(total)
        if self._servicer is not None:
            self._workers_live.set(len(self._servicer.live_workers()))
            self._generation.set(self._servicer.cluster_version)
            # heartbeat-shipped worker RPC outcomes + the servicer's own
            # eval dedup drops (set_total: mirrored monotone aggregates)
            totals = getattr(
                self._servicer, "rpc_stats_totals", lambda: {}
            )()
            self._rpc_retries.set_total(totals.get("retries", 0))
            self._rpc_deadline_exceeded.set_total(
                totals.get("deadline_exceeded", 0)
            )
            self._rpc_unavailable.set_total(totals.get("unavailable", 0))
            self._rpc_eval_deduped.set_total(
                getattr(self._servicer, "duplicate_eval_drops", 0)
            )
            # step-anatomy phase totals (heartbeat-shipped,
            # telemetry/anatomy.py): a monotone ms counter AND a
            # mirrored log-bucket histogram per phase — the one
            # registration site of the elasticdl_step_phase_* families
            phase_totals = getattr(
                self._servicer, "phase_stats_totals", lambda: {}
            )()
            for phase, agg in phase_totals.items():
                self.registry.counter(
                    "elasticdl_step_phase_ms_total",
                    "Per-dispatch phase wall time by phase "
                    "(host_fetch/assemble/h2d_transfer/device_compute/"
                    "step_bookkeeping/untracked)",
                    labels={"phase": phase},
                ).set_total(agg.get("ms", 0.0))
                self.registry.histogram(
                    "elasticdl_step_phase_seconds",
                    "Per-dispatch phase duration distribution by phase",
                    labels={"phase": phase},
                ).set_totals(
                    agg.get("buckets", {}),
                    agg.get("ms", 0.0) / 1000.0,
                    agg.get("count", 0),
                )
            # device-prefetch staging totals (heartbeat-shipped,
            # trainer/device_pipeline.py): the one registration site of
            # the elasticdl_device_prefetch_* counters
            # heartbeat fan-in shape (coalesced drain batches) and the
            # incremental dead-worker sweep cost: the control-plane
            # scale counters the fleetsim budgets gate
            hb = getattr(self._servicer, "heartbeat_stats", lambda: {})()
            if hb:
                self.registry.counter(
                    "elasticdl_heartbeats_total",
                    "Heartbeats applied by the coalesced fan-in",
                ).set_total(hb.get("beats", 0))
                self.registry.counter(
                    "elasticdl_heartbeat_batches_total",
                    "Drain batches (one lock acquisition each)",
                ).set_total(hb.get("batches", 0))
                self.registry.gauge(
                    "elasticdl_heartbeat_batch_max",
                    "Largest heartbeat batch applied in one drain",
                ).set(hb.get("max_batch", 0))
            sweep = getattr(self._servicer, "sweep_stats", lambda: {})()
            if sweep:
                self.registry.counter(
                    "elasticdl_dead_worker_sweeps_total",
                    "Incremental dead-worker sweep invocations",
                ).set_total(sweep.get("count", 0))
                self.registry.counter(
                    "elasticdl_dead_worker_sweep_ms_total",
                    "Cumulative dead-worker sweep wall time",
                ).set_total(sweep.get("ms", 0.0))
                self.registry.gauge(
                    "elasticdl_dead_worker_sweep_max_ms",
                    "Slowest single dead-worker sweep",
                ).set(sweep.get("max_ms", 0.0))
            self._collect_worker_ages()
            self._collect_memory()
            prefetch_totals = getattr(
                self._servicer, "prefetch_stats_totals", lambda: {}
            )()
            if prefetch_totals:
                self.registry.counter(
                    "elasticdl_device_prefetch_groups_total",
                    "Dispatch groups staged onto device by the "
                    "prefetch thread",
                ).set_total(prefetch_totals.get("groups", 0))
                self.registry.counter(
                    "elasticdl_device_prefetch_stall_ms_total",
                    "Consumer-visible wait for a staged group (the "
                    "residual h2d stall after overlap)",
                ).set_total(prefetch_totals.get("stall_ms", 0))
                self.registry.counter(
                    "elasticdl_device_prefetch_stage_ms_total",
                    "Background staging time overlapped with device "
                    "compute",
                ).set_total(prefetch_totals.get("stage_ms", 0))
                self.registry.counter(
                    "elasticdl_boundary_stall_ms_total",
                    "Device-idle time between the last retire of one "
                    "task and the first dispatch of the next",
                ).set_total(prefetch_totals.get("boundary_stall_ms", 0))
        if self.slo_engine is not None:
            # scrape-time mirror of the watchdog's detector state onto
            # the elasticdl_slo_* families (registered inside the
            # engine — the one registration site of each)
            self.slo_engine.mirror_metrics(self.registry)

    def _collect_worker_ages(self):
        """Per-worker heartbeat-age series, cardinality-bounded.

        At or under the series budget every worker gets its own labeled
        gauge (the small-fleet debugging view); above it the family
        collapses to aggregate-above-threshold children — worker="max"
        and worker="p50" — so scrape cost for this family is O(1) at
        any world size.  Stale children (forgotten workers, or the
        whole individual set after crossing the budget) are pruned so
        the exposition never accumulates dead series."""
        ages = getattr(self._servicer, "heartbeat_ages", lambda: {})()
        if len(ages) <= worker_series_budget():
            series = {str(wid): age for wid, age in ages.items()}
        else:
            ordered = sorted(ages.values())
            series = {
                "max": ordered[-1],
                "p50": ordered[len(ordered) // 2],
            }
        self.registry.prune_children(
            _WORKER_HB_AGE, [{"worker": key} for key in series]
        )
        for key, value in series.items():
            self.registry.gauge(
                "elasticdl_worker_heartbeat_age_secs",
                "Seconds since each worker's last heartbeat (per-worker "
                "under the series budget, aggregate max/p50 above it)",
                labels={"worker": key},
            ).set(value)

    def _collect_memory(self):
        """Mirror the memory ledger onto ``elasticdl_memory_bytes
        {component=, kind=current|peak}``: the heartbeat-fed fleet
        aggregates (last-writer-wins currents, max-merged peaks) plus
        this process's own ledger components (master journal buffers).

        Cardinality-bounded like the per-worker age series: component
        names arrive over the wire (untrusted), so above the series
        budget the smallest components collapse into ``component=
        "other"`` and stale children are pruned."""
        totals = getattr(
            self._servicer, "memory_stats_totals", lambda: {}
        )()
        current = dict((totals or {}).get("current") or {})
        peak = dict((totals or {}).get("peak") or {})
        ledger = self._memory_mod.get_ledger()
        if ledger is not None:
            # sample at scrape time so the journal-buffer reading (and
            # master RSS) is fresh without any run-loop bookkeeping
            ledger.sample("scrape")
            own = ledger.snapshot()
            for key, value in own["current"].items():
                current[key] = current.get(key, 0) + value
            for key, value in own["peak"].items():
                peak[key] = peak.get(key, 0) + value
        if not current and not peak:
            return
        budget = worker_series_budget()

        def bounded(values: dict) -> dict:
            if len(values) <= budget:
                return dict(values)
            ordered = sorted(
                values.items(), key=lambda kv: (-kv[1], kv[0])
            )
            kept = dict(ordered[: budget - 1])
            # ADD into the collapse bucket (never assign): component
            # names arrive over the wire, so a real component that is
            # literally named "other" and ranked in the kept set must
            # not have its value overwritten by the tail aggregate
            kept["other"] = kept.get("other", 0) + sum(
                v for _k, v in ordered[budget - 1 :]
            )
            return kept

        current = bounded(current)
        peak = bounded(peak)
        keep = [
            {"component": name, "kind": "current"} for name in current
        ] + [{"component": name, "kind": "peak"} for name in peak]
        self.registry.prune_children(_MEMORY_BYTES, keep)
        for kind, values in (("current", current), ("peak", peak)):
            for name, value in values.items():
                # the literal (not _MEMORY_BYTES) is the telemetry-names
                # checker's registration site; it must match the
                # constant the prune call above targets
                self.registry.gauge(
                    "elasticdl_memory_bytes",
                    "Component-level memory ledger (host/HBM bytes by "
                    "registered owner; kind=current is last-writer-"
                    "wins across beats, kind=peak is the monotone "
                    "watermark)",
                    labels={"component": name, "kind": kind},
                ).set(value)

    def build_health_fn(self, job_type: str, instance_manager_fn=lambda: None):
        """The ``/healthz`` payload closure (also used directly by
        tests): generation, live workers, model version, quiesce."""
        servicer = self._servicer

        def health() -> dict:
            im = instance_manager_fn()
            live = (
                im.worker_ids()
                if im is not None
                else (servicer.live_workers() if servicer else [])
            )
            quiescing = bool(servicer and servicer.is_quiescing)
            # progress-vs-liveness split: a hung-but-alive job keeps
            # heartbeating (live_workers stays full) while
            # last_step_age_secs grows without bound; degraded_network
            # says whether PR-8's outage-class RPC counters moved
            # recently — together they tell "stuck" from "slow link"
            # from "progressing" without reading the event log
            step_age = (
                servicer.last_step_age_secs()
                if servicer is not None
                and hasattr(servicer, "last_step_age_secs")
                else None
            )
            # memory headroom: the master host's point-in-time RSS and
            # availability (telemetry/memory.py; None-safe off-Linux),
            # plus the fleet's tracked byte total when the servicer
            # carries heartbeat-fed ledger aggregates
            from elasticdl_tpu.telemetry.memory import (
                KEY_DEVICE_IN_USE,
                KEY_HOST_RSS,
                host_memory_health,
            )

            memory = host_memory_health()
            if servicer is not None and hasattr(
                servicer, "memory_stats_totals"
            ):
                totals = servicer.memory_stats_totals()
                # tracked COMPONENTS only: the wire map also carries the
                # host_rss/device pseudo-keys, and summing those in
                # would double-count each worker's entire RSS on top of
                # the components it contains
                memory["fleet_tracked_bytes"] = sum(
                    value
                    for key, value in (
                        totals.get("current") or {}
                    ).items()
                    if key not in (KEY_HOST_RSS, KEY_DEVICE_IN_USE)
                )
            payload = {
                "status": "quiescing" if quiescing else "ok",
                "job_type": job_type,
                "generation": servicer.cluster_version if servicer else 0,
                "model_version": (
                    servicer.get_model_version() if servicer else 0
                ),
                "live_workers": sorted(live),
                "num_live_workers": len(live),
                "quiescing": quiescing,
                "last_step_age_secs": round(step_age, 3)
                if step_age is not None
                else None,
                "degraded_network": bool(
                    servicer is not None
                    and hasattr(servicer, "network_degraded")
                    and servicer.network_degraded()
                ),
                "memory": memory,
            }
            # the slo block appears only when the watchdog is armed —
            # an unarmed master's payload stays byte-identical
            if self.slo_engine is not None:
                payload["slo"] = self.slo_engine.health_block()
            return payload

        return health

    # ---- TaskDispatcher observer -------------------------------------------

    def on_task_leased(self, task_id, worker_id, task):
        type_name = task.type.name.lower()
        self.registry.counter(
            _TASKS_DISPATCHED, labels={"type": type_name}
        ).inc()
        # one task = one trace.  First lease opens a fresh root trace; a
        # RE-lease (failure/timeout/worker-death recovery) opens a new
        # root span INSIDE the original trace, parented to the previous
        # attempt's span — the Dapper link that lets `trace analyze`
        # follow a task across a preemption.
        link = self._task_trace_links.get(id(task))
        span = self.tracer.start_span(
            SPAN_TASK_LIFECYCLE,
            trace_ctx=link
            if link is not None
            else {"trace_id": gen_trace_id(), "span_id": ""},
            task_id=task_id,
            worker_id=worker_id,
            type=type_name,
            shard=task.shard_name,
            recovered=link is not None,
        )
        self._task_spans[task_id] = span
        self._task_trace_links[id(task)] = span.context
        self.events.emit(
            EVENT_TASK_DISPATCH,
            task_id=task_id,
            worker_id=worker_id,
            type=type_name,
            shard=task.shard_name,
            records=task.num_records,
            trace_id=span.trace_id,
        )

    def on_task_done(
        self, task_id, task, worker_id, success, exec_counters=None
    ):
        type_name = task.type.name.lower()
        span = self._task_spans.pop(task_id, None)
        if span is not None:
            span.end(success=bool(success))
        if success:
            # the trace is complete: drop the link so the (freed) Task
            # object's identity can never alias a future task's trace
            self._task_trace_links.pop(id(task), None)
            self.registry.counter(
                _TASKS_COMPLETED, labels={"type": type_name}
            ).inc()
            self._records.inc(task.num_records)
            self.events.emit(
                EVENT_TASK_DONE,
                task_id=task_id,
                worker_id=worker_id,
                type=type_name,
                records=task.num_records,
                **{
                    k: v
                    for k, v in (exec_counters or {}).items()
                    if k.startswith("time_")
                },
            )
        else:
            self._tasks_recovered.inc()
            self.events.emit(
                EVENT_TASK_RECOVERED,
                task_id=task_id,
                worker_id=worker_id,
                type=type_name,
                records=task.num_records,
                reason="report_failed",
            )

    def on_task_reported(self, task_id, task, success, counted):
        """Every report outcome, counted or not: a ``counted=False``
        report is a drop by the dispatcher's task-id dedup — a
        duplicate delivery or a stale (reclaimed) lease — the counter
        the duplicate-safety contract is observable through."""
        if not counted:
            self._rpc_reports_deduped.inc()

    def on_task_reclaimed(self, task_id, task):
        span = self._task_spans.pop(task_id, None)
        if span is not None:
            span.end(success=False, reclaimed=True)
        self._tasks_recovered.inc()
        self.events.emit(
            EVENT_TASK_RECOVERED,
            task_id=task_id,
            type=task.type.name.lower(),
            records=task.num_records,
            reason="lease_timeout",
        )

    # ---- servicer / master lifecycle ---------------------------------------

    def on_version_report(self, worker_id, model_version):
        if model_version <= self._model_version.value:
            return
        self._model_version.set(model_version)
        if self._tb_service is not None and (
            model_version > self._tb_mirrored_version
        ):
            # registry scalars mirrored so TB (and metrics.jsonl) keeps
            # carrying the run's health timeline unchanged
            self._tb_mirrored_version = model_version
            self._tb_service.write_dict_to_summary(
                {
                    "telemetry/model_version": model_version,
                    "telemetry/workers_live": self._workers_live.value,
                    "telemetry/records_processed": self._records.value,
                    "telemetry/reforms": self._reforms.value,
                },
                model_version,
            )

    def job_start(self, job_type: str, num_workers: int):
        self.events.emit(
            EVENT_JOB_START, job_type=job_type, num_workers=num_workers
        )

    def job_end(self, rc: int):
        self.events.emit(EVENT_JOB_END, rc=rc)
        self.events.flush()
        self.tracer.flush()

    def worker_dead(self, worker_ids, generation: int):
        self._workers_dead.inc(len(worker_ids))
        for worker_id in worker_ids:
            self.events.emit(
                EVENT_WORKER_DEAD, worker_id=worker_id, generation=generation
            )

    def reform_start(self, generation, dead, reason, old_world_size):
        self._generation.set(generation)
        # phase-edge memory sample: a re-formation is where harvested
        # replica payloads and restore stages spike master RSS
        self._memory_mod.sample("reform")
        # every re-formation is one trace: the root span opens here, the
        # fence/relaunch child spans bracket the phases in
        # Master._reform_lockstep, and the relaunched workers' world_join
        # spans link in via the propagated context (reform_trace_context)
        self._reform_span = self.tracer.start_span(
            SPAN_REFORM,
            trace_ctx={"trace_id": gen_trace_id(), "span_id": ""},
            generation=generation,
            reason=reason,
            dead_workers=sorted(dead),
        )
        self.events.emit(
            EVENT_REFORM_START,
            generation=generation,
            dead_workers=sorted(dead),
            reason=reason,
            old_world_size=old_world_size,
            trace_id=self._reform_span.trace_id,
        )

    def reform_trace_context(self) -> dict:
        """The open re-formation's trace context ({} outside a reform)."""
        span = self._reform_span
        return span.context if span is not None else {}

    def reform_complete(self, generation, old_world_size, new_world_size):
        self._reforms.inc()
        self._memory_mod.sample("reform")
        span, self._reform_span = self._reform_span, None
        if span is not None:
            span.end(new_world_size=new_world_size)
        self.events.emit(
            EVENT_REFORM_COMPLETE,
            generation=generation,
            old_world_size=old_world_size,
            new_world_size=new_world_size,
        )

    def reform_failed(self, generation):
        """The relaunch gave up (reform budget exhausted): close the
        reform trace with the failure recorded."""
        span, self._reform_span = self._reform_span, None
        if span is not None:
            span.end(failed=True)
        self.tracer.flush()

    def master_restart(self, generation: int):
        """The master process is starting RESTORED from the control-plane
        journal (master high availability).  Emitted at restore START so
        the event's timestamp marks the end of the master-down phase in
        downtime attribution."""
        from elasticdl_tpu.telemetry.events import EVENT_MASTER_RESTART

        self.events.emit(EVENT_MASTER_RESTART, generation=generation)

    def journal_replay(
        self,
        generation: int,
        duration_secs: float,
        pending: int,
        active: int,
        epoch: int,
        stage_lost: bool = False,
    ):
        """Journal replay finished; ``duration_secs`` lets event-only
        consumers (telemetry.report) reconstruct the replay interval
        without reading the span log.  ``stage_lost`` marks a staged
        replica set that died with the previous master's RAM."""
        from elasticdl_tpu.telemetry.events import EVENT_JOURNAL_REPLAY

        self.events.emit(
            EVENT_JOURNAL_REPLAY,
            generation=generation,
            duration_secs=duration_secs,
            pending=pending,
            active=active,
            epoch=epoch,
            stage_lost=stage_lost,
        )

    def worker_rehome(
        self,
        worker_id: int,
        generation: int,
        kept: int,
        requeued: int,
        started_at: float,
    ):
        """One worker re-homed onto the restarted master (lease
        reconciliation outcome included)."""
        from elasticdl_tpu.telemetry.events import EVENT_WORKER_REHOME
        from elasticdl_tpu.telemetry.tracing import SPAN_WORKER_REHOME

        self.events.emit(
            EVENT_WORKER_REHOME,
            worker_id=worker_id,
            generation=generation,
            kept=kept,
            requeued=requeued,
        )
        self.tracer.record_span(
            SPAN_WORKER_REHOME,
            started_at,
            time.monotonic(),
            generation=generation,
            worker_id=worker_id,
            kept=kept,
            requeued=requeued,
        )

    def slice_loss(
        self,
        generation: int,
        lost_slices: list,
        dead_workers: list,
        old_slices: int,
        new_slices: int,
        parked: bool,
        started_at: float,
        trace_ctx: dict | None = None,
    ):
        """A whole slice's processes died (slice-granular reform): the
        span covers failure detection to the re-plan decision, inside
        the re-formation's trace."""
        from elasticdl_tpu.telemetry.events import EVENT_SLICE_LOSS
        from elasticdl_tpu.telemetry.tracing import SPAN_SLICE_LOSS

        self.events.emit(
            EVENT_SLICE_LOSS,
            generation=generation,
            lost_slices=list(lost_slices),
            dead_workers=list(dead_workers),
            old_slices=old_slices,
            new_slices=new_slices,
            parked=bool(parked),
        )
        self.tracer.record_span(
            SPAN_SLICE_LOSS,
            started_at,
            time.monotonic(),
            trace_ctx=trace_ctx,
            generation=generation,
            lost_slices=list(lost_slices),
            new_slices=new_slices,
            parked=bool(parked),
        )

    def mesh_resize(
        self,
        generation: int,
        old_world_size: int,
        new_world_size: int,
        old_slices: int,
        new_slices: int,
        dcn: dict | None,
        started_at: float,
        trace_ctx: dict | None = None,
    ):
        """The hybrid mesh was re-planned for a resized world (the dp
        axis grows/shrinks across the DCN slice dimension) — the span
        the multislice smoke gates on."""
        from elasticdl_tpu.telemetry.events import EVENT_MESH_RESIZE
        from elasticdl_tpu.telemetry.tracing import SPAN_MESH_RESIZE

        self.events.emit(
            EVENT_MESH_RESIZE,
            generation=generation,
            old_world_size=old_world_size,
            new_world_size=new_world_size,
            old_slices=old_slices,
            new_slices=new_slices,
            dcn=dict(dcn or {}),
        )
        self.tracer.record_span(
            SPAN_MESH_RESIZE,
            started_at,
            time.monotonic(),
            trace_ctx=trace_ctx,
            generation=generation,
            old_world_size=old_world_size,
            new_world_size=new_world_size,
            old_slices=old_slices,
            new_slices=new_slices,
        )
        self.tracer.flush()

    def autoscale_decision(
        self,
        generation: int,
        started_at: float,
        action: str,
        from_slices: int,
        to_slices: int,
        reason: str,
        p95_step_ms=None,
        backlog=None,
    ):
        """The autoscaler crossed an SLO and requested a resize."""
        from elasticdl_tpu.telemetry.events import EVENT_AUTOSCALE_DECISION
        from elasticdl_tpu.telemetry.tracing import SPAN_AUTOSCALE_DECISION

        self.events.emit(
            EVENT_AUTOSCALE_DECISION,
            generation=generation,
            action=action,
            from_slices=from_slices,
            to_slices=to_slices,
            reason=reason,
            p95_step_ms=p95_step_ms,
            backlog=backlog,
        )
        self.tracer.record_span(
            SPAN_AUTOSCALE_DECISION,
            started_at,
            time.monotonic(),
            generation=generation,
            action=action,
            from_slices=from_slices,
            to_slices=to_slices,
        )

    def stream_tick(self, status: dict):
        """Run-loop tick in watermark-lease mode: emit the watermark
        pair and the derived lag.  Deduped on the (source, trained)
        pair — a tick where neither watermark moved emits nothing, so
        an idle stream costs no event-log growth."""
        from elasticdl_tpu.telemetry.events import (
            EVENT_STREAM_LAG,
            EVENT_STREAM_WATERMARK,
        )

        key = (status["source_watermark"], status["trained_watermark"])
        if key == self._last_stream_emit:
            return
        self._last_stream_emit = key
        self.events.emit(
            EVENT_STREAM_WATERMARK,
            source_watermark=status["source_watermark"],
            trained_watermark=status["trained_watermark"],
            next_offset=status["next_offset"],
            closed=bool(status["closed"]),
        )
        self.events.emit(
            EVENT_STREAM_LAG,
            lag_records=status["lag"],
            source_watermark=status["source_watermark"],
            trained_watermark=status["trained_watermark"],
        )

    def live_push(
        self,
        *,
        model_version: int,
        trained_watermark: int,
        source_watermark: int,
        accepted: bool,
        replica: str,
        swap_ms: float,
        started_at: float,
        reason: str = "",
    ):
        """One live train->serve push: the freshness ledger's row.
        ``staleness`` is records the served model is behind the source
        at the moment of the swap."""
        from elasticdl_tpu.telemetry.events import EVENT_LIVE_PUSH
        from elasticdl_tpu.telemetry.tracing import SPAN_LIVE_PUSH

        self.registry.counter(
            "elasticdl_stream_live_push_total",
            "Live train->serve pushes (replica-ring commit fanned into "
            "serving swap_state_dicts); accepted= marks the stale-"
            "refused ones",
            labels={"accepted": "true" if accepted else "false"},
        ).inc()
        self.events.emit(
            EVENT_LIVE_PUSH,
            model_version=model_version,
            trained_watermark=trained_watermark,
            source_watermark=source_watermark,
            staleness=max(0, source_watermark - trained_watermark),
            accepted=bool(accepted),
            replica=replica,
            swap_ms=swap_ms,
            reason=reason,
        )
        self.tracer.record_span(
            SPAN_LIVE_PUSH,
            started_at,
            time.monotonic(),
            model_version=model_version,
            trained_watermark=trained_watermark,
            accepted=bool(accepted),
            replica=replica,
        )

    def replica_harvest(
        self, generation, complete: bool, version, sources: int
    ):
        """Reform-time replica harvest outcome (replication subsystem):
        ``complete=False`` means the new generation falls back to disk."""
        from elasticdl_tpu.telemetry.events import EVENT_REPLICA_HARVEST

        self.events.emit(
            EVENT_REPLICA_HARVEST,
            generation=generation,
            complete=bool(complete),
            version=version,
            sources=sources,
        )

    def reform_latency(self, generation, latency_secs: float):
        self._reform_downtime.observe(latency_secs)
        self.events.emit(
            EVENT_REFORM_LATENCY,
            generation=generation,
            latency_secs=latency_secs,
        )
        # the reform trace is complete once latency resolves: make the
        # phase spans durable even if the job later dies uncleanly
        self.tracer.flush()
