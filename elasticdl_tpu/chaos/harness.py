"""The chaos harness: run a real model-zoo job under a fault plan with
the invariant checker attached; return a JSON-able report.

This is the one shared implementation behind the chaos runner CLI,
``benchmarks/reform_bench.py`` and
``benchmarks/preemption_accuracy_bench.py``: a 2-process lockstep mnist
job on the host CPU backend, faults injected from the plan (worker-side
via the env-exported plan file, master-side via the capacity driver),
and the elastic contract checked end to end.

Clock note: workers log fault firings with ``time.monotonic()``;
CLOCK_MONOTONIC is machine-wide on Linux, so the master-side metrics
(detection latency, kill-to-step) subtract worker event times from the
master's own monotonic readings directly — valid because chaos jobs are
single-host by construction.
"""

from __future__ import annotations

import json
import os
import threading
import time
from dataclasses import dataclass, field

from elasticdl_tpu.chaos import hooks as chaos_hooks
from elasticdl_tpu.chaos.invariants import InvariantChecker
from elasticdl_tpu.chaos.plan import FaultKind, FaultPlan
from elasticdl_tpu.utils.log_utils import default_logger as logger

# fault kinds whose firing is "the preemption" for latency metrics —
# including the network kinds that end in an eviction: a blackholed or
# one-way-partitioned worker exhausts its retry budget, dies, and the
# reform it causes is the fault's downtime (delay and duplicate kinds
# are excluded: they must NOT cost a re-formation)
_KILL_KINDS = frozenset(
    {
        FaultKind.PREEMPT,
        FaultKind.KILL_COORDINATOR,
        FaultKind.KILL_IN_CHECKPOINT,
        FaultKind.KILL_DURING_REPLICATION,
        FaultKind.DROP_HEARTBEAT,
        FaultKind.SLICE_LOSS,
        FaultKind.NET_BLACKHOLE,
        FaultKind.NET_PARTITION,
    }
)

# kill kinds a COMPLETE replica set must survive: the victim's shard
# lives on its ring neighbor, so the resumed generation must restore at
# the last replicated step (kill_during_replication deliberately leaves
# coverage incomplete and is therefore excluded).  SLICE_LOSS qualifies
# BECAUSE the ring is slice-aware: every dead process's shard lives on
# a surviving slice — exactly what --corrupt same_slice_ring breaks.
_REPLICA_RECOVERABLE_KINDS = frozenset(
    {FaultKind.PREEMPT, FaultKind.KILL_COORDINATOR, FaultKind.SLICE_LOSS}
)

# deliberate-corruption modes: prove the checker catches what it claims
# to catch (a checker that cannot fail is not a checker).
# ``journal_rollback`` forges a DECREASING generation-record pair into
# the control-plane journal between master lives — the master_recovery
# invariant must flag the fence rollback (replay's monotone guard keeps
# the run itself alive, so the trip is the checker's, not the job's).
# ``same_slice_ring`` forces the slice-BLIND (i+1)%n replica ring onto a
# multi-slice world (worker-side, via env): a slice loss then takes a
# shard and its only replica together — cross_slice_replica_coverage
# must flag the same-slice pushes and the restore degrades to disk.
# ``drop_dedup`` disables the dispatcher's task-id dedup, so a netem-
# duplicated report counts TWICE — exactly_once and
# duplicate_delivery_exactly_once must both trip (requires a plan with
# net_duplicate faults, e.g. dup_report_storm).
# ``drop_shard_parts`` strips the sharded table rows from every replica
# push blob (worker-side, via env) while the push event still reports
# the state HAS sharded rows — the shape of "a shard's only replica
# died" — so the sharded extension of cross_slice_replica_coverage must
# trip (requires replication and a model with row-sharded tables).
# ``drop_stream_window`` (streaming runs only) vanishes one leased
# stream window from the dispatcher's active set and marks it already-
# reported, so neither timeout reclaim nor worker recovery ever
# requeues it — the trained watermark stalls at the hole and the
# bounded_lag invariant's final-drain clause must trip (the run itself
# still terminates: ``finished()`` gates on mint-drain, not on
# trained == watermark).
CORRUPTIONS = (
    "",
    "double_report",
    "lose_task",
    "version_rollback",
    "journal_rollback",
    "same_slice_ring",
    "drop_dedup",
    "drop_shard_parts",
    "drop_stream_window",
)

# model-zoo presets the harness can run: model_def + the synthetic
# dataset generator that feeds it (the chaos jobs are real model-zoo
# jobs, and the sharded-embedding smoke needs the recommender model,
# not mnist)
DATASETS = ("mnist", "frappe")


@dataclass
class ChaosJobConfig:
    plan: FaultPlan
    workdir: str
    # which model-zoo job the faults hit: any model_def the master can
    # resolve, paired with the synthetic dataset that feeds it
    model_def: str = "mnist_functional_api.mnist_functional_api.custom_model"
    dataset: str = "mnist"  # one of DATASETS
    num_records: int = 512
    num_epochs: int = 2
    num_workers: int = 2
    minibatch_size: int = 32
    records_per_task: int = 64
    checkpoint_steps: int = 2
    heartbeat_timeout_secs: float = 3.0
    data_seed: int = 3
    shuffle_seed: int = 5
    # restore the final checkpoint and score a held-out split
    evaluate: bool = False
    eval_records: int = 512
    eval_seed: int = 9
    corrupt: str = ""  # one of CORRUPTIONS
    run_timeout_secs: float = 600.0
    extra_master_args: list = field(default_factory=list)
    # peer state replication: ring-push state into surviving hosts' RAM
    # so the re-formed world hot-restores without a disk read
    replication: bool = False
    replication_steps: int = 0  # 0 = every task boundary
    # master high availability: journal the control plane so MASTER_KILL
    # faults can relaunch the master from it (workers re-home instead of
    # dying with it).  Standbys are disabled in HA runs: a killed
    # master's warm pool would outlive it as orphans the relaunched
    # master cannot drain.
    master_ha: bool = False
    rehome_grace_secs: float = 5.0
    # slice-granular elasticity: split the worker fleet into this many
    # forced TPU slices (hybrid ICI/DCN mesh on the CPU backend via the
    # canonical process->slice map); 1 = classic single-slice reform
    num_slices: int = 1
    # start the job on fewer slices than the fleet (grow_under_load:
    # a capacity grant then grows the world mid-training)
    initial_slices: int | None = None
    # network-chaos knobs (netem plans): per-method RPC deadlines so a
    # blackhole degrades to DEADLINE_EXCEEDED, a retry budget so the
    # worker survives transient windows (and dies — evictably — on
    # permanent ones), and a task lease timeout so an unreachable
    # worker's lease is reclaimed.  None = flags absent, byte-identical
    rpc_deadline_secs: float | None = None
    rpc_retry_secs: float | None = None
    task_timeout_secs: float | None = None
    # streaming (watermark-lease) mode: train over a stream:// origin
    # instead of generated recordio shards — no epochs, no checkpoints
    # (the replica ring is the only durability, so streaming runs want
    # replication=True); record accounting gates on the stream total
    # and the bounded_lag invariant replaces epoch parity
    streaming: bool = False
    stream_total: int = 0  # records the bounded-prefix source publishes
    stream_rate: float = 0.0  # watermark advance in records/sec
    stream_initial: int = 0  # records already published at t0
    # bounded_lag threshold in RECORDS; 0 = auto (6 windows, floored at
    # 256 — roomy enough for a reform outage at the smoke's rates, tight
    # enough that a stalled stream trips it)
    stream_lag_limit: int = 0
    # live train->serve push target ("host:port" of a serving frontend
    # or replica); "" = no live push.  The streaming smoke points this
    # at a real serving CLI and hammers it with traffic during the run
    live_push_addr: str = ""


def _master_args(config: ChaosJobConfig, train_dir: str, ckpt_dir: str):
    from elasticdl_tpu.utils.args import parse_master_args

    envs = [
        "JAX_PLATFORMS=cpu",
        "XLA_FLAGS= ",
        f"{chaos_hooks.PLAN_ENV}={os.path.join(config.workdir, 'chaos_plan.json')}",
        f"{chaos_hooks.EVENTS_ENV}={os.path.join(config.workdir, 'chaos_events.jsonl')}",
    ]
    if config.corrupt == "same_slice_ring":
        from elasticdl_tpu.replication.replicator import SAME_SLICE_RING_ENV

        envs.append(f"{SAME_SLICE_RING_ENV}=1")
    if config.corrupt == "drop_shard_parts":
        from elasticdl_tpu.replication.replicator import DROP_SHARD_PARTS_ENV

        envs.append(f"{DROP_SHARD_PARTS_ENV}=1")
    return parse_master_args(
        [
            "--model_def",
            config.model_def,
            "--training_data",
            train_dir,
            "--minibatch_size",
            str(config.minibatch_size),
            "--records_per_task",
            str(config.records_per_task),
            "--num_epochs",
            str(config.num_epochs),
            "--compute_dtype",
            "float32",
            "--shuffle_seed",
            str(config.shuffle_seed),
            "--jax_platform",
            "cpu",
            "--envs",
            ",".join(envs),
            "--port",
            "0",
            "--distribution_strategy",
            "AllreduceStrategy",
            "--num_workers",
            str(config.num_workers),
            *(
                # checkpoint-free durability: a streaming run persists
                # through the replica ring ONLY (the PR-4 disk fallback
                # then degrades to a fresh start, which bounded_lag
                # absorbs as requeued windows)
                []
                if config.streaming
                else [
                    "--checkpoint_dir",
                    ckpt_dir,
                    "--checkpoint_steps",
                    str(config.checkpoint_steps),
                ]
            ),
            *(["--streaming", "true"] if config.streaming else []),
            *(
                ["--live_push_addr", config.live_push_addr]
                if config.live_push_addr
                else []
            ),
            "--heartbeat_timeout_secs",
            str(config.heartbeat_timeout_secs),
            # telemetry event log (master lifecycle + worker step
            # samples) lands in the run dir, so the report CLI can join
            # it with the chaos artifacts written alongside
            "--telemetry_dir",
            os.path.join(config.workdir, "telemetry"),
            *(
                [
                    "--replication",
                    "true",
                    "--replication_steps",
                    str(config.replication_steps),
                ]
                if config.replication
                else []
            ),
            *(
                [
                    "--master_journal_dir",
                    os.path.join(config.workdir, "journal"),
                    "--rehome_grace_secs",
                    str(config.rehome_grace_secs),
                    "--standby_workers",
                    "0",
                ]
                if config.master_ha
                else []
            ),
            *(
                # forced multi-slice fleet (standbys off: a standby is
                # sliceless until activated, and slice plans re-form
                # into RESIZED worlds the warm pool was not sized for)
                ["--num_slices", str(config.num_slices),
                 "--standby_workers", "0"]
                if config.num_slices > 1
                else []
            ),
            *(
                ["--rpc_deadline_secs", str(config.rpc_deadline_secs)]
                if config.rpc_deadline_secs is not None
                else []
            ),
            *(
                ["--rpc_retry_secs", str(config.rpc_retry_secs)]
                if config.rpc_retry_secs is not None
                else []
            ),
            *(
                ["--task_timeout_secs", str(config.task_timeout_secs)]
                if config.task_timeout_secs is not None
                else []
            ),
            *config.extra_master_args,
        ]
    )


def _install_corruption(master, checker: InvariantChecker, mode: str):
    """Deliberately corrupt the run so the checker MUST flag it.

    - ``double_report``: the first successful training completion is
      delivered to observers twice (a double-counting dispatcher bug);
    - ``lose_task``: the first successful training completion is hidden
      from observers (a silently-lost completion);
    - ``version_rollback``: once training passes version 4, a
      lower-version report is injected (state regression);
    - ``drop_dedup``: the dispatcher's task-id dedup is disabled — a
      report for a no-longer-active lease (i.e. a netem-duplicated
      delivery) is counted AGAIN instead of dropped, so the
      exactly-once and duplicate-delivery invariants must trip.
    - ``drop_stream_window``: the first leased stream window vanishes
      (dropped from the active set, marked already-reported) — a
      lost-lease bug the watermark accounting must surface: the trained
      watermark can never cross the hole, so ``bounded_lag``'s
      final-drain clause must trip while the run still terminates.
    """
    from elasticdl_tpu.utils.constants import TaskType

    if not mode:
        return
    if mode not in CORRUPTIONS:
        raise ValueError(f"unknown corruption {mode!r}; valid: {CORRUPTIONS}")
    fired: list = []
    if mode in ("double_report", "lose_task"):
        task_d = master.task_d
        orig_report = task_d.report

        def corrupt_report(task_id, success=True, exec_counters=None):
            assignment = task_d._active.get(task_id)
            task = assignment.task if assignment else None
            is_victim = (
                success
                and not fired
                and task is not None
                and task.type == TaskType.TRAINING
            )
            if is_victim and mode == "lose_task":
                fired.append(task_id)
                # process the completion with the checker disconnected:
                # the dispatcher counts it, observers never learn
                observers, task_d._observers = task_d._observers, []
                try:
                    orig_report(
                        task_id, success=success, exec_counters=exec_counters
                    )
                finally:
                    task_d._observers = observers
                return
            orig_report(task_id, success=success, exec_counters=exec_counters)
            if is_victim and mode == "double_report":
                fired.append(task_id)
                task_d._notify("on_task_reported", task_id, task, True, True)

        task_d.report = corrupt_report
    elif mode == "version_rollback":

        def rollback(worker_id, version):
            if version >= 4 and not fired:
                fired.append(version)
                checker.on_version_report(worker_id, version - 3)

        master.servicer.add_version_observer(rollback)
    elif mode == "drop_dedup":
        task_d = master.task_d
        orig_report = task_d.report
        leased: dict[int, object] = {}

        class _LeaseMemo:
            """Remembers every lease so the duplicate path below can
            resurrect the Task object the dispatcher already popped."""

            def on_task_leased(self, task_id, worker_id, task):
                leased[task_id] = task

        task_d.add_observer(_LeaseMemo())

        def no_dedup_report(task_id, success=True, exec_counters=None):
            active_before = task_d.is_active(task_id)
            orig_report(
                task_id, success=success, exec_counters=exec_counters
            )
            task = leased.get(task_id)
            if (
                success
                and not active_before
                and task is not None
                and task.type == TaskType.TRAINING
            ):
                # dedup disabled: the duplicate delivery the dispatcher
                # just (correctly) dropped is counted anyway — the
                # double-counting bug the dedup contract prevents
                task_d._notify(
                    "on_task_reported", task_id, task, True, True
                )

        task_d.report = no_dedup_report
    elif mode == "drop_stream_window":
        task_d = master.task_d
        orig_get = task_d.get

        def dropping_get(worker_id):
            task_id, task = orig_get(worker_id)
            if (
                not fired
                and task is not None
                and task.type == TaskType.TRAINING
            ):
                fired.append(task_id)
                # the lease vanishes: gone from the active set AND
                # pre-marked reported, so neither the timeout reclaim
                # nor worker-death recovery can ever requeue it — the
                # exact shape of a lost-lease bug.  The worker still
                # trains the window (its report is then dropped as a
                # duplicate), so the job keeps moving and terminates.
                with task_d._lock:
                    task_d._active.pop(task_id, None)
                    task_d._reported_task_ids.add(task_id)
            return task_id, task

        task_d.get = dropping_get


class _CapacityDriver(threading.Thread):
    """Master-side fault execution: capacity faults trigger on the
    master-observed model version and re-form the world at the new
    size."""

    def __init__(
        self,
        master,
        plan: FaultPlan,
        events_path: str,
        fired: set | None = None,
    ):
        super().__init__(name="chaos-capacity-driver", daemon=True)
        self._master = master
        # `fired` is shared across master lives: the journal-restored
        # model version is already past an executed fault's at_step, so
        # without it every capacity fault would re-fire after a
        # MASTER_KILL relaunch
        self._fired = fired if fired is not None else set()
        self._pending = [
            f for f in plan.master_faults() if f.fault_id not in self._fired
        ]
        self._events_path = events_path
        self._stop = threading.Event()

    def stop(self):
        self._stop.set()

    def run(self):
        im = self._master.instance_manager
        if im is None or not getattr(im, "lockstep", False):
            return
        # the size a RESTORE_CAPACITY grows back to: the configured
        # fleet, not the CURRENT world — grow_under_load starts the job
        # deliberately smaller than the fleet
        full_size = getattr(im, "max_world_size", im.world_size)
        while self._pending and not self._stop.is_set():
            version = self._master.servicer.get_model_version()
            due = sorted(
                (f for f in self._pending if version >= f.at_step),
                key=lambda f: f.at_step,
            )
            if not due:
                self._stop.wait(0.2)
                continue
            # ONE fault per re-formation: firing shrink and restore in
            # the same poll would coalesce into a single full-size
            # reform — the shrunken world would never exist, yet both
            # faults would be logged as executed
            fault = due[0]
            self._pending.remove(fault)
            self._fired.add(fault.fault_id)
            if fault.kind == FaultKind.REDUCE_CAPACITY:
                im.set_world_size(im.world_size - fault.count)
            else:
                im.set_world_size(full_size)
            self._record(fault, version, im.world_size)
            reforms_before = len(self._master.reform_events)
            self._master.request_reform(f"chaos:{fault.fault_id}")
            deadline = time.monotonic() + 30.0
            while (
                not self._stop.is_set()
                and len(self._master.reform_events) == reforms_before
                and time.monotonic() < deadline
            ):
                self._stop.wait(0.2)

    def _record(self, fault, version: int, world_size: int):
        logger.warning(
            "CHAOS capacity fault %s at version %d -> world size %d",
            fault.fault_id,
            version,
            world_size,
        )
        chaos_hooks.append_event(
            self._events_path,
            {
                "fault_id": fault.fault_id,
                "kind": fault.kind,
                "process_id": None,
                "step": version,
                "world_size": world_size,
                "time": time.time(),
                "monotonic": time.monotonic(),
            },
        )


class _MasterKillWatcher(threading.Thread):
    """Arms a step-triggered MASTER_KILL: when the master-observed model
    version reaches the fault's ``at_step``, ask the run loop to die at
    its next tick (reform-triggered kills are armed up front via
    ``request_crash("reform")`` and need no watcher)."""

    def __init__(self, master, fault):
        super().__init__(name="chaos-master-kill-watcher", daemon=True)
        self._master = master
        self._fault = fault
        self._stop = threading.Event()

    def stop(self):
        self._stop.set()

    def run(self):
        while not self._stop.is_set():
            version = self._master.servicer.get_model_version()
            if version >= self._fault.at_step:
                logger.warning(
                    "CHAOS arming master kill %s at version %d",
                    self._fault.fault_id,
                    version,
                )
                self._master.request_crash("tick")
                return
            self._stop.wait(0.1)


def _record_master_kill(events_path: str, fault, crashed_at: float):
    """MASTER_KILL firings are recorded by the harness (the victim IS
    the process that owns the event log machinery), stamped with the
    master's own crash time so downtime metrics are exact."""
    chaos_hooks.append_event(
        events_path,
        {
            "fault_id": fault.fault_id,
            "kind": fault.kind,
            "process_id": None,
            "trigger": fault.trigger,
            "time": time.time(),
            "monotonic": crashed_at,
        },
        fsync=True,
    )


def _corrupt_journal_rollback(journal_dir: str):
    """``--corrupt journal_rollback``: forge a decreasing generation
    pair into the journal between master lives.  Replay's monotone
    guard absorbs it (the job must still complete); the master_recovery
    invariant must still FLAG the rolled-back fence record."""
    from elasticdl_tpu.master.journal import journal_path

    with open(journal_path(journal_dir), "a", encoding="utf-8") as f:
        for version in (1, 0):
            f.write(
                json.dumps(
                    {
                        "seq": 10**9,
                        "kind": "generation",
                        "cluster_version": version,
                        "time": time.time(),
                        "monotonic": time.monotonic(),
                        "forged": True,
                    }
                )
                + "\n"
            )


def _check_master_recovery(
    config: ChaosJobConfig,
    telemetry_dir: str,
    master_lives: int,
    events: list | None = None,
) -> dict | None:
    """The master-HA contract under a MASTER_KILL: the relaunched
    master must have restored from the journal (a ``master_restart``
    event per extra life), and the journal's generation-fence records
    must be monotone — a rolled-back fence would let a restored master
    resurrect a fenced generation."""
    kills = config.plan.master_kill_faults()
    if not kills or not config.master_ha:
        return None
    from elasticdl_tpu.master.journal import journal_path
    from elasticdl_tpu.telemetry.events import (
        EVENT_MASTER_RESTART,
        EVENTS_FILENAME,
        read_jsonl,
    )

    violations = []
    if events is None:
        events = read_jsonl(os.path.join(telemetry_dir, EVENTS_FILENAME))
    restarts = [
        e for e in events if e.get("event") == EVENT_MASTER_RESTART
    ]
    # realization first: the plan's kills must actually have fired —
    # deriving expected_restarts from the observed life count alone
    # would let a never-triggered MASTER_KILL (at_step beyond the job,
    # or a lost race with completion) pass this invariant vacuously
    if master_lives - 1 < len(kills):
        violations.append(
            f"plan demands {len(kills)} master kill(s) but only "
            f"{master_lives - 1} fired — the MASTER_KILL fault was "
            "never realized"
        )
    expected_restarts = master_lives - 1
    if len(restarts) < expected_restarts:
        violations.append(
            f"{expected_restarts} master relaunch(es) but only "
            f"{len(restarts)} master_restart event(s) — a relaunched "
            "master did not restore from the journal"
        )
    records = read_jsonl(
        journal_path(os.path.join(config.workdir, "journal"))
    )
    if not records:
        violations.append("control-plane journal is empty or unreadable")
    fences = [
        int(r["cluster_version"])
        for r in records
        if r.get("kind") == "generation"
    ]
    for prev, nxt in zip(fences, fences[1:]):
        if nxt < prev:
            violations.append(
                f"journal generation fence rolled back: {nxt} recorded "
                f"after {prev} — a restored master could resurrect a "
                "fenced generation"
            )
    return {
        "name": "master_recovery",
        "status": "FAIL" if violations else "PASS",
        "violations": violations,
    }


def _master_ha_stats(
    telemetry_dir: str, events: list | None = None
) -> dict | None:
    """Master-downtime stats from the run's telemetry event log — the
    SAME aggregation ``telemetry.report`` embeds, so
    ``chaos_result.json`` and the report can never disagree on schema."""
    from elasticdl_tpu.telemetry.events import EVENTS_FILENAME, read_jsonl
    from elasticdl_tpu.telemetry.report import master_ha_section

    if events is None:
        events = read_jsonl(os.path.join(telemetry_dir, EVENTS_FILENAME))
    return master_ha_section(events)


def _read_events(path: str) -> tuple[list[dict], list[dict]]:
    """(fault firings, observations) from the shared event log."""
    faults: list[dict] = []
    observations: list[dict] = []
    if not os.path.exists(path):
        return faults, observations
    with open(path, encoding="utf-8") as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                event = json.loads(line)
            except ValueError:
                continue  # torn line from a killed writer
            (observations if "observation" in event else faults).append(event)
    return faults, observations


def _load_telemetry_events(telemetry_dir: str) -> list[dict]:
    """ONE parse of the (possibly multi-shard, rotated) telemetry event
    log per run — every post-run checker/stats consumer below shares
    the returned list instead of re-reading the file."""
    from elasticdl_tpu.telemetry.events import EVENTS_FILENAME, read_jsonl

    return read_jsonl(os.path.join(telemetry_dir, EVENTS_FILENAME))


def _replication_stats(events: list[dict]) -> dict:
    """Replica coverage from the run's telemetry event log — the SAME
    aggregation ``telemetry.report`` embeds, so ``chaos_result.json``
    and the report can never disagree on schema."""
    from elasticdl_tpu.telemetry.report import replication_section

    return replication_section(events) or {}


def _check_no_lost_steps(
    config: ChaosJobConfig,
    events: list[dict],
    fault_events: list[dict],
) -> dict | None:
    """The replication contract under a plain preemption: the resumed
    generation restores FROM PEER RAM at exactly the last replicated
    step before the kill — not the (older) last disk milestone."""
    if not config.replication:
        return None
    recoverable = [
        e
        for e in fault_events
        if e.get("kind") in _REPLICA_RECOVERABLE_KINDS
    ]
    if not recoverable:
        return None
    kill_at = min(e["monotonic"] for e in recoverable)
    push_events = [
        e
        for e in events
        if e.get("event") == "replica_push"
        and e.get("monotonic", 0.0) <= kill_at
    ]
    restore_events = [
        e for e in events if e.get("event") == "replica_restore"
    ]
    pushed = [int(e.get("step", -1)) for e in push_events]
    restored = [int(e.get("step", -1)) for e in restore_events]
    violations = []
    if not pushed:
        violations.append("no replica_push before the kill")
    if not restored:
        violations.append(
            "no replica_restore event — the re-formed world did not "
            "restore from peer RAM"
        )
    elif pushed and max(restored) < max(pushed):
        violations.append(
            f"restored at step {max(restored)} but step {max(pushed)} "
            "was replicated before the kill — steps lost despite a "
            "complete replica set"
        )
    # sharded-table extension: when the replicated state carries
    # row-sharded tables, "no lost steps" includes the ROWS — the
    # pushes before the kill must have carried them and the restore
    # must have applied them (a restore event alone proves only the
    # dense leaves came back)
    sharded_state = any(e.get("has_sharded") for e in push_events)
    if sharded_state:
        rows_pushed = sum(
            int(e.get("sharded_rows", 0) or 0) for e in push_events
        )
        rows_restored = sum(
            int(e.get("sharded_rows", 0) or 0) for e in restore_events
        )
        if not rows_pushed:
            violations.append(
                "pushes report row-sharded state but carried zero "
                "sharded table rows before the kill — the tables had "
                "no replica to survive it"
            )
        if restored and not rows_restored:
            violations.append(
                "replica restore applied zero sharded table rows "
                "though the replicated state is row-sharded — the "
                "tables were lost across the reform"
            )
    return {
        "name": "replication_no_lost_steps",
        "status": "FAIL" if violations else "PASS",
        "violations": violations,
    }


def check_cross_slice_coverage(
    events: list[dict], num_slices: int
) -> list[str]:
    """The slice-aware replica-ring contract, as a pure function of the
    telemetry event log (unit-testable against synthetic events): on a
    multi-slice world every replica push must land on a DIFFERENT slice
    than its source — otherwise a whole-slice preemption takes a shard
    and its only replica together and the hot restore silently degrades
    to disk.  Returns the violations (empty = PASS)."""
    violations: list[str] = []
    pushes = [
        e
        for e in events
        if e.get("event") == "replica_push"
        # only pushes made FROM a multi-slice world are in contract
        # (a post-shrink single-slice world has no off-slice to push to)
        and int(e.get("num_slices", 1) or 1) > 1
    ]
    if num_slices > 1 and not pushes:
        violations.append(
            "no replica_push events from a multi-slice world — ring "
            "coverage unproven"
        )
    for e in pushes:
        src, dst = e.get("source_slice"), e.get("target_slice")
        if src is None or dst is None:
            violations.append(
                f"replica_push at step {e.get('step')} carries no slice "
                "placement (source_slice/target_slice missing)"
            )
        elif src == dst:
            violations.append(
                f"replica_push at step {e.get('step')}: process "
                f"{e.get('source')} pushed to process {e.get('target')} "
                f"on its OWN slice {src} — a slice loss takes shard and "
                "replica together"
            )
    # sharded-table extension (audited over EVERY push, not just the
    # multi-slice ones): a push whose source state HAS row-sharded
    # tables must carry its shard's rows — has_sharded with zero
    # sharded_rows is a replica that would restore the dense leaves but
    # lose the table (exactly what --corrupt drop_shard_parts forges)
    for e in events:
        if e.get("event") != "replica_push" or not e.get("has_sharded"):
            continue
        if not int(e.get("sharded_rows", 0) or 0):
            violations.append(
                f"replica_push at step {e.get('step')} from process "
                f"{e.get('source')}: state has "
                f"{e.get('sharded_tables')} row-sharded table(s) but "
                "the push carried zero rows — the shard's only replica "
                "holds no table coverage"
            )
    return violations


def _check_no_false_dead(
    config: ChaosJobConfig, reform_events: list[dict]
) -> dict | None:
    """Gray-vs-dead discrimination, the tolerant half: a plan whose only
    faults are network LATENCY (within the heartbeat tolerance) must
    complete with ZERO re-formations — a slow link is not a dead
    worker, and evicting on latency turns every congested epoch into a
    reform storm."""
    kinds = {f.kind for f in config.plan.faults}
    if not kinds or kinds != {FaultKind.NET_DELAY}:
        return None
    violations = []
    if reform_events:
        violations.append(
            f"{len(reform_events)} re-formation(s) during a latency-only "
            "network plan — a slow-but-alive worker was declared dead "
            f"(reasons: {[e.get('reason') for e in reform_events]})"
        )
    return {
        "name": "no_false_dead",
        "status": "FAIL" if violations else "PASS",
        "violations": violations,
    }


def _check_duplicate_delivery(
    config: ChaosJobConfig, checker: InvariantChecker, fault_events: list[dict]
) -> dict | None:
    """The dedup contract under ACTUAL duplicate delivery: netem
    re-executed report RPCs server-side, and task accounting must still
    be exactly-once — with proof the dedup ENGAGED (the dispatcher
    visibly dropped the re-deliveries), not that duplication silently
    never happened.  Falsifiable via ``--corrupt drop_dedup``."""
    dup_faults = [
        f
        for f in config.plan.faults
        if f.kind == FaultKind.NET_DUPLICATE
    ]
    if not dup_faults and config.corrupt != "drop_dedup":
        return None
    violations = []
    dup_fired = [
        e for e in fault_events if e.get("kind") == FaultKind.NET_DUPLICATE
    ]
    if dup_faults and not dup_fired:
        # realization first (PR-6 pattern): an unfired duplicate fault
        # must not let this invariant pass vacuously
        violations.append(
            f"plan injects {len(dup_faults)} duplicate-delivery fault(s) "
            "but none fired — netem server-seam plumbing broken?"
        )
    dup_task_reports = [
        e for e in dup_fired if e.get("method") == "report_task_result"
    ]
    if dup_task_reports and checker.dropped_reports == 0:
        violations.append(
            f"{len(dup_task_reports)} duplicated report_task_result "
            "deliveries but the dispatcher never dropped one — the "
            "task-id dedup did not engage"
        )
    for detail in checker.double_counted_tasks():
        violations.append(
            f"task {detail} — duplicate delivery double-counted a shard"
        )
    return {
        "name": "duplicate_delivery_exactly_once",
        "status": "FAIL" if violations else "PASS",
        "violations": violations,
    }


def _check_cross_slice_coverage(
    config: ChaosJobConfig, events: list[dict]
) -> dict | None:
    if not config.replication or config.num_slices <= 1:
        return None
    violations = check_cross_slice_coverage(events, config.num_slices)
    return {
        "name": "cross_slice_replica_coverage",
        "status": "FAIL" if violations else "PASS",
        "violations": violations,
    }


def _check_bounded_lag(
    config: ChaosJobConfig,
    events: list[dict],
    final_status: dict | None,
) -> dict | None:
    """Streaming replacement for epoch parity: under fault, the lag
    behind the source watermark must stay bounded, and the final drain
    must be complete (trained watermark == stream total — a window
    whose lease was lost forever leaves a hole the trained watermark
    can never cross).  None on epoch-mode runs."""
    if not config.streaming:
        return None
    limit = config.stream_lag_limit or max(
        256, 6 * config.records_per_task
    )
    lags = [
        int(e.get("lag_records", 0))
        for e in events
        if e.get("event") == "stream_lag"
    ]
    violations = []
    if not lags:
        violations.append(
            "streaming run produced no stream_lag events — watermark "
            "telemetry missing"
        )
    else:
        worst = max(lags)
        if worst > limit:
            violations.append(
                f"lag peaked at {worst} records > bound {limit} — "
                "backlog not bounded under fault"
            )
    trained = (final_status or {}).get("trained_watermark")
    if config.stream_total and trained != config.stream_total:
        violations.append(
            f"final drain incomplete: trained watermark {trained} != "
            f"stream total {config.stream_total} (a leased window was "
            "lost and never requeued)"
        )
    return {
        "name": "bounded_lag",
        "status": "FAIL" if violations else "PASS",
        "violations": violations,
        "max_lag_records": max(lags) if lags else None,
        "lag_limit_records": limit,
    }


def _check_freshness_monotone(
    config: ChaosJobConfig, events: list[dict]
) -> dict | None:
    """The served model's trained-watermark must never decrease across
    live pushes: an accepted push with an older watermark than a
    previously accepted one means serving regressed to staler state.
    Vacuously PASS (with ``pushes: 0``) on streaming runs without a
    live-push target; None on epoch-mode runs."""
    if not config.streaming:
        return None
    pushes = sorted(
        (
            e
            for e in events
            if e.get("event") == "live_push" and e.get("accepted")
        ),
        key=lambda e: e.get("monotonic", 0.0),
    )
    violations = []
    high = None
    for push in pushes:
        trained = int(push.get("trained_watermark", -1))
        if high is not None and trained < high:
            violations.append(
                f"served trained-watermark regressed: {trained} after "
                f"{high} (model version {push.get('model_version')})"
            )
        high = trained if high is None else max(high, trained)
    return {
        "name": "freshness_monotone",
        "status": "FAIL" if violations else "PASS",
        "violations": violations,
        "pushes": len(pushes),
    }


def run_chaos_job(config: ChaosJobConfig) -> dict:
    """Run one chaos'd job end to end; returns the report dict.

    The report's ``invariants_ok`` is the verdict; ``records_ok`` keeps
    the benchmarks' historical record-accounting boolean."""
    from elasticdl_tpu.data.recordio_gen import synthetic
    from elasticdl_tpu.master.main import build_master
    from elasticdl_tpu.utils.constants import TaskType

    os.makedirs(config.workdir, exist_ok=True)
    plan_path = os.path.join(config.workdir, "chaos_plan.json")
    events_path = os.path.join(config.workdir, "chaos_events.jsonl")
    config.plan.save(plan_path)
    if os.path.exists(events_path):
        os.remove(events_path)
    # a reused --workdir must start FRESH: a leftover checkpoint would
    # make restore_trainer_state resume at the previous run's final
    # version, so the plan's step-armed faults would fire against a
    # different (already-trained) trajectory than the report claims
    import shutil

    shutil.rmtree(os.path.join(config.workdir, "ckpt"), ignore_errors=True)
    # same freshness rule for the telemetry event log: stale step events
    # from a previous run would corrupt the report's per-generation stats
    shutil.rmtree(
        os.path.join(config.workdir, "telemetry"), ignore_errors=True
    )
    # and for the control-plane journal: a stale journal would make the
    # FIRST master of this run restore a previous run's dispatch state
    shutil.rmtree(
        os.path.join(config.workdir, "journal"), ignore_errors=True
    )

    if config.dataset not in DATASETS:
        raise ValueError(
            f"unknown dataset {config.dataset!r}; valid: {DATASETS}"
        )
    if config.streaming:
        if config.stream_total <= 0:
            # a truly unbounded source never closes, so finished()
            # never fires and the harness would only ever time out
            raise ValueError(
                "streaming chaos runs need a bounded prefix: set "
                "ChaosJobConfig.stream_total > 0"
            )
        # no recordio shards: records are a pure function of
        # (seed, index), so the origin string IS the dataset
        train = (
            f"stream://{config.dataset}?seed={config.data_seed}"
            f"&total={config.stream_total}&rate={config.stream_rate}"
            f"&initial={config.stream_initial}"
        )
    else:
        gen = (
            synthetic.gen_frappe
            if config.dataset == "frappe"
            else synthetic.gen_mnist
        )
        train = gen(
            os.path.join(config.workdir, "train"),
            num_records=config.num_records,
            num_shards=2,
            seed=config.data_seed,
        )
    ckpt = os.path.join(config.workdir, "ckpt")
    args = _master_args(config, train, ckpt)

    expected_records = (
        config.stream_total
        if config.streaming
        else config.num_epochs * config.num_records
    )
    checker = InvariantChecker(expected_records=expected_records)

    from elasticdl_tpu.master.master import SimulatedMasterCrash

    kills = config.plan.master_kill_faults()
    if kills and not config.master_ha:
        # refuse rather than silently drop the kills: the run would
        # complete green with the plan's MASTER_KILL never armed and no
        # invariant recording the unrealized fault
        raise ValueError(
            f"plan {config.plan.name!r} contains MASTER_KILL faults "
            "but master_ha is off — enable ChaosJobConfig.master_ha "
            "(the runner does this for the master_kill_* plans)"
        )
    if config.corrupt == "journal_rollback" and not kills:
        # the forgery happens between master lives; without a MASTER_KILL
        # fault it would inject NOTHING and the "corrupted runs must exit
        # non-zero" contract would silently pass green
        raise ValueError(
            "--corrupt journal_rollback requires a master_kill plan "
            "with master HA enabled (the forgery lands between master "
            "lives)"
        )
    if config.corrupt == "drop_dedup" and not any(
        f.kind == FaultKind.NET_DUPLICATE for f in config.plan.faults
    ):
        # the corruption counts DUPLICATED deliveries twice; without a
        # net_duplicate fault nothing is ever duplicated and the
        # "corrupted runs must exit non-zero" contract would pass green
        raise ValueError(
            "--corrupt drop_dedup requires a plan with net_duplicate "
            "faults (dup_report_storm) — without duplicate delivery "
            "the disabled dedup corrupts nothing"
        )
    if config.corrupt == "drop_shard_parts" and not config.replication:
        # the corruption strips sharded rows from replica push BLOBS;
        # without replication no push ever happens and the "corrupted
        # runs must exit non-zero" contract would pass green (a model
        # without row-sharded tables is caught at run time: pushes then
        # carry has_sharded=False and the sharded-coverage extension
        # reports the vacuity)
        raise ValueError(
            "--corrupt drop_shard_parts requires replication on and a "
            "model whose tables are row-sharded (it strips sharded rows "
            "from the replica push payloads)"
        )
    if config.corrupt == "drop_stream_window" and not config.streaming:
        # the corruption vanishes a leased STREAM window; an epoch-mode
        # run has no watermark accounting to trip, so the "corrupted
        # runs must exit non-zero" contract would silently pass green
        raise ValueError(
            "--corrupt drop_stream_window requires a streaming run "
            "(ChaosJobConfig.streaming=True) — epoch-mode runs have no "
            "watermark accounting to falsify"
        )
    if config.corrupt == "same_slice_ring" and not (
        config.replication and config.num_slices > 1
    ):
        # the corruption swaps the replica ring's neighbor function:
        # without replication AND a multi-slice world it would corrupt
        # nothing and the run would pass green
        raise ValueError(
            "--corrupt same_slice_ring requires replication on and "
            "num_slices > 1 (it forces the slice-blind replica ring)"
        )
    slice_faults = [
        f for f in config.plan.faults if f.kind == FaultKind.SLICE_LOSS
    ]
    if slice_faults and config.num_slices <= 1:
        # a SLICE_LOSS on a single-slice world arms nothing (no process
        # carries the target slice_id) — refuse rather than pass green
        raise ValueError(
            f"plan {config.plan.name!r} contains SLICE_LOSS faults but "
            "num_slices is 1 — configure ChaosJobConfig.num_slices (the "
            "runner does this for the slice plans)"
        )
    started_at = time.monotonic()
    deadline = started_at + config.run_timeout_secs
    reform_events: list[dict] = []
    timed_out = False
    rc: list[int] = []
    life = 0
    fired_capacity: set[str] = set()
    from elasticdl_tpu.chaos import netem

    # start clean: a previous run in this process (back-to-back tests)
    # may have left a server-seam shim installed if it unwound on error
    netem.uninstall()
    net_shim = None
    try:
        while True:
            master = build_master(args)
            # server-seam network faults (duplicate delivery) fire inside
            # THIS process's handlers.  Installed ONCE per run — the shim's
            # arming state must span master lives (a rebuilt shim would
            # reset its counters and re-fire exhausted faults after a
            # MASTER_KILL relaunch, like the capacity-fault fired-set
            # guards against) — with only the telemetry sink rebound to the
            # new life's event log.  A plan without such faults installs
            # nothing.
            if net_shim is None:
                net_shim = netem.install_master_from_plan(
                    config.plan,
                    events_path,
                    telemetry_sink=master.telemetry.events.emit,
                )
            else:
                net_shim.set_telemetry_sink(master.telemetry.events.emit)
            if config.initial_slices is not None and hasattr(
                master.instance_manager, "set_world_slices"
            ):
                # grow_under_load: the job STARTS on fewer slices than the
                # fleet; the capacity-grant fault grows it mid-training
                master.instance_manager.set_world_slices(config.initial_slices)
            # the SAME checker spans every master life: task identity is the
            # journaled uid, so the restored dispatcher's backlog replay
            # dedups onto the pre-outage records instead of resetting them
            master.task_d.add_observer(checker)
            master.servicer.add_version_observer(checker.on_version_report)
            master.reform_callbacks.append(checker.on_reform)
            if life == 0:
                _install_corruption(master, checker, config.corrupt)
            kill = kills[life] if life < len(kills) else None
            watcher = None
            if kill is not None:
                if kill.trigger == "reform":
                    master.request_crash("reform")
                else:
                    watcher = _MasterKillWatcher(master, kill)
            driver = _CapacityDriver(
                master, config.plan, events_path, fired=fired_capacity
            )
            master.prepare()
            crashed: list[bool] = []

            def run_master(m=master):
                try:
                    rc.append(m.run())
                except SimulatedMasterCrash:
                    crashed.append(True)

            runner = threading.Thread(
                target=run_master, name=f"chaos-master-run-{life}"
            )
            runner.start()
            driver.start()
            if watcher is not None:
                watcher.start()
            try:
                runner.join(timeout=max(1.0, deadline - time.monotonic()))
                timed_out = runner.is_alive()
            finally:
                driver.stop()
                if watcher is not None:
                    watcher.stop()
                if timed_out or not crashed:
                    master.request_stop()
                    runner.join(timeout=30)
            reform_events.extend(master.reform_events)
            if crashed and not timed_out:
                life += 1
                _record_master_kill(events_path, kill, master.crashed_at)
                if config.corrupt == "journal_rollback":
                    _corrupt_journal_rollback(
                        os.path.join(config.workdir, "journal")
                    )
                # the master-down window: workers retry/back off in here
                time.sleep(kill.duration_secs or 2.0)
                continue
            break
    finally:
        # the module-global server-seam shim must not leak into the
        # baseline run that typically follows in this same process —
        # nor into unrelated masters if this loop unwinds on an error
        netem.uninstall()
    counters = master.task_d.counters(TaskType.TRAINING)
    fault_events, observations = _read_events(events_path)

    # ---- latency metrics (first kill-type firing -> detection -> step)
    kill_at = next(
        (
            e["monotonic"]
            for e in fault_events
            if e.get("kind") in _KILL_KINDS
        ),
        None,
    )
    # the re-formation CAUSED BY the fault (a heavily-loaded host can
    # reform spuriously before the fault fires)
    reform = next(
        (
            e
            for e in reform_events
            if kill_at is None or e["detected_at"] >= kill_at
        ),
        reform_events[0] if reform_events else {},
    )
    pull_at = master.servicer.first_stream_pull_at()
    detect_secs = (
        round(reform["detected_at"] - kill_at, 3)
        if reform and kill_at is not None
        else None
    )
    kill_to_step_secs = (
        round(pull_at - kill_at, 3)
        if pull_at is not None and kill_at is not None
        else None
    )

    records_ok = (
        rc == [0]
        and master.task_d.finished()
        and counters.total_records == expected_records
    )
    invariants = checker.summary(counters)

    # ---- the plan must have EXECUTED: a fault-free run must not pass a
    # fault-injection gate (the old reform_bench's os.kill guaranteed
    # this by construction; here a plan-plumbing regression would
    # otherwise train undisturbed and report PASS).  Conservative on
    # purpose: a gen-0 kill legitimately pre-empts later same-generation
    # faults, so individual unfired faults are reported, not failed.
    fired_ids = {e.get("fault_id") for e in fault_events}
    unfired = [
        f.fault_id for f in config.plan.faults if f.fault_id not in fired_ids
    ]
    fault_violations = []
    if config.plan.faults and not fault_events:
        fault_violations.append(
            "plan has %d fault(s) but none fired — injection plumbing "
            "broken?" % len(config.plan.faults)
        )
    def _evicting(f) -> bool:
        """Kill kinds always cost their worker; a network window fault
        only when the window OUTLASTS the worker's retry budget — a
        survivable blackhole (netchaos smoke) must ride out on retries
        with no re-formation at all."""
        if f.kind not in _KILL_KINDS:
            return False
        if f.kind in (FaultKind.NET_BLACKHOLE, FaultKind.NET_PARTITION):
            from elasticdl_tpu.rpc.retry import DEFAULT_RETRY_SECS

            budget = (
                config.rpc_retry_secs
                if config.rpc_retry_secs is not None
                else DEFAULT_RETRY_SECS
            )
            return (f.duration_secs or 0.0) > budget
        return True

    gen0_kills = [
        f
        for f in config.plan.faults
        if f.cluster_version == 0 and _evicting(f)
    ]
    if gen0_kills and not reform_events:
        fault_violations.append(
            "plan kills a generation-0 worker but no re-formation "
            "occurred"
        )
    # a capacity fault is only EXECUTED once a re-formation realizes the
    # new size — the driver records the request, but the job can finish
    # (or the run loop stop) before the reform runs.  Accept either the
    # matching chaos-reason reform or any reform at/after the firing
    # (a racing failure-reform coalesces the resize into itself).
    reform_reasons = {e.get("reason") for e in reform_events}
    for event in fault_events:
        if event.get("kind") not in (
            FaultKind.REDUCE_CAPACITY,
            FaultKind.RESTORE_CAPACITY,
        ):
            continue
        realized = f"chaos:{event['fault_id']}" in reform_reasons or any(
            e["detected_at"] >= event["monotonic"] - 2.0
            for e in reform_events
        )
        if not realized:
            fault_violations.append(
                f"capacity fault {event['fault_id']} was requested but "
                "no re-formation realized it"
            )
    invariants["invariants"].append(
        {
            "name": "faults_injected",
            "status": "FAIL" if fault_violations else "PASS",
            "violations": fault_violations,
        }
    )
    if fault_violations:
        invariants["ok"] = False

    # ---- network-chaos invariants (gray failures: docs/designs/
    # network_chaos.md) — None unless the plan is in their contract
    for network_check in (
        _check_no_false_dead(config, reform_events),
        _check_duplicate_delivery(config, checker, fault_events),
    ):
        if network_check is not None:
            invariants["invariants"].append(network_check)
            if network_check["status"] == "FAIL":
                invariants["ok"] = False

    telemetry_dir = os.path.join(config.workdir, "telemetry")
    # ONE shared parse of the (possibly multi-shard) telemetry event log
    # for every post-run checker and stats section below
    telemetry_events = (
        _load_telemetry_events(telemetry_dir)
        if (
            config.replication
            or config.num_slices > 1
            or config.master_ha
            or config.streaming
        )
        else []
    )
    replication_stats = (
        _replication_stats(telemetry_events)
        if config.replication
        else None
    )
    lost_steps = _check_no_lost_steps(
        config, telemetry_events, fault_events
    )
    if lost_steps is not None:
        invariants["invariants"].append(lost_steps)
        if lost_steps["status"] == "FAIL":
            invariants["ok"] = False
    cross_slice = _check_cross_slice_coverage(config, telemetry_events)
    if cross_slice is not None:
        invariants["invariants"].append(cross_slice)
        if cross_slice["status"] == "FAIL":
            invariants["ok"] = False
    stream_status = (
        master.task_d.stream_status() if config.streaming else None
    )
    for stream_check in (
        _check_bounded_lag(config, telemetry_events, stream_status),
        _check_freshness_monotone(config, telemetry_events),
    ):
        if stream_check is not None:
            invariants["invariants"].append(stream_check)
            if stream_check["status"] == "FAIL":
                invariants["ok"] = False
    multislice_stats = None
    if config.num_slices > 1:
        from elasticdl_tpu.telemetry.report import multislice_section

        multislice_stats = multislice_section(telemetry_events)
    master_recovery = _check_master_recovery(
        config,
        telemetry_dir,
        master_lives=life + 1,
        events=telemetry_events if config.master_ha else None,
    )
    if master_recovery is not None:
        invariants["invariants"].append(master_recovery)
        if master_recovery["status"] == "FAIL":
            invariants["ok"] = False
    master_ha_stats = (
        _master_ha_stats(telemetry_dir, events=telemetry_events)
        if config.master_ha
        else None
    )

    report = {
        "plan": config.plan.name,
        "seed": config.plan.seed,
        "corrupt": config.corrupt,
        "num_workers": config.num_workers,
        "num_records": config.num_records,
        "num_epochs": config.num_epochs,
        "rc": rc[0] if rc else None,
        "timed_out": timed_out,
        "wall_secs": round(time.monotonic() - started_at, 3),
        "records_ok": records_ok,
        "faults_injected": fault_events,
        "observations": observations,
        "invariants": invariants["invariants"],
        "invariants_ok": bool(
            invariants["ok"] and records_ok and not timed_out
        ),
        "faults_unfired": unfired,
        "tasks_tracked": invariants["tasks_tracked"],
        "max_model_version": invariants["max_model_version"],
        "reforms": [
            {
                k: round(v, 3) if isinstance(v, float) else v
                for k, v in e.items()
                if k != "detected_at"
            }
            for e in reform_events
        ],
        "reform_latency_secs": round(reform.get("latency_secs", -1.0), 3),
        "detect_secs": detect_secs,
        "kill_to_step_secs": kill_to_step_secs,
        "heartbeat_timeout_secs": config.heartbeat_timeout_secs,
        "standby_activated": getattr(
            master.instance_manager, "standby_activations", 0
        ),
        # fleet-wide RPC outcome totals (heartbeat-shipped; rpc/stats.py)
        # plus the master-observed dedup drops — what the netchaos smoke
        # gates on (a blackhole run must show deadline_exceeded > 0)
        "rpc": {
            **master.servicer.rpc_stats_totals(),
            "reports_deduped": checker.dropped_reports,
            "eval_reports_deduped": master.servicer.duplicate_eval_drops,
        },
    }
    if replication_stats is not None:
        report["replication"] = replication_stats
    if multislice_stats is not None:
        report["multislice"] = multislice_stats
    if master_ha_stats is not None:
        report["master_ha"] = master_ha_stats
    if config.streaming:
        from elasticdl_tpu.telemetry.report import streaming_section

        report["streaming"] = {
            "final": stream_status,
            **(streaming_section(telemetry_events) or {}),
        }
    if config.master_ha:
        report["master_lives"] = life + 1
    if not records_ok:
        report["total_records"] = counters.total_records

    if config.evaluate and records_ok:
        report["accuracy"] = round(
            _evaluate_checkpoint(config, ckpt), 4
        )
    return report


def _evaluate_checkpoint(config: ChaosJobConfig, ckpt: str) -> float:
    """Restore the job's final checkpoint into a single-process evaluator
    and score it on a held-out split (the lockstep layout re-shards onto
    this process's local mesh via the save_utils reshard property)."""
    from elasticdl_tpu.data.recordio_gen import synthetic
    from elasticdl_tpu.trainer.local_executor import LocalExecutor
    from elasticdl_tpu.utils.args import parse_master_args

    gen = (
        synthetic.gen_frappe
        if config.dataset == "frappe"
        else synthetic.gen_mnist
    )
    eval_dir = gen(
        os.path.join(config.workdir, "eval"),
        num_records=config.eval_records,
        num_shards=1,
        seed=config.eval_seed,
    )
    args = parse_master_args(
        [
            "--model_def",
            config.model_def,
            "--validation_data",
            eval_dir,
            "--minibatch_size",
            str(config.minibatch_size),
            "--records_per_task",
            str(config.eval_records),
            "--checkpoint_dir",
            ckpt,
            "--compute_dtype",
            "float32",
        ]
    )
    results = LocalExecutor(args).run()
    return float(results.get("accuracy", 0.0))
