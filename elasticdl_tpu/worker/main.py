"""Worker process entry (reference elasticdl/python/worker/main.py:9-40).

Connects to the master control plane over gRPC and runs the task loop:
``python -m elasticdl_tpu.worker.main --master_addr=... --worker_id=N ...``

Two runtimes, selected by the master via the argv round-trip:

- ``--coordinator_addr`` set: the **lockstep** multi-process SPMD runtime
  — this process joins the job's ``jax.distributed`` world and trains the
  ONE shared model with its peers (worker/lockstep.py).
- otherwise: the single-process task-stream runtime (worker/worker.py),
  an SPMD program over this process's local devices only.
"""

from __future__ import annotations

import json
import os
import sys
import time

from elasticdl_tpu.rpc.service import MasterClient
from elasticdl_tpu.utils.args import parse_worker_args
from elasticdl_tpu.utils.log_utils import default_logger as logger


def build_master_client(master_addr: str) -> MasterClient:
    """Master-HA- and deadline-aware client: when the master exported a
    retry budget (``--master_journal_dir`` or ``--rpc_retry_secs``),
    RPCs back off across an outage and re-resolve the control-plane
    address from the journal dir's addr file; when it exported
    ``--rpc_deadline_secs``, every call carries a per-method deadline so
    a blackholed link degrades to DEADLINE_EXCEEDED (which feeds that
    same retry loop) instead of hanging forever.  With neither env the
    client is the plain fail-fast one — byte-identical behavior."""
    from elasticdl_tpu.master.journal import (
        MASTER_ADDR_FILE_ENV,
        read_master_addr,
    )
    from elasticdl_tpu.rpc.deadline import DeadlinePolicy
    from elasticdl_tpu.rpc.retry import (
        DEFAULT_RETRY_SECS,
        RETRY_SECS_ENV,
        RetryPolicy,
    )
    from elasticdl_tpu.rpc.service import MASTER_RETRYABLE_METHODS

    deadlines = DeadlinePolicy.from_env()
    budget = os.environ.get(RETRY_SECS_ENV, "")
    addr_file = os.environ.get(MASTER_ADDR_FILE_ENV, "")
    if not budget and not addr_file:
        return MasterClient(master_addr, deadlines=deadlines)
    try:
        budget_secs = float(budget) if budget else DEFAULT_RETRY_SECS
    except ValueError:
        budget_secs = DEFAULT_RETRY_SECS
    return MasterClient(
        master_addr,
        retry=RetryPolicy.from_budget(budget_secs),
        retryable_methods=MASTER_RETRYABLE_METHODS,
        resolve_addr=(
            (lambda: read_master_addr(addr_file)) if addr_file else None
        ),
        deadlines=deadlines,
    )


def _standby_wait(args) -> bool:
    """Hot-standby mode: pay the cold-start cost NOW (imports dominate
    worker spawn latency), then block until the master writes a world
    assignment as one JSON line on stdin.  Returns False on EOF (master
    shut the pool down without using this process)."""
    from elasticdl_tpu.parallel import elastic

    # pin the platform BEFORE any import can initialize a backend: a
    # model-zoo module doing jnp work at import time would otherwise
    # initialize the default backend, making the activation-time
    # configure_platform ineffective.  (On a chip that import-time
    # backend would also reach for chips the live world holds — a
    # standby must not touch a device before its assignment.)
    elastic.configure_platform(getattr(args, "jax_platform", "") or None)

    from elasticdl_tpu.utils.model_utils import get_model_spec
    from elasticdl_tpu.worker import lockstep  # noqa: F401 — warm the chain

    try:  # model-zoo import is part of the cold start too
        get_model_spec(
            getattr(args, "model_zoo", "") or "", args.model_def
        )
    except Exception:  # noqa: BLE001 — the live run will surface it
        pass
    standby_id = os.environ.get("EDL_STANDBY_ID", "")
    logger.info(
        "Standby worker warmed; waiting for a world assignment (%s)",
        f"RPC as {standby_id!r}" if standby_id else "stdin",
    )
    if standby_id:
        assignment = _poll_world_assignment(args, standby_id)
    else:
        # local backend: the instance manager writes one JSON line
        line = sys.stdin.readline()
        assignment = json.loads(line) if line.strip() else None
    if assignment is None:
        return False
    # the local manager's per-process chip binding (a cold spawn gets it
    # in its environment): applied before any backend initializes
    os.environ.update(assignment.pop("env", {}))
    for key, value in assignment.items():
        setattr(args, key, value)
    args.standby = 0
    return True


def _poll_world_assignment(
    args, standby_id: str, poll_secs: float = 0.5,
    max_unreachable_secs: float = 900.0,
) -> dict | None:
    """k8s standbys cannot receive stdin: poll the master's assignment
    mailbox instead (same payload keys as the stdin line).

    ``max_unreachable_secs``: if the master stays CONTINUOUSLY
    unreachable this long (crashed without posting shutdown, and the pod
    not GC'd via owner references), the standby exits cleanly rather
    than polling forever as an orphan; any successful poll resets the
    clock."""
    from elasticdl_tpu.rpc import messages as msg
    from elasticdl_tpu.rpc.deadline import DeadlinePolicy

    # the poll loop survives ANY exception, but without a deadline a
    # blackholed master link would hang the poll itself forever — the
    # standby then never notices the master moved (found by elastic-lint
    # rpc-contract: every client threads the job's deadline policy)
    client = MasterClient(args.master_addr, deadlines=DeadlinePolicy.from_env())
    failures = 0
    unreachable_since = None
    try:
        while True:
            try:
                resp = client.get_world_assignment(
                    msg.GetWorldAssignmentRequest(standby_id=standby_id)
                )
                failures = 0
                unreachable_since = None
            except Exception as ex:  # noqa: BLE001 — a standby must
                # survive transient master unavailability (pod reschedule,
                # network blip): crashing here silently shrinks the pool
                failures += 1
                now = time.monotonic()
                if unreachable_since is None:
                    unreachable_since = now
                elif (
                    max_unreachable_secs > 0
                    and now - unreachable_since > max_unreachable_secs
                ):
                    logger.error(
                        "Standby %s: master unreachable for %.0fs; "
                        "assuming the job is gone and exiting",
                        standby_id,
                        now - unreachable_since,
                    )
                    return None
                if failures % 60 == 1:
                    logger.warning(
                        "Standby %s cannot reach the master (%s); retrying",
                        standby_id,
                        ex,
                    )
                time.sleep(poll_secs)
                continue
            if resp.has:
                return {
                    "worker_id": resp.worker_id,
                    "coordinator_addr": resp.coordinator_addr,
                    "num_processes": resp.num_processes,
                    "process_id": resp.process_id,
                    "cluster_version": resp.cluster_version,
                    # slice coordinates (multi-slice worlds; defaults
                    # on single-slice jobs)
                    "slice_id": resp.slice_id,
                    "num_slices": resp.num_slices,
                    # reform trace context: the activated standby's
                    # world_join span links into the re-formation's trace
                    "trace": dict(resp.trace),
                }
            if resp.shutdown:
                return None
            time.sleep(poll_secs)
    finally:
        client.close()


def main(argv=None) -> int:
    args = parse_worker_args(argv)
    from elasticdl_tpu.data.recordio import ensure_native_codec
    from elasticdl_tpu.parallel.elastic import configure_compilation_cache

    ensure_native_codec()
    configure_compilation_cache(getattr(args, "compilation_cache_dir", ""))
    if getattr(args, "standby", 0):
        if not _standby_wait(args):
            return 0
    logger.info(
        "Worker %d connecting to master at %s",
        args.worker_id,
        args.master_addr,
    )
    coordinator_addr = getattr(args, "coordinator_addr", "") or ""
    # distributed tracing: a no-op unless the master exported
    # ELASTICDL_TPU_TELEMETRY_DIR; on a relaunched world the join span
    # links into the master's re-formation trace (assignment payload for
    # standbys, TRACE_PARENT env for cold spawns)
    from elasticdl_tpu.telemetry import tracing

    tracing.install_from_env(
        worker_id=args.worker_id,
        process_id=int(getattr(args, "process_id", 0) or 0),
        generation=int(getattr(args, "cluster_version", 0) or 0),
    )
    # transport-level network chaos (chaos/netem.py): a no-op unless the
    # master exported a fault plan with network faults targeting this
    # process/generation — armed BEFORE the client is built so the very
    # first RPC rides the shim'd seam
    from elasticdl_tpu.chaos import netem

    netem.install_from_env(
        process_id=int(getattr(args, "process_id", 0) or 0),
        cluster_version=int(getattr(args, "cluster_version", 0) or 0),
        worker_id=args.worker_id,
    )
    reform_parent = getattr(args, "trace", None) or tracing.parent_from_env()
    client = build_master_client(args.master_addr)
    try:
        if coordinator_addr:
            from elasticdl_tpu.parallel import elastic
            from elasticdl_tpu.worker.lockstep import LockstepWorker

            with tracing.trace_span(
                tracing.SPAN_WORLD_JOIN,
                trace_ctx=reform_parent,
                coordinator=coordinator_addr,
            ) as join_span:
                elastic.initialize_world(
                    coordinator_addr,
                    args.num_processes,
                    args.process_id,
                    platform=getattr(args, "jax_platform", "") or None,
                )
                # where this process runs, on the span and in the log:
                # the proof that each worker holds its own chip(s)
                import jax

                where = dict(
                    elastic.describe_devices(),
                    local_devices=jax.local_device_count(),
                )
                if join_span is not None:
                    join_span.set(**where)
                logger.info("Worker %d devices: %s", args.worker_id, where)
            tracing.flush()
            try:
                LockstepWorker(args, client).run()
            finally:
                elastic.shutdown_world()
        else:
            from elasticdl_tpu.parallel.elastic import configure_platform
            from elasticdl_tpu.worker.worker import Worker

            configure_platform(getattr(args, "jax_platform", "") or None)
            Worker(args, client).run()
    finally:
        tracing.flush()
        client.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
