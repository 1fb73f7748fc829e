"""Control-plane message types.

Reference: the protobuf messages in ``elasticdl/proto/elasticdl.proto``
(Task, GetTaskRequest, ReportTaskResultRequest, ReportEvaluationMetricsRequest,
ReportVersionRequest).  The TPU build represents them as plain dataclasses
serialized with msgpack; tensors ride as raw frames from
:mod:`elasticdl_tpu.utils.tensor` inside the msgpack map.  This keeps the
wire binary and schema'd without a protoc/grpc_tools build step.
"""

from __future__ import annotations

from dataclasses import asdict, dataclass, field

import msgpack

from elasticdl_tpu.utils.constants import TaskType
from elasticdl_tpu.utils.tensor import (
    Tensor,
    deserialize_tensors,
    serialize_tensors,
)


@dataclass
class GetTaskRequest:
    worker_id: int
    task_type: int = -1  # -1 = any; TaskType.EVALUATION for eval-only pulls
    # optional trace context ({"trace_id", "span_id"}); empty dict on old
    # payloads — decode() fills defaults, so the field is wire-compatible
    trace: dict = field(default_factory=dict)


@dataclass
class TaskResponse:
    """A leased task (or WAIT/empty sentinel).

    ``task_id == -1`` with ``type == WAIT`` means poll again later;
    ``task_id == -1`` with ``type == -1`` means the job is complete.
    """

    task_id: int = -1
    shard_name: str = ""
    start: int = 0
    end: int = 0
    type: int = -1
    model_version: int = -1
    minibatch_size: int = 0
    extended: dict = field(default_factory=dict)
    # trace context of the master's dispatch span: ONE task is ONE trace
    # across master and workers (telemetry/tracing.py); empty when the
    # master runs without tracing or on pre-trace payloads
    trace: dict = field(default_factory=dict)

    @property
    def is_wait(self) -> bool:
        return self.task_id == -1 and self.type == int(TaskType.WAIT)

    @property
    def is_empty(self) -> bool:
        return self.task_id == -1 and self.type == -1


@dataclass
class GetStepTaskRequest:
    """Lockstep task pull for multi-process SPMD training.

    All processes of one distributed world request the same monotonically
    increasing ``seq``; the master resolves each seq to ONE task exactly
    once and memoizes the answer, so every process sees an identical task
    stream (the lockstep invariant: the same jitted collectives run on
    every process).  ``cluster_version`` fences stale worlds after a mesh
    re-formation.
    """

    seq: int
    worker_id: int
    cluster_version: int = 0


@dataclass
class ReportTaskResultRequest:
    task_id: int
    err_message: str = ""
    exec_counters: dict = field(default_factory=dict)
    # the dispatch trace context echoed back for wire symmetry and
    # offline log joins; the master's own span bookkeeping is by task_id
    trace: dict = field(default_factory=dict)


@dataclass
class ReportVersionRequest:
    model_version: int
    worker_id: int = 0


@dataclass
class ReportEvaluationMetricsRequest:
    """Eval forward outputs + labels for master-side metric accumulation.

    Tensors are carried out-of-band as serialized frames so msgpack never
    sees large binary blobs it would copy.
    """

    model_outputs: dict = field(default_factory=dict)  # name -> Tensor
    labels: Tensor | None = None
    model_version: int = -1
    # lease guard: metrics are dropped unless this task is still actively
    # leased, so a reclaimed/retried eval task can't double-count
    task_id: int = -1
    # the step of the state the worker ACTUALLY evaluated with (may trail
    # or lead the milestone model_version; surfaced in the eval summary)
    evaluated_version: int = -1


@dataclass
class HeartbeatRequest:
    worker_id: int
    step: int = 0
    timestamp: float = 0.0
    # peer-replication advertisement (elasticdl_tpu.replication): the
    # worker's replica-server address plus the shards its RAM currently
    # holds ({"addr", "process_id", "generation", "holdings": [...]}).
    # Empty when replication is off; old payloads decode to {} so the
    # field is wire-compatible
    replica: dict = field(default_factory=dict)
    # client-side RPC outcome totals (rpc/stats.py): monotone counts of
    # retries / deadline_exceeded / unavailable since process start.
    # The heartbeat carries them BECAUSE it keeps flowing when task
    # reports stall — exactly when these spike.  Empty on a clean link;
    # old payloads decode to {} so the field is wire-compatible
    rpc: dict = field(default_factory=dict)
    # step-anatomy phase totals (telemetry/anatomy.py): monotone
    # per-phase {ms, count, buckets} the master mirrors onto the
    # elasticdl_step_phase_* metric families.  Empty when --step_anatomy
    # is off; old payloads decode to {} so the field is wire-compatible
    phases: dict = field(default_factory=dict)
    # device-prefetch staging totals (trainer/device_pipeline.py):
    # monotone {groups, stall_ms, stage_ms} the master mirrors onto the
    # elasticdl_device_prefetch_* counters.  Empty when
    # --device_prefetch is off; old payloads decode to {} so the field
    # is wire-compatible
    prefetch: dict = field(default_factory=dict)
    # memory-ledger snapshot (telemetry/memory.py): {"at": <sender wall
    # clock>, "current": {component: bytes}, "peak": {component:
    # bytes}}.  NON-monotone by nature (a swap releases, a queue
    # drains), so the master merges "current" with timestamped
    # last-writer-wins (utils/merge.last_merge_counters) and "peak"
    # with the usual max rule.  Empty when the ledger is off; old
    # payloads decode to {} so the field is wire-compatible
    memory: dict = field(default_factory=dict)


@dataclass
class HeartbeatResponse:
    accepted: bool = True
    # master may instruct the worker to quiesce for mesh re-formation
    should_quiesce: bool = False
    cluster_version: int = 0
    # process_id -> replica-server addr of the current generation (the
    # ring-push targets, from the master's replica directory); empty
    # when replication is off or peers have not advertised yet
    replica_peers: dict = field(default_factory=dict)
    # identity of the master PROCESS serving this response (non-empty
    # only when the journaled-HA control plane is on).  A worker that
    # sees the boot id CHANGE has outlived a master: it re-homes —
    # presents its generation and in-flight leases so the restarted
    # master reconciles accounting (master/journal.py).  Old payloads
    # decode to "" — wire-compatible
    boot_id: str = ""
    # on-demand profiler command (utils/profiling.py): {"window_id",
    # "num_steps", "out_dir", "seconds"} when a request_profile window is being
    # distributed; workers dedupe by window_id, so the master can keep
    # re-sending the latest command and every replay is absorbed.
    # Empty otherwise; old payloads decode to {} — wire-compatible
    profile: dict = field(default_factory=dict)


@dataclass
class RehomeRequest:
    """Worker -> restarted master: the re-homing handshake.

    ``lease_ids`` are the task leases this worker still holds in
    flight; ``cluster_version`` is the world generation it belongs to
    (the fence — a stale generation is rejected); ``pid`` lets a local
    master ADOPT the orphaned process (the previous master spawned it,
    so the restarted one holds no handle)."""

    worker_id: int
    cluster_version: int = 0
    pid: int = 0
    lease_ids: list = field(default_factory=list)


@dataclass
class RehomeResponse:
    # False = generation fence rejected the worker (stale world): it
    # must exit like any fenced worker
    accepted: bool = False
    cluster_version: int = 0
    boot_id: str = ""
    # the presented leases the master re-accepted; the worker must drop
    # any lease NOT in this list (its eventual report would be dropped
    # and the task re-trains from the queue exactly once)
    accepted_leases: list = field(default_factory=list)


@dataclass
class GetWorldAssignmentRequest:
    """Hot-standby poll: a pre-warmed worker (pod) asks whether it has
    been assigned a place in a (re-)formed world.  ``standby_id`` is the
    identity the instance manager addressed the assignment to (the pod
    name on k8s)."""

    standby_id: str


@dataclass
class WorldAssignmentResponse:
    has: bool = False
    # True once the job is shutting down: the standby exits cleanly
    shutdown: bool = False
    worker_id: int = 0
    coordinator_addr: str = ""
    num_processes: int = 1
    process_id: int = 0
    cluster_version: int = 0
    # slice coordinates of a multi-slice world (slice-granular
    # elasticity); defaults keep old payloads wire-compatible
    slice_id: int = 0
    num_slices: int = 1
    # reform trace context: the activated standby's world_join span links
    # into the master's re-formation trace
    trace: dict = field(default_factory=dict)


@dataclass
class PushReplicaRequest:
    """Ring-neighbor state push (worker -> worker, replica service).

    ``payload`` is one encoded state shard (:mod:`..replication.blob`);
    ``checksum`` lets the receiver detect a torn transfer and refuse to
    commit it; ``generation`` fences pushes from stale worlds.
    """

    source: int  # process index whose state shard this is
    version: int  # model version the shard was snapshotted at
    generation: int = 0
    checksum: str = ""
    payload: bytes = b""


@dataclass
class PushReplicaResponse:
    accepted: bool = False
    reason: str = ""


@dataclass
class FetchReplicaRequest:
    """Master-side harvest pull (master -> worker, replica service).
    ``probe=True`` returns metadata only (version/generation/checksum
    plus every retained version), so the harvester can pick a complete
    replica set before moving any payload bytes.  ``version=-1`` means
    the newest retained shard; a specific version fetches exactly that
    one (an older shard may be the only COMPLETE set left)."""

    source: int
    probe: bool = False
    version: int = -1


@dataclass
class FetchReplicaResponse:
    has: bool = False
    source: int = -1
    version: int = -1
    generation: int = -1
    checksum: str = ""
    payload: bytes = b""
    # every version the store retains for this source (probe responses;
    # the store keeps more than the advertised newest — see ReplicaStore)
    versions: list = field(default_factory=list)


# ---- serving plane (elasticdl_tpu/serving) ----------------------------------
#
# Feature/output trees ride as tensor frames like the eval-metrics
# payload: ``pack_array_tree``/``unpack_array_tree`` flatten a bare
# ndarray or a {name: ndarray} dict into the serialize_tensors form (a
# bare array travels under the reserved name below), so msgpack never
# copies large binary blobs.

BARE_ARRAY_KEY = "__bare__"


def pack_array_tree(tree) -> bytes:
    """Serialize a bare ndarray or a flat {name: ndarray} dict."""
    import numpy as np

    if isinstance(tree, dict):
        named = {
            str(k): Tensor(str(k), np.asarray(v)) for k, v in tree.items()
        }
    else:
        named = {BARE_ARRAY_KEY: Tensor(BARE_ARRAY_KEY, np.asarray(tree))}
    return serialize_tensors(named)


def unpack_array_tree(buf: bytes):
    """Inverse of :func:`pack_array_tree`."""
    tensors = deserialize_tensors(buf)
    if set(tensors) == {BARE_ARRAY_KEY}:
        return tensors[BARE_ARRAY_KEY].values
    return {name: t.values for name, t in tensors.items()}


@dataclass
class PredictRequest:
    """One inference request: ``rows`` rows of features (any row count —
    the replica's micro-batcher coalesces/splits them into the one
    canonical batch shape).  ``request_id`` is the client-chosen
    identity (router retries re-send the SAME id; predict is read-only
    so a re-delivery is harmless either way)."""

    request_id: str = ""
    features: bytes = b""  # pack_array_tree frames
    rows: int = 0
    # PR-3 trace context ({"trace_id", "span_id"}): the client's root
    # span, so the router's (re)route children and the replica's
    # queue/engine spans land in the SAME trace.  Empty when the client
    # does not trace; old payloads decode to {} — wire-compatible
    trace: dict = field(default_factory=dict)


@dataclass
class PredictResponse:
    outputs: bytes = b""  # pack_array_tree frames
    model_version: int = -1
    rows: int = 0
    # sum-exact per-request anatomy, ms keyed by serving phase name
    # (queue_wait/assemble/h2d_transfer/device_compute/d2h_transfer/
    # untracked) plus total_ms; empty on error responses
    phases: dict = field(default_factory=dict)
    # non-empty = the request failed (overload, shape mismatch, ...);
    # the error classes a client may retry are marked retryable=True
    error: str = ""
    retryable: bool = False


@dataclass
class ServingStatusRequest:
    """Replica/router status snapshot; doubles as the liveness probe."""

    detail: bool = False
    # trace context of the caller (probe beats usually omit it; an
    # operator's traced status read parents the replica's work).  Old
    # payloads decode to {} — wire-compatible
    trace: dict = field(default_factory=dict)


@dataclass
class ServingStatusResponse:
    replica_id: int = -1
    model_version: int = -1
    # process-wide XLA compile count (telemetry/compile_tracker): the
    # observable face of the serving compile-once guarantee — flat
    # across steady-state traffic, whatever the request-size mix
    compile_count: int = 0
    requests: int = 0
    rows: int = 0
    rejected: int = 0
    swaps: int = 0
    queue_rows: int = 0
    canonical_rows: int = 0
    # router responses: one status dict per live replica (detail=True)
    replicas: list = field(default_factory=list)
    # probe-beat telemetry fan-in (the PR-8/9 heartbeat pattern riding
    # the RPC that keeps flowing — here the liveness probe itself):
    # monotone request/error counters since process start.  The router
    # max-merges per replica (utils/merge.max_merge_counters), so
    # duplicated or reordered probe replies are absorbed.  Empty when
    # telemetry is off; old payloads decode to {} — wire-compatible
    counters: dict = field(default_factory=dict)
    # monotone per-phase {ms, count, buckets} serving-request totals in
    # the step-anatomy heartbeat shape (bucket keys stringified for
    # msgpack), max-merged per replica and fed to the router's SLO
    # watchdog.  Empty when telemetry is off; old payloads decode to {}
    phases: dict = field(default_factory=dict)
    # memory-ledger snapshot {"at", "current", "peak"} — NON-monotone,
    # merged last-writer-wins like the heartbeat field of the same
    # name.  Empty when the ledger is off; old payloads decode to {}
    memory: dict = field(default_factory=dict)


@dataclass
class SwapModelRequest:
    """Hot-swap the served model.  ``model_dir`` names an export
    directory (manifest + npz); ``min_version`` guards staleness — the
    replica refuses a swap that would not advance its version, which is
    what makes the method a safe versioned-put under re-delivery."""

    model_dir: str = ""
    min_version: int = -1
    # trace context of the operator's swap request: the router's
    # per-replica fan-out spans and every replica's model_swap span
    # parent into it, so one swap = one trace across the fleet.  Empty
    # when untraced; old payloads decode to {} — wire-compatible
    trace: dict = field(default_factory=dict)
    # live train->serve push (streaming subsystem): a non-empty
    # ``payload`` carries an encoded replica snapshot
    # (replication/blob.py) to swap from directly — no export dir, no
    # disk.  ``version`` stamps the swap (the versioned-put guard is
    # unchanged: a version <= the served one is refused as stale);
    # ``source`` labels provenance for the model_swap event; the two
    # watermarks ride along so the replica's swap telemetry carries the
    # freshness pair (trained-at-push vs source).  All default-valued,
    # so old payloads decode cleanly — wire-compatible
    payload: bytes = b""
    version: int = -1
    source: str = ""
    trained_watermark: int = -1
    source_watermark: int = -1


@dataclass
class SwapModelResponse:
    accepted: bool = False
    model_version: int = -1
    reason: str = ""
    # structured staleness marker: True when the refusal means "already
    # at/past this version" — the absorbed-replay case of the
    # versioned-put contract.  A FIELD, not a reason-string prefix, so
    # the router's convergence logic cannot be broken by rewording
    stale: bool = False
    # router fan-out: per-replica outcomes
    replicas: list = field(default_factory=list)


@dataclass
class RequestProfileRequest:
    """Arm an on-demand XLA profiler window on the running job: the
    master rides the command down on every heartbeat response until the
    distribution TTL lapses, and each worker opens one
    ``num_steps``-step capture into its telemetry dir (or ``out_dir``
    when given); ``seconds`` > 0 sizes the window by the clock instead
    (old payloads decode to 0 — wire-compatible).  Arming while a window is already being distributed is
    ABSORBED (the response carries the existing window id) — that is
    what makes a re-delivered arm safe to retry."""

    num_steps: int = 5
    out_dir: str = ""
    seconds: float = 0.0


@dataclass
class RequestProfileResponse:
    accepted: bool = False
    window_id: int = 0
    reason: str = ""


@dataclass
class GetRestoreStateRequest:
    """A re-formed world asks the master for the harvested in-memory
    replica set.  ``cluster_version`` fences the stage: only the
    generation the harvest was staged FOR may restore from it."""

    cluster_version: int
    process_id: int = 0


@dataclass
class RestoreStateResponse:
    has: bool = False
    version: int = -1
    checksum: str = ""
    payload: bytes = b""


_SIMPLE_TYPES = {
    "GetTaskRequest": GetTaskRequest,
    "GetStepTaskRequest": GetStepTaskRequest,
    "TaskResponse": TaskResponse,
    "ReportTaskResultRequest": ReportTaskResultRequest,
    "ReportVersionRequest": ReportVersionRequest,
    "HeartbeatRequest": HeartbeatRequest,
    "HeartbeatResponse": HeartbeatResponse,
    "RehomeRequest": RehomeRequest,
    "RehomeResponse": RehomeResponse,
    "GetWorldAssignmentRequest": GetWorldAssignmentRequest,
    "WorldAssignmentResponse": WorldAssignmentResponse,
    "PushReplicaRequest": PushReplicaRequest,
    "PushReplicaResponse": PushReplicaResponse,
    "FetchReplicaRequest": FetchReplicaRequest,
    "FetchReplicaResponse": FetchReplicaResponse,
    "GetRestoreStateRequest": GetRestoreStateRequest,
    "RestoreStateResponse": RestoreStateResponse,
    "RequestProfileRequest": RequestProfileRequest,
    "RequestProfileResponse": RequestProfileResponse,
    "PredictRequest": PredictRequest,
    "PredictResponse": PredictResponse,
    "ServingStatusRequest": ServingStatusRequest,
    "ServingStatusResponse": ServingStatusResponse,
    "SwapModelRequest": SwapModelRequest,
    "SwapModelResponse": SwapModelResponse,
}


def encode(msg) -> bytes:
    """Serialize any message dataclass to bytes."""
    kind = type(msg).__name__
    if kind == "ReportEvaluationMetricsRequest":
        payload = {
            "model_version": msg.model_version,
            "task_id": msg.task_id,
            "evaluated_version": msg.evaluated_version,
            "outputs": serialize_tensors(msg.model_outputs),
            "labels": b""
            if msg.labels is None
            else msg.labels.to_bytes(),
        }
    else:
        payload = asdict(msg)
    return msgpack.packb({"kind": kind, "body": payload}, use_bin_type=True)


def decode(buf: bytes):
    """Deserialize bytes back into the right message dataclass."""
    obj = msgpack.unpackb(buf, raw=False)
    kind, body = obj["kind"], obj["body"]
    if kind == "ReportEvaluationMetricsRequest":
        return ReportEvaluationMetricsRequest(
            model_outputs=deserialize_tensors(body["outputs"]),
            labels=Tensor.from_bytes(body["labels"])
            if body["labels"]
            else None,
            model_version=body["model_version"],
            task_id=body.get("task_id", -1),
            evaluated_version=body.get("evaluated_version", -1),
        )
    cls = _SIMPLE_TYPES.get(kind)
    if cls is None:
        raise ValueError(f"unknown message kind: {kind}")
    return cls(**body)


def task_to_response(
    task_id: int,
    task,
    model_version: int,
    minibatch_size: int,
    trace: dict | None = None,
) -> TaskResponse:
    return TaskResponse(
        task_id=task_id,
        shard_name=task.shard_name,
        start=task.start,
        end=task.end,
        type=int(task.type),
        model_version=task.model_version
        if task.type == TaskType.EVALUATION
        else model_version,
        minibatch_size=minibatch_size,
        extended=dict(task.extended),
        trace=dict(trace or {}),
    )
