"""Multi-process SPMD runtime: world formation and host-data placement.

This is the TPU-native replacement for the reference's cross-worker data
plane (PS pull/push ``elasticdl/python/worker/worker.py:295-530``; FTLib
allreduce ``collective_ops/communicator.py:30-67``): N worker processes —
one per TPU host — join ONE ``jax.distributed`` world, build ONE global
mesh, and run the SAME jitted step in lockstep; gradient exchange is the
psum XLA derives from shardings, riding ICI (and DCN across slices).

Membership is master-owned (the reference's k8s watch equivalent): the
master assigns ``process_id``/``num_processes``/``coordinator_addr`` via
the argv round-trip and re-forms the world (new cluster_version, new
coordinator) when a worker dies — there is no gossip.

Worker liveness inside the world is the coordination service's concern;
liveness *of* the world is the master's (heartbeat timeouts).
"""

from __future__ import annotations

import os
import socket

import jax
import numpy as np

from elasticdl_tpu.utils.log_utils import default_logger as logger


def configure_platform(platform: str | None):
    """Pin the JAX platform (``--jax_platform``, or the CLI handing over
    ``JAX_PLATFORMS``) before any backend initializes."""
    if platform:
        jax.config.update("jax_platforms", platform)


# ---- persistent compilation cache ------------------------------------------

COMPILATION_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
# the fixed in-checkout default (git-ignored), resolved from the package
# location: the directory is part of the cache key's environment, so it
# must be the same path for every process and every cwd
DEFAULT_COMPILATION_CACHE_DIR = os.path.join(
    os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ),
    ".jax_compilation_cache",
)


def resolve_compilation_cache_dir(flag_value: str | None = "") -> str | None:
    """Where this process keeps its persistent compile cache, as the
    directory the CODE must set — ``None`` when ``JAX_COMPILATION_CACHE_DIR``
    is exported: JAX's own reading of the environment then stands and no
    code path sets another.  Otherwise ``--compilation_cache_dir`` if
    given, else the fixed in-checkout default."""
    if os.environ.get(COMPILATION_CACHE_ENV):
        return None
    return flag_value or DEFAULT_COMPILATION_CACHE_DIR


def configure_compilation_cache(cache_dir: str | None = ""):
    """Enable the persistent XLA compilation cache — THE one place, called
    unconditionally by every process that compiles (CLI/Local, workers
    and standbys, serving replicas, chip_smoke.py): a re-formed
    world or a re-run of the same job loads its executables from disk
    instead of recompiling.  Worker children inherit the choice (the
    environment, the forwarded flag, or the same package-relative
    default).

    The program store (parallel/program_store.py) is switched on here
    too, in ``program_store/`` under the same directory: a trainer's init
    program and train step are then loaded ahead of trace and lower."""
    resolved = resolve_compilation_cache_dir(cache_dir)
    if resolved is not None:
        jax.config.update("jax_compilation_cache_dir", resolved)
    # cache every executable: the default thresholds skip exactly the
    # small programs a test-size job re-forms over
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    from elasticdl_tpu.parallel import program_store

    directory = jax.config.jax_compilation_cache_dir
    if directory and jax.config.jax_enable_compilation_cache:
        program_store.enable(
            os.path.join(directory, program_store.DIRECTORY_NAME)
        )
    else:
        program_store.disable()


# ---- one process per chip ---------------------------------------------------

# libtpu process grids (x, y, z) for N one-chip processes sharing ONE TPU
# host; sizes outside the table get a line, which libtpu may refuse (a
# 3-of-4 world is not a sub-rectangle of a 2x2 host)
_TPU_PROCESS_GRIDS = {1: (1, 1, 1), 2: (2, 1, 1), 4: (2, 2, 1), 8: (2, 4, 1)}
_TPU_PROCESS_BASE_PORT = 8476


def chip_binding_env(process_id: int, num_processes: int) -> dict[str, str]:
    """The libtpu environment binding process ``process_id`` of an
    ``num_processes``-process single-host world to exactly ONE chip — a
    pure function of the world coordinates the instance manager already
    assigns.  A TPU chip belongs to one process at a time: without this
    every local worker reaches for every chip of the host.  Inert on
    CPU (nothing reads these variables)."""
    if not 0 <= process_id < num_processes:
        raise ValueError(
            f"process_id {process_id} outside world of {num_processes}"
        )
    grid = _TPU_PROCESS_GRIDS.get(num_processes, (num_processes, 1, 1))
    return {
        "TPU_VISIBLE_CHIPS": str(process_id),
        "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_BOUNDS": ",".join(str(n) for n in grid),
        "TPU_PROCESS_ADDRESSES": ",".join(
            f"localhost:{_TPU_PROCESS_BASE_PORT + i}"
            for i in range(num_processes)
        ),
        "TPU_PROCESS_PORT": str(_TPU_PROCESS_BASE_PORT + process_id),
        "CLOUD_TPU_TASK_ID": str(process_id),
    }


def describe_devices(devices=None) -> dict:
    """``{"platform", "kind", "count"}`` of ``devices`` (default: every
    device of the default backend) — the line that proves WHERE a run
    happened."""
    devices = list(devices) if devices is not None else jax.devices()
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }


def initialize_world(
    coordinator_addr: str,
    num_processes: int,
    process_id: int,
    platform: str | None = None,
    timeout_secs: int = 60,
):
    """Join the job's ``jax.distributed`` world (process 0 additionally
    hosts the coordination service at ``coordinator_addr``)."""
    configure_platform(platform)
    if platform == "cpu":
        # cross-process CPU collectives need an explicit implementation.
        # Set ONLY here, between platform selection and distributed init:
        # jaxlib's gloo factory requires the distributed client, so a
        # single-process job (tests, LocalExecutor, the CLI) with this
        # config set cannot initialize the cpu backend at all.
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    # reform-phase span (telemetry/tracing.py, no-op when tracing is
    # off): the coordination-service handshake blocks until every peer
    # of the (re-)formed world arrives, so its duration IS the
    # world-formation term of reform downtime
    from elasticdl_tpu.telemetry.tracing import (
        SPAN_WORLD_INITIALIZE,
        trace_span,
    )

    with trace_span(
        SPAN_WORLD_INITIALIZE,
        num_processes=num_processes,
        process_id=process_id,
    ):
        jax.distributed.initialize(
            coordinator_address=coordinator_addr,
            num_processes=num_processes,
            process_id=process_id,
            initialization_timeout=timeout_secs,
            # membership is master-owned: every coordinate is explicit,
            # so JAX's cluster auto-detection (which on a TPU host asks
            # the cloud metadata server) has nothing to add
            cluster_detection_method="deactivate",
        )
    logger.info(
        "Joined distributed world: process %d/%d (coordinator %s)",
        process_id,
        num_processes,
        coordinator_addr,
    )


def shutdown_world():
    try:
        jax.distributed.shutdown()
    except Exception:  # noqa: BLE001 — peers may already be gone
        pass


def pick_coordinator_port() -> int:
    """A free TCP port for the next world's coordination service (each
    re-formation gets a fresh one: the old coordinator died with its
    process 0)."""
    with socket.socket() as s:
        s.bind(("", 0))
        return s.getsockname()[1]


# ---- host-data placement ---------------------------------------------------


def mesh_process_indices(mesh) -> list[int]:
    """Sorted process indices participating in the mesh."""
    return sorted({d.process_index for d in mesh.devices.flat})


def is_multiprocess_mesh(mesh) -> bool:
    """Mesh spans >1 process.  (Do NOT use ``jax.process_count()`` for
    this: it reports the default backend, which may be a single-process
    platform plugin even when the mesh's backend is multi-process.)"""
    return len(mesh_process_indices(mesh)) > 1


def my_process_index(mesh) -> int:
    """This process's index in the mesh's backend (NOT
    ``jax.process_index()``, which reads the default backend)."""
    return mesh.devices.flat[0].client.process_index()


def local_batch_ranges(
    sharding, global_shape: tuple, process_index: int
) -> list[tuple[int, int]]:
    """The ascending, de-duplicated dim-0 ``[start, stop)`` ranges of the
    global batch owned by ``process_index`` under ``sharding``.

    This is the contract of ``jax.make_array_from_process_local_data``:
    each process contributes its shards' rows in global index order.
    Deriving the ranges from ``devices_indices_map`` (instead of assuming
    process-contiguous layout) keeps placement correct for ANY device
    order the mesh builder chose — including ICI-topology-optimized
    orders on real pods.
    """
    ranges = set()
    for device, idx in sharding.devices_indices_map(global_shape).items():
        if device.process_index != process_index:
            continue
        sl = idx[0]
        start = sl.start if sl.start is not None else 0
        stop = sl.stop if sl.stop is not None else global_shape[0]
        ranges.add((start, stop))
    return sorted(ranges)


def state_checkpoint_parts(state, mesh, materialize_dense: bool = True):
    """Split the live device state into ``(dense, parts)`` for part-based
    checkpointing, driven by each array's ACTUAL sharding (the sharded
    analogue of ``trainer.state.state_to_checkpoint``):

    - fully-replicated leaves -> ``dense`` (read from the local replica,
      no communication; skipped entirely when ``materialize_dense`` is
      False — non-chief processes discard them, so they must not pay N-1
      device-to-host copies);
    - 2-D leaves range-sharded on dim 0 only (embedding tables, and any
      fsdp dim-0 shard) -> ``parts``: ``name -> (ids, rows)`` for the
      row ranges this process OWNS — when dp replicates a range across
      processes, only the lowest process index owning it writes it, so
      parts are disjoint and each host writes exactly its slice (a table
      larger than one host's RAM never materializes; reference
      per-PS-shard checkpointing, common/save_utils.py:100-116);
    - anything else sharded -> gathered collectively into ``dense``.

    Collective: every process of the mesh must call this at the same
    point (leaf classification is identical everywhere, so the gathers
    line up).
    """
    flat = flat_state_arrays(state)
    my_proc = my_process_index(mesh) if is_multiprocess_mesh(mesh) else None

    dense: dict = {}
    parts: dict = {}
    to_gather: dict = {}
    for name, arr in flat.items():
        if not isinstance(arr, jax.Array):
            if materialize_dense:
                dense[name] = np.asarray(arr)
            continue
        sharding = arr.sharding
        if sharding.is_fully_replicated:
            if materialize_dense:
                dense[name] = np.asarray(arr)
            continue
        if arr.ndim == 2 and _dim0_sharded_only(arr):
            owned = _owned_row_ranges(sharding, arr.shape, my_proc)
            ranges: dict[tuple[int, int], np.ndarray] = {}
            for shard in arr.addressable_shards:
                r = _dim0_range(shard.index, arr.shape)
                if r in owned:
                    ranges[r] = np.asarray(shard.data)
            ordered = sorted(ranges)
            if ordered:
                ids = np.concatenate(
                    [np.arange(lo, hi, dtype=np.int64) for lo, hi in ordered]
                )
                rows = np.concatenate([ranges[r] for r in ordered], axis=0)
            else:
                ids = np.zeros((0,), dtype=np.int64)
                rows = np.zeros((0, arr.shape[1]), dtype=arr.dtype)
            parts[name] = (ids, rows)
        else:
            to_gather[name] = arr
    if to_gather:
        gathered = replicate_to_hosts(to_gather, mesh)
        if materialize_dense:
            dense.update(gathered)
    return dense, parts


def _owned_row_ranges(sharding, shape, my_proc) -> set[tuple[int, int]]:
    """Dim-0 ranges this process WRITES: when dp replicates a range over
    several processes, the lowest process index owning it is the writer
    (deterministic, communication-free)."""
    if my_proc is None:
        # single-process mesh: everything addressable is owned
        return {
            _dim0_range(idx, shape)
            for idx in sharding.devices_indices_map(shape).values()
        }
    owner: dict[tuple[int, int], int] = {}
    for device, idx in sharding.devices_indices_map(shape).items():
        r = _dim0_range(idx, shape)
        prev = owner.get(r)
        if prev is None or device.process_index < prev:
            owner[r] = device.process_index
    return {r for r, proc in owner.items() if proc == my_proc}


def _dim0_range(idx, shape) -> tuple[int, int]:
    sl = idx[0]
    lo = sl.start if sl.start is not None else 0
    hi = sl.stop if sl.stop is not None else shape[0]
    return (lo, hi)


def local_table_row_ranges(state, mesh) -> dict:
    """Per-table dim-0 row ranges this process's devices hold — the keep
    filter a restore passes to ``save_utils.restore_checkpoint`` so no
    host ever accumulates a whole distributed table."""
    proc = my_process_index(mesh)
    out = {}
    for name, arr in flat_state_arrays(state).items():
        if (
            isinstance(arr, jax.Array)
            and arr.ndim == 2
            and not arr.sharding.is_fully_replicated
            and _dim0_sharded_only(arr)
        ):
            out[name] = local_batch_ranges(arr.sharding, arr.shape, proc)
    return out


def flat_state_arrays(state) -> dict:
    """Checkpoint-named flat view of the state's restorable leaves
    (``params/...`` + mutable collections), KEEPING device arrays as-is
    (tree_to_dict would device_get sharded arrays whole, which is exactly
    what part-based checkpointing exists to avoid)."""
    flat = {
        f"params/{k}": v for k, v in _flat_arrays(state.params).items()
    }
    if state.model_state:
        flat.update(_flat_arrays(state.model_state))
    return flat


def _flat_arrays(tree) -> dict:
    from elasticdl_tpu.utils.tree_utils import _key_str

    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {
        "/".join(_key_str(k) for k in path): leaf for path, leaf in flat
    }


def dim0_split_only(sharding, shape) -> bool:
    """The layout splits only dim 0: every trailing dim is a full slice
    on every device.  Shared predicate for part-based checkpointing
    (2-D tables) and batch placement (dp/fsdp-only batch layouts)."""
    for idx in sharding.devices_indices_map(shape).values():
        for dim, sl in enumerate(idx[1:], start=1):
            if not (
                sl.start in (None, 0) and sl.stop in (None, shape[dim])
            ):
                return False
    return True


def _dim0_sharded_only(arr) -> bool:
    return dim0_split_only(arr.sharding, arr.shape)


def replicate_to_hosts(tree, mesh):
    """All-gather a (possibly sharded) device tree so every process holds
    the full values — the collective equivalent of ``device_get`` on a
    single-process mesh.  Used to materialize eval outputs and state for
    host-side reporting/export; runs on ALL processes (it is a collective
    program)."""
    from jax.sharding import NamedSharding, PartitionSpec

    replicated = NamedSharding(mesh, PartitionSpec())

    def _sharding_tree(t):
        return jax.tree_util.tree_map(lambda _: replicated, t)

    with mesh:
        gathered = jax.jit(
            lambda t: t, out_shardings=_sharding_tree(tree)
        )(tree)
    return jax.device_get(gathered)
