"""Component-level host/HBM memory ledger — bytes, attributed.

Every observability layer so far measures TIME (traces, step anatomy,
serving latency, fleet CPU budgets); the failure mode that actually
kills elastic TPU jobs is MEMORY: an autoscale grow, a hot model swap
(transiently double-resident leaves), or the ReplicaStore's
two-versions-per-source retention can walk a host into OOM with no
telemetry warning at all.  This module is the byte-side of the anatomy
discipline: long-lived byte owners register an accounting callback
under a stable component name, and the ledger samples them — plus
device memory via ``jax.Device.memory_stats()`` (``bytes_in_use`` /
``peak_bytes_in_use``; gracefully absent on CPU backends, which return
``None``) and host RSS from ``/proc/self/status`` — periodically (the
worker heartbeat cadence) and at phase edges (reform, model swap,
checkpoint, engine build).

Registered components (each registers itself at construction; the
names below are the single vocabulary site):

- ``model_state``      — trainer params/opt-state/model-state leaf bytes
- ``replica_store``    — retained replica shard payloads (2/source)
- ``device_stager``    — staged dispatch groups waiting on device
- ``task_prefetcher``  — decoded batches buffered by the host pipeline
- ``serving_queue``    — the micro-batcher's pending request rows
- ``serving_model``    — served model leaves (including the swap's
  transient double residency: old + incoming leaves both resident
  between placement and the state-pointer replace)
- ``master_journal``   — the control-plane journal's unflushed buffer

Honesty contract: the ledger does NOT claim sum-exactness the way step
anatomy does — allocators lie (arenas, fragmentation, the interpreter
and the XLA runtime themselves), so the residual between host RSS and
the tracked components is surfaced as an explicit ``unaccounted``
line with its own absolute-bytes budget
(``ELASTICDL_TPU_MEMORY_UNTRACKED_BUDGET_MB``) instead of being
hand-waved or forced to zero.  At toy-model scale the interpreter +
runtime dominate RSS, which is exactly why the budget is absolute
bytes, not a share (docs/designs/memory_ledger.md).

Wire/merge semantics: workers ship ``heartbeat_snapshot()`` on the
beat (``HeartbeatRequest.memory``).  Because memory goes DOWN as well
as up, the master merges current values with
``utils.merge.last_merge_counters`` (timestamped last-writer-wins) —
a max-merge would ratchet and never report a release — while the peak
watermark fields ARE max-merged (a peak is monotone).  The heartbeat
timestamp is the SENDER's wall clock (``time.time()``), comparable
across that worker's process lives.

Disabled cost: every module-level sample site is one global load and a
``None`` check (``# elastic-lint: hot-path``, machine-checked).
Component registration is construction-time, not hot; callbacks only
run when an installed ledger samples.
"""

from __future__ import annotations

import os
import threading
import time

from elasticdl_tpu.utils.log_utils import default_logger as logger

# ---- component vocabulary (one definition site) ------------------------------

COMPONENT_MODEL_STATE = "model_state"
COMPONENT_REPLICA_STORE = "replica_store"
COMPONENT_DEVICE_STAGER = "device_stager"
COMPONENT_TASK_PREFETCHER = "task_prefetcher"
COMPONENT_SERVING_QUEUE = "serving_queue"
COMPONENT_SERVING_MODEL = "serving_model"
COMPONENT_MASTER_JOURNAL = "master_journal"
# sharded embedding subsystem (elasticdl_tpu.embeddings): device-tier
# row shards this process holds, and the host-RAM spill tier's row
# stores + per-step minitable staging
COMPONENT_EMBEDDING_TABLE = "embedding_table"
COMPONENT_EMBEDDING_SPILL = "embedding_spill"

# pseudo-components carried in the same current/peak maps (so /metrics
# renders one elasticdl_memory_bytes family for everything byte-shaped)
KEY_HOST_RSS = "host_rss"
KEY_DEVICE_IN_USE = "device_bytes_in_use"

# the unaccounted-bytes budget (absolute, NOT a share: at toy-model
# scale interpreter + XLA runtime RSS dominates any model, so a share
# budget would be either vacuous or dishonest — see the design doc)
UNTRACKED_BUDGET_MB_ENV = "ELASTICDL_TPU_MEMORY_UNTRACKED_BUDGET_MB"
DEFAULT_UNTRACKED_BUDGET_MB = 8192

# host memory-pressure threshold: MemAvailable below this fraction of
# MemTotal emits a memory_pressure event (once per crossing)
PRESSURE_FRACTION_ENV = "ELASTICDL_TPU_MEMORY_PRESSURE_FRACTION"
DEFAULT_PRESSURE_FRACTION = 0.05


def untracked_budget_bytes() -> int:
    raw = os.environ.get(UNTRACKED_BUDGET_MB_ENV, "")
    try:
        mb = float(raw) if raw else DEFAULT_UNTRACKED_BUDGET_MB
    except ValueError:
        mb = DEFAULT_UNTRACKED_BUDGET_MB
    return int(mb * 1024 * 1024)


def pressure_fraction() -> float:
    raw = os.environ.get(PRESSURE_FRACTION_ENV, "")
    try:
        return float(raw) if raw else DEFAULT_PRESSURE_FRACTION
    except ValueError:
        return DEFAULT_PRESSURE_FRACTION


# ---- byte accounting helpers -------------------------------------------------


def pytree_bytes(tree) -> int:
    """Total leaf bytes of a pytree (numpy and jax arrays both carry
    ``nbytes``; leaves without it contribute 0 — scalars and None are
    not what OOMs a host)."""
    try:
        import jax

        leaves = jax.tree_util.tree_leaves(tree)
    except Exception:  # noqa: BLE001 — accounting must never raise
        return 0
    total = 0
    for leaf in leaves:
        total += int(getattr(leaf, "nbytes", 0) or 0)
    return total


def read_host_rss() -> int | None:
    """Resident set size of THIS process (``/proc/self/status`` VmRSS),
    bytes; None where /proc is unavailable."""
    try:
        with open("/proc/self/status", encoding="ascii") as f:
            for line in f:
                if line.startswith("VmRSS:"):
                    return int(line.split()[1]) * 1024
    except (OSError, ValueError, IndexError):
        return None
    return None


def _read_meminfo(field: str) -> int | None:
    try:
        with open("/proc/meminfo", encoding="ascii") as f:
            for line in f:
                if line.startswith(field + ":"):
                    return int(line.split()[1]) * 1024
    except (OSError, ValueError, IndexError):
        return None
    return None


def read_host_available() -> int | None:
    """Host-wide MemAvailable, bytes (the /healthz headroom source)."""
    return _read_meminfo("MemAvailable")


# MemTotal is constant for the machine's uptime: read it once so the
# per-sample pressure check costs no extra /proc parse (and none while
# holding the ledger lock).  The sentinel distinguishes "never read"
# from "read, unavailable" (non-Linux).
_host_total_cache: list = []


def read_host_total() -> int | None:
    if not _host_total_cache:
        _host_total_cache.append(_read_meminfo("MemTotal"))
    return _host_total_cache[0]


# the allocator's figures of one device, as ``Device.memory_stats()`` names
# them; the benchmark's ``peak_hbm_gb`` is ``peak_bytes_in_use +
# peak_bytes_reserved`` of the fullest device (live arrays, and what is
# reserved for the programs' temporaries and code)
ALLOCATOR_FIGURES = (
    "bytes_in_use", "peak_bytes_in_use", "bytes_reserved",
    "peak_bytes_reserved", "largest_free_block_bytes", "bytes_limit",
)


def read_device_memory() -> dict:
    """Accelerator allocator stats: ``{"bytes_in_use", "peak_bytes_in_use",
    "bytes_reserved", "peak_bytes_reserved", "bytes_limit"}`` summed over
    local devices (the limit is 0 where the allocator reports none) and
    ``"devices"``, each device's own :data:`ALLOCATOR_FIGURES` under its
    ``id``; or ``{}`` on backends without allocator stats (CPU returns
    ``None`` from ``memory_stats()``) — the graceful-None contract.
    :func:`device_headroom_bytes` is what the device stager's admission
    control budgets against.

    Reads the backend this process ALREADY runs and never starts one:
    ``{}`` before any backend is initialized.  A chip belongs to one
    process at a time, so a master (its ledger samples at reform edges)
    or a waiting standby that initialized a backend here would take
    every chip of the host from the workers."""
    import jax
    from jax._src import xla_bridge

    if not xla_bridge.backends_are_initialized():
        return {}
    devices = []
    for device in jax.local_devices():
        try:
            stats = device.memory_stats()
        except Exception:  # noqa: BLE001 — per-device stats are optional
            stats = None
        if not stats:
            continue
        figures = {
            name: int(stats.get(name, 0) or 0) for name in ALLOCATOR_FIGURES
        }
        for peak in ("peak_bytes_in_use", "peak_bytes_reserved"):
            # an allocator without a high-water mark: what it holds now
            figures[peak] = max(figures[peak], figures[peak[len("peak_"):]])
        devices.append({"id": int(device.id), **figures})
    if not devices:
        return {}
    summed = {
        name: sum(d[name] for d in devices)
        for name in ALLOCATOR_FIGURES
        if name != "largest_free_block_bytes"
    }
    return {**summed, "devices": devices}


def fullest_device(stats: dict) -> dict | None:
    """Of ``read_device_memory()``'s devices the one the benchmark's
    ``peak_hbm_gb`` reads: the largest ``peak_bytes_in_use +
    peak_bytes_reserved``."""
    return max(
        stats.get("devices", ()),
        key=lambda d: d["peak_bytes_in_use"] + d["peak_bytes_reserved"],
        default=None,
    )


def device_headroom_bytes() -> int | None:
    """What the fullest local device has left: its limit less its live
    arrays and less what is reserved for the programs' temporaries and
    code (2.7-8 GB of a 16 GB chip under a train step, which
    ``bytes_in_use`` does not count).  None without allocator stats or a
    limit."""
    left = [
        d["bytes_limit"] - d["bytes_in_use"] - d["bytes_reserved"]
        for d in read_device_memory().get("devices", ())
        if d["bytes_limit"] > 0
    ]
    return max(0, min(left)) if left else None


def host_memory_health() -> dict:
    """The /healthz headroom block: point-in-time host RSS, host-wide
    availability and the headroom share (None-safe on /proc-less
    platforms)."""
    rss = read_host_rss()
    available = read_host_available()
    total = read_host_total()
    return {
        "host_rss_bytes": rss,
        "host_available_bytes": available,
        "headroom_share": round(available / total, 4)
        if available is not None and total
        else None,
    }


# ---- the byte side of the train step, read on demand --------------------------
#
# What the device holds while the trainer's step runs: the state as the
# fullest device holds it, XLA's own sizes of each compiled train program
# with the reading of what is alive at its peak
# (``telemetry/op_scopes.py::live_bytes``), and the allocator's figures.
# Read when somebody asks (a benchmark's reader after its window,
# ``utils/profiling.py`` at a window's close), from the trainer
# ``op_scopes.watch`` remembers, and never on the train path.


def _on_device_bytes(array) -> int:
    try:
        return int(array.on_device_size_in_bytes())
    except Exception:  # noqa: BLE001 — a backend without the figure
        return int(getattr(array, "nbytes", 0) or 0)


def device_bytes(tree) -> dict[int, int]:
    """``{device id: bytes}`` of a pytree's arrays as each device holds
    them: its addressable shards at their size on the device, not the
    global ``nbytes`` (a replicated leaf stands whole on every device)."""
    import jax

    held: dict[int, int] = {}
    for leaf in jax.tree_util.tree_leaves(tree):
        for shard in getattr(leaf, "addressable_shards", ()):
            at = int(shard.device.id)
            held[at] = held.get(at, 0) + _on_device_bytes(shard.data)
    return held


def _state_on(device_id: int | None, state) -> dict:
    """The state's bytes on one device (the one that holds most of it where
    ``device_id`` is None): ``params``, ``opt_state``, each model buffer's
    collection, ``step``, and their ``total``."""
    groups = {
        "params": state.params,
        "opt_state": state.opt_state,
        "step": state.step,
        **dict(state.model_state),
    }
    held = {name: device_bytes(tree) for name, tree in groups.items()}
    if device_id is None:
        totals = device_bytes(groups)
        device_id = max(totals, key=totals.get, default=0)
    split = {name: by_device.get(device_id, 0) for name, by_device in held.items()}
    return {"device": device_id, **split, "total": sum(split.values())}


def read_step_memory() -> dict | None:
    """The byte side of the watched trainer's step; None without a trainer
    or before its first step.

    - ``programs``: per compiled train program ``op_scopes.live_bytes``:
      ``xla`` (its ``argument``, ``output``, ``alias``, ``temp``,
      ``generated_code`` and ``peak`` bytes a device) and what is alive at
      its peak by the model's scopes (``live``: None where the reading is
      off XLA's figure), the program with the most temporaries first;
    - ``state``: the train state on the fullest device, split (``params``,
      ``opt_state``, the model's buffers, ``total``);
    - ``undonated``: the state's leaves the first program writes no output
      in place of, ``[path, bytes]``: each stands twice while the step
      runs (``alias`` short of the state's bytes says the same in sum);
    - ``other_arrays``: what else is alive on that device now, batches
      placed and not yet retired and the last metrics;
    - ``allocator``: that device's :data:`ALLOCATOR_FIGURES` ({} on the
      CPU)."""
    import jax

    from elasticdl_tpu.telemetry import op_scopes

    trainer, programs = op_scopes.watched_programs()
    if trainer is None:
        return None
    read = [
        op_scopes.live_bytes(program) or {"xla": op_scopes.xla_sizes(program)}
        for program in programs
    ]
    read.sort(key=lambda p: -(p["xla"] or {}).get("temp", 0))
    allocator = fullest_device(read_device_memory())
    state = _state_on(allocator["id"] if allocator else None, trainer.state)
    # (a shard's own array is a live array too, of the same buffer: each
    # buffer once)
    alive: dict[int, int] = {}
    for array in jax.live_arrays():
        for shard in array.addressable_shards:
            if int(shard.device.id) == state["device"]:
                try:
                    buffer = shard.data.unsafe_buffer_pointer()
                except Exception:  # noqa: BLE001 — a backend without it
                    buffer = id(shard.data)
                alive[buffer] = _on_device_bytes(shard.data)
    return {
        "programs": read,
        "state": state,
        "undonated": read[0].get("undonated", []),
        "other_arrays": max(0, sum(alive.values()) - state["total"]),
        "allocator": allocator or {},
    }


def dump_step_memory(path: str) -> bool:
    """Write :func:`read_step_memory` to ``path``; False with nothing to
    write."""
    import json

    reading = read_step_memory()
    if reading is None:
        return False
    with open(path, "w") as f:
        json.dump(reading, f, separators=(",", ":"))
    return True


# ---- the ledger --------------------------------------------------------------

# component name -> zero-arg bytes callback.  Module-level so byte
# owners can register at construction BEFORE any ledger is installed
# (and independent of whether one ever is); re-registering a name
# replaces the callback (bench runs several configs per process).
_components: dict[str, object] = {}
_components_lock = threading.Lock()


def register_component(component: str, fn):
    """Register (or replace) a component's accounting callback.  ``fn``
    returns the component's CURRENT resident bytes; it must be cheap
    (attribute reads) and must never raise for correctness — a raising
    callback is skipped for that sample."""
    with _components_lock:
        _components[component] = fn


def unregister_component(component: str, fn=None):
    """Drop a component's callback.  Pass the registered callable as
    ``fn`` to make the removal identity-guarded: an owner being torn
    down AFTER a replacement registered under the same name (bench and
    the in-process harnesses build several owners per process) then
    leaves the newer registration alone."""
    with _components_lock:
        if fn is None or _components.get(component) is fn:
            _components.pop(component, None)


def register_trainer_state(get_state):
    """Register the ``model_state`` component from a zero-arg state
    getter (params + optimizer state + mutable collections — the
    trainer's whole carried pytree).  One definition site for the shape
    all three runtimes (local executor, task-stream worker, lockstep)
    register."""

    def _bytes():
        state = get_state()
        return pytree_bytes(state) if state is not None else 0

    register_component(COMPONENT_MODEL_STATE, _bytes)


class MemoryLedger:
    """Per-process byte ledger: samples the component registry, device
    allocator stats and host RSS; maintains current values and peak
    watermarks; emits ``memory_sample``/``memory_pressure`` events.

    ``emit`` is the event sink (``fn(event, **fields)``) — workers pass
    :func:`~elasticdl_tpu.telemetry.worker_hooks.emit_event`, the
    master passes its own event log's emit.  A None sink keeps the
    ledger usable for direct reads (tests, bench)."""

    def __init__(self, emit=None, clock=time.time):
        self._emit = emit
        self._clock = clock
        self._lock = threading.Lock()
        self._current: dict[str, int] = {}  # guarded-by: _lock
        self._peak: dict[str, int] = {}  # guarded-by: _lock
        self._stamp = 0.0  # guarded-by: _lock (writes)
        self._samples = 0  # guarded-by: _lock (writes)
        self._pressure_active = False  # guarded-by: _lock (writes)

    # ---- sampling ----------------------------------------------------------

    def sample(self, phase: str = "periodic") -> dict:
        """One full sample: run every registered callback, read device
        and host memory, roll peaks forward, and emit a
        ``memory_sample`` event.  Returns the sample dict (the report
        section's schema)."""
        with _components_lock:
            callbacks = list(_components.items())
        components: dict[str, int] = {}
        for name, fn in callbacks:
            try:
                value = int(fn())
            except Exception:  # noqa: BLE001 — a broken callback skips
                # its component for this sample, never breaks sampling
                continue
            if value >= 0:
                components[name] = value
        rss = read_host_rss()
        available = read_host_available()
        device = read_device_memory()
        tracked = sum(components.values())
        unaccounted = max(0, rss - tracked) if rss is not None else None
        with self._lock:
            self._samples += 1
            self._stamp = self._clock()
            # whole-map replacement: a component absent from this round
            # (unregistered owner) leaves the current view — the sample
            # IS the truth, matching the wire's last-writer-wins
            self._current = dict(components)
            if rss is not None:
                self._current[KEY_HOST_RSS] = rss
            if device:
                self._current[KEY_DEVICE_IN_USE] = device["bytes_in_use"]
            for name, value in self._current.items():
                if value > self._peak.get(name, 0):
                    self._peak[name] = value
            if device and device["peak_bytes_in_use"] > self._peak.get(
                KEY_DEVICE_IN_USE, 0
            ):
                # the allocator's own high-water mark outranks anything
                # a sampling cadence could have caught
                self._peak[KEY_DEVICE_IN_USE] = device["peak_bytes_in_use"]
            pressure = self._pressure_check_locked(available)
        out = {
            "phase": phase,
            "components": components,
            "tracked_bytes": tracked,
            "host_rss_bytes": rss,
            "host_available_bytes": available,
            "unaccounted_bytes": unaccounted,
        }
        if device:
            out["device_bytes_in_use"] = device["bytes_in_use"]
            out["device_peak_bytes_in_use"] = device["peak_bytes_in_use"]
        if self._emit is not None:
            from elasticdl_tpu.telemetry.events import EVENT_MEMORY_SAMPLE

            try:
                self._emit(EVENT_MEMORY_SAMPLE, **out)
            except Exception:  # noqa: BLE001 — telemetry never raises
                # into the sampling caller (heartbeat thread, swap path)
                logger.exception("Memory sample event emit failed")
        if pressure is not None:
            self._emit_pressure(pressure, available, rss)
        return out

    # lock-holding: _lock
    def _pressure_check_locked(self, available) -> bool | None:
        """Crossing detector: True = entered pressure, False = left it,
        None = no change (one event per crossing, not per sample)."""
        total = read_host_total()
        if available is None or not total:
            return None
        under = (available / total) < pressure_fraction()
        if under == self._pressure_active:
            return None
        self._pressure_active = under
        return under

    def _emit_pressure(self, entered: bool, available, rss):
        if self._emit is None:
            return
        from elasticdl_tpu.telemetry.events import EVENT_MEMORY_PRESSURE

        try:
            self._emit(
                EVENT_MEMORY_PRESSURE,
                entered=bool(entered),
                host_available_bytes=available,
                host_rss_bytes=rss,
            )
        except Exception:  # noqa: BLE001 — telemetry never raises
            logger.exception("Memory pressure event emit failed")

    # ---- reads -------------------------------------------------------------

    def snapshot(self) -> dict:
        """Current + peak maps (copies) — the /metrics mirror's read."""
        with self._lock:
            return {
                "current": dict(self._current),
                "peak": dict(self._peak),
            }

    def heartbeat_snapshot(self) -> dict:
        """The wire shape for ``HeartbeatRequest.memory``: ``{"at":
        <sender wall clock>, "current": {...}, "peak": {...}}``.  ``at``
        orders this worker's samples under the master's last-writer-wins
        merge; peaks merge monotone.  ``{}`` before the first sample so
        an idle worker ships nothing (wire-compatible old payloads)."""
        with self._lock:
            if not self._samples:
                return {}
            return {
                "at": self._stamp,
                "current": dict(self._current),
                "peak": dict(self._peak),
            }

    @property
    def samples(self) -> int:
        return self._samples


# ---- module-level install + zero-cost-when-disabled accessors ---------------

_active: MemoryLedger | None = None


def install(emit=None, clock=time.time) -> MemoryLedger:
    global _active
    _active = MemoryLedger(emit=emit, clock=clock)
    return _active


def install_if_enabled(telemetry_dir: str, emit=None) -> MemoryLedger | None:
    """Install when telemetry is configured (the ledger's surfaces —
    events, heartbeat field, report section — all hang off the
    telemetry dir); clears any stale ledger otherwise, so a
    telemetry-less runtime constructed after an instrumented one (bench
    runs several configs per process) does not inherit it."""
    if not telemetry_dir:
        uninstall()
        return None
    if emit is None:
        from elasticdl_tpu.telemetry import worker_hooks

        emit = worker_hooks.emit_event
    return install(emit=emit)


def install_from_env(emit=None) -> MemoryLedger | None:
    """Worker-subprocess entry: install only when the master exported
    the telemetry dir (the chaos-plan/anatomy env pattern)."""
    from elasticdl_tpu.telemetry.worker_hooks import TELEMETRY_DIR_ENV

    return install_if_enabled(
        os.environ.get(TELEMETRY_DIR_ENV, ""), emit=emit
    )


def uninstall():
    global _active
    _active = None


def get_ledger() -> MemoryLedger | None:  # elastic-lint: hot-path
    return _active


def sample(phase: str = "periodic"):  # elastic-lint: hot-path
    """THE sample site: one global load + None check when disabled."""
    ledger = _active
    if ledger is None:
        return None
    return ledger.sample(phase)


def heartbeat_snapshot() -> dict:  # elastic-lint: hot-path
    """Ledger state for ``HeartbeatRequest.memory``; ``{}`` when
    disabled (old payloads decode the same — wire-compatible)."""
    ledger = _active
    if ledger is None:
        return {}
    return ledger.heartbeat_snapshot()
