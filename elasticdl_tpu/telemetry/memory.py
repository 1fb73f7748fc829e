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


def read_device_memory() -> dict:
    """Accelerator allocator stats summed over local devices:
    ``{"bytes_in_use", "peak_bytes_in_use", "bytes_limit"}`` (the limit
    is 0 where the allocator reports none), or ``{}`` on backends
    without allocator stats (CPU returns ``None`` from
    ``memory_stats()``) — the graceful-None contract.  The limit minus
    in-use is the headroom the device stager's admission control
    budgets against.

    Reads the backend this process ALREADY runs and never starts one:
    ``{}`` before any backend is initialized.  A chip belongs to one
    process at a time, so a master (its ledger samples at reform edges)
    or a waiting standby that initialized a backend here would take
    every chip of the host from the workers."""
    import jax
    from jax._src import xla_bridge

    if not xla_bridge.backends_are_initialized():
        return {}
    devices = jax.local_devices()
    in_use = peak = limit = 0
    found = False
    for device in devices:
        try:
            stats = device.memory_stats()
        except Exception:  # noqa: BLE001 — per-device stats are optional
            stats = None
        if not stats:
            continue
        found = True
        in_use += int(stats.get("bytes_in_use", 0) or 0)
        peak += int(
            stats.get("peak_bytes_in_use", stats.get("bytes_in_use", 0))
            or 0
        )
        limit += int(stats.get("bytes_limit", 0) or 0)
    if not found:
        return {}
    return {
        "bytes_in_use": in_use,
        "peak_bytes_in_use": peak,
        "bytes_limit": limit,
    }


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
