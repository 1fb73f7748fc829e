"""Memory observability plane: the component ledger, timestamped
last-writer-wins merging, the /metrics mirror + cardinality cap, the
report's memory and serving sections (with their no_data discipline),
and the on-demand request_profile round trip.

The merge pins mirror tests/test_fleetsim.py's max-merge properties:
reordered, duplicated and batched-then-replayed heartbeat sets must
produce IDENTICAL merged state — with the extra, defining property that
current values go DOWN when a newer-stamped sample says so, while peak
watermarks never decrease.
"""

from __future__ import annotations

import itertools
import json
import os
import random
import threading

import numpy as np
import pytest

from elasticdl_tpu.master.servicer import MasterServicer
from elasticdl_tpu.master.task_dispatcher import TaskDispatcher
from elasticdl_tpu.rpc import messages as msg
from elasticdl_tpu.telemetry import memory as memory_mod
from elasticdl_tpu.telemetry.memory import (
    COMPONENT_MODEL_STATE,
    MemoryLedger,
    pytree_bytes,
    register_component,
    unregister_component,
)
from elasticdl_tpu.utils.merge import last_merge_counters, max_merge_counters


@pytest.fixture(autouse=True)
def _clean_registry():
    """Each test starts from an empty component registry and no
    installed ledger (module-global state)."""
    with memory_mod._components_lock:
        saved = dict(memory_mod._components)
        memory_mod._components.clear()
    memory_mod.uninstall()
    yield
    with memory_mod._components_lock:
        memory_mod._components.clear()
        memory_mod._components.update(saved)
    memory_mod.uninstall()


def _dispatcher():
    return TaskDispatcher(
        {"shard": (0, 64)}, records_per_task=64, num_epochs=1
    )


# ---- last_merge_counters properties -----------------------------------------


def test_last_merge_newest_stamp_wins_and_goes_down():
    merged, stamps, totals = {}, {}, {}
    last_merge_counters(merged, {"m": 100}, 1.0, stamps, totals=totals)
    assert merged == {"m": 100} and totals == {"m": 100}
    # newer stamp, LOWER value: applied (the release a max-merge
    # ratchet could never report)
    last_merge_counters(merged, {"m": 40}, 2.0, stamps, totals=totals)
    assert merged == {"m": 40} and totals == {"m": 40}
    # older stamp, higher value: dropped
    last_merge_counters(merged, {"m": 999}, 1.5, stamps, totals=totals)
    assert merged == {"m": 40} and totals == {"m": 40}


def test_last_merge_malformed_values_skipped():
    merged, stamps = {}, {}
    last_merge_counters(
        merged, {"ok": 5, "bad": "not-an-int", "none": None}, 1.0, stamps
    )
    assert merged == {"ok": 5}


def test_last_merge_order_insensitive_permutations():
    """Every delivery order of the same sample set converges to the
    same merged state and the same aggregate."""
    samples = [
        (1.0, {"a": 10, "b": 5}),
        (2.0, {"a": 7}),
        (3.0, {"a": 12, "b": 2}),
    ]
    expected_state = None
    for perm in itertools.permutations(samples):
        merged, stamps, totals = {}, {}, {}
        for at, update in perm:
            last_merge_counters(merged, update, at, stamps, totals=totals)
        if expected_state is None:
            expected_state = (dict(merged), dict(totals))
        assert (merged, totals) == (
            expected_state[0],
            expected_state[1],
        ), f"order {perm} diverged"
    assert expected_state[0] == {"a": 12, "b": 2}


def test_last_merge_duplicated_and_batch_replayed_sets_identical():
    rng = random.Random(7)
    samples = [
        (float(i), {"x": rng.randrange(1000), "y": rng.randrange(1000)})
        for i in range(20)
    ]
    # reference: in-order, once each
    ref_m, ref_s, ref_t = {}, {}, {}
    for at, update in samples:
        last_merge_counters(ref_m, update, at, ref_s, totals=ref_t)
    # duplicated + shuffled + whole-set replayed afterwards
    stream = samples * 2
    rng.shuffle(stream)
    stream += samples
    got_m, got_s, got_t = {}, {}, {}
    for at, update in stream:
        last_merge_counters(got_m, update, at, got_s, totals=got_t)
    assert got_m == ref_m
    assert got_t == ref_t


def test_last_merge_equal_stamp_ties_are_deterministic():
    a = ({"k": 3}, {"k": 9})
    for first, second in (a, a[::-1]):
        merged, stamps = {}, {}
        last_merge_counters(merged, first, 5.0, stamps)
        last_merge_counters(merged, second, 5.0, stamps)
        assert merged == {"k": 9}


def test_last_merge_complete_snapshot_deletes_absent_keys():
    """complete=True declares the update a WHOLE snapshot: a key the
    newest snapshot no longer carries was released at the source (its
    owner unregistered) and must leave the merged view — and its total
    — instead of ratcheting at its last nonzero reading."""
    merged, stamps, totals = {}, {}, {}
    last_merge_counters(
        merged, {"q": 50, "m": 10}, 1.0, stamps, totals=totals,
        complete=True,
    )
    last_merge_counters(
        merged, {"m": 12}, 2.0, stamps, totals=totals, complete=True
    )
    assert merged == {"m": 12}
    assert totals == {"m": 12}


def test_last_merge_complete_snapshot_stale_cannot_readd():
    """A reordered STALE snapshot must not re-add a key a newer
    snapshot deleted — the newest complete stamp is a floor, so every
    delivery order of the same snapshot set converges."""
    snapshots = [
        (1.0, {"q": 50}),
        (2.0, {}),  # q's owner unregistered
        (3.0, {"m": 7}),
    ]
    reference = None
    for perm in itertools.permutations(snapshots):
        merged, stamps, totals = {}, {}, {}
        for at, update in perm:
            last_merge_counters(
                merged, update, at, stamps, totals=totals, complete=True
            )
        if reference is None:
            reference = (dict(merged), dict(totals))
        assert (merged, totals) == reference, f"order {perm} diverged"
    assert reference[0] == {"m": 7}
    assert reference[1] == {"m": 7}


def test_peaks_never_decrease_under_any_order():
    rng = random.Random(3)
    samples = [{"p": rng.randrange(100)} for _ in range(30)]
    expected = max(s["p"] for s in samples)
    for _ in range(5):
        rng.shuffle(samples)
        merged: dict = {}
        running_max = 0
        for update in samples:
            max_merge_counters(merged, update)
            assert merged["p"] >= running_max
            running_max = merged["p"]
        assert merged["p"] == expected


# ---- the ledger --------------------------------------------------------------


def test_pytree_bytes_counts_leaves():
    tree = {
        "a": np.zeros((4, 4), np.float32),
        "b": [np.zeros(10, np.int64), None, 3],
    }
    assert pytree_bytes(tree) == 4 * 4 * 4 + 10 * 8


def test_ledger_samples_components_and_peaks():
    register_component("thing", lambda: 100)
    ledger = MemoryLedger()
    snap = ledger.sample("test")
    assert snap["components"]["thing"] == 100
    register_component("thing", lambda: 40)  # replace: memory released
    ledger.sample("test")
    state = ledger.snapshot()
    assert state["current"]["thing"] == 40
    assert state["peak"]["thing"] == 100  # the watermark survives


def test_ledger_broken_callback_skipped():
    register_component("ok", lambda: 7)
    register_component("broken", lambda: 1 / 0)
    ledger = MemoryLedger()
    snap = ledger.sample()
    assert snap["components"] == {"ok": 7}


def test_ledger_heartbeat_snapshot_shape_and_empty_before_sample():
    ledger = MemoryLedger(clock=lambda: 42.0)
    assert ledger.heartbeat_snapshot() == {}
    register_component("c", lambda: 5)
    ledger.sample()
    snap = ledger.heartbeat_snapshot()
    assert snap["at"] == 42.0
    assert snap["current"]["c"] == 5
    assert snap["peak"]["c"] == 5
    # host RSS rides as a pseudo-component on Linux
    if memory_mod.read_host_rss() is not None:
        assert snap["current"][memory_mod.KEY_HOST_RSS] > 0


def test_ledger_emits_sample_events():
    events = []
    register_component("c", lambda: 11)
    ledger = MemoryLedger(emit=lambda name, **f: events.append((name, f)))
    ledger.sample("swap_test")
    assert events and events[0][0] == "memory_sample"
    assert events[0][1]["phase"] == "swap_test"
    assert events[0][1]["components"] == {"c": 11}
    assert events[0][1]["tracked_bytes"] == 11


def test_module_gates_are_noops_when_uninstalled():
    assert memory_mod.sample() is None
    assert memory_mod.heartbeat_snapshot() == {}
    assert memory_mod.get_ledger() is None


def test_unregister_component_identity_guard():
    """An owner torn down AFTER a replacement registered the same name
    must not drop the newer registration (bench and the in-process
    harnesses build several owners per process); an unguarded
    unregister still removes unconditionally."""
    old_cb, new_cb = (lambda: 1), (lambda: 2)
    register_component("x", old_cb)
    register_component("x", new_cb)  # replacement
    unregister_component("x", old_cb)  # stale owner's teardown
    with memory_mod._components_lock:
        assert memory_mod._components["x"] is new_cb
    unregister_component("x")  # unguarded: removes whatever is there
    with memory_mod._components_lock:
        assert "x" not in memory_mod._components


def test_serving_entrypoint_installs_ledger(tmp_path):
    """The serving CLI's telemetry install must include the memory
    ledger: without it every engine/batcher sample site is a no-op and
    the swap double-residency instrumentation is inert in the real
    serving path (the smoke installs in-process, which masked this)."""
    import types

    from elasticdl_tpu.serving.main import _install_telemetry
    from elasticdl_tpu.telemetry import tracing, worker_hooks

    args = types.SimpleNamespace(telemetry_dir=str(tmp_path))
    try:
        _install_telemetry(args)
        assert memory_mod.get_ledger() is not None
    finally:
        worker_hooks.uninstall()
        tracing.uninstall()
        memory_mod.uninstall()
    # and a telemetry-less serving process installs nothing
    args = types.SimpleNamespace(telemetry_dir="")
    os.environ.pop(worker_hooks.TELEMETRY_DIR_ENV, None)
    try:
        _install_telemetry(args)
        assert memory_mod.get_ledger() is None
    finally:
        worker_hooks.uninstall()
        tracing.uninstall()
        memory_mod.uninstall()


def test_register_trainer_state_none_safe():
    memory_mod.register_trainer_state(lambda: None)
    ledger = memory_mod.install()
    assert ledger.sample()["components"][COMPONENT_MODEL_STATE] == 0


# ---- servicer merge end to end ----------------------------------------------


def _beat(wid, at, current, peak):
    return msg.HeartbeatRequest(
        worker_id=wid,
        memory={"at": at, "current": current, "peak": peak},
    )


def test_servicer_memory_merge_order_insensitive_and_non_monotone():
    beats = [
        _beat(1, 1.0, {"model_state": 100}, {"model_state": 100}),
        _beat(1, 2.0, {"model_state": 250}, {"model_state": 250}),
        _beat(1, 3.0, {"model_state": 80}, {"model_state": 250}),
        _beat(2, 1.5, {"model_state": 60}, {"model_state": 60}),
    ]
    reference = None
    for perm in itertools.permutations(beats):
        servicer = MasterServicer(64, _dispatcher())
        for beat in perm:
            servicer.heartbeat(beat)
            # duplicate delivery too
            servicer.heartbeat(beat)
        totals = servicer.memory_stats_totals()
        if reference is None:
            reference = totals
        assert totals == reference
    # worker 1's newest sample says 80 (released from its 250 peak):
    # current reflects the release, peak keeps the watermark
    assert reference["current"]["model_state"] == 80 + 60
    assert reference["peak"]["model_state"] == 250 + 60


def test_servicer_memory_release_by_absence():
    """A component the newest beat no longer ships (its owner
    unregistered — a closed stager, a drained queue) leaves the fleet
    CURRENT gauge; its peak watermark stays."""
    servicer = MasterServicer(64, _dispatcher())
    servicer.heartbeat(
        _beat(
            1,
            1.0,
            {"model_state": 100, "device_stager": 30},
            {"model_state": 100, "device_stager": 30},
        )
    )
    servicer.heartbeat(
        _beat(1, 2.0, {"model_state": 90}, {"model_state": 100})
    )
    totals = servicer.memory_stats_totals()
    assert totals["current"] == {"model_state": 90}
    assert totals["peak"] == {
        "model_state": 100,
        "device_stager": 30,
    }


def test_servicer_memory_malformed_payload_tolerated():
    servicer = MasterServicer(64, _dispatcher())
    servicer.heartbeat(
        msg.HeartbeatRequest(worker_id=1, memory={"at": "nope"})
    )
    servicer.heartbeat(
        msg.HeartbeatRequest(
            worker_id=1, memory={"at": 1.0, "current": "bad", "peak": []}
        )
    )
    assert servicer.memory_stats_totals() == {"current": {}, "peak": {}}


def test_heartbeat_memory_field_wire_roundtrip():
    request = _beat(3, 9.5, {"a": 1}, {"a": 2})
    decoded = msg.decode(msg.encode(request))
    assert decoded.memory == {
        "at": 9.5,
        "current": {"a": 1},
        "peak": {"a": 2},
    }
    # old payloads (no memory key) decode to the default
    old = msg.decode(msg.encode(msg.HeartbeatRequest(worker_id=1)))
    assert old.memory == {}


def test_forget_worker_retires_current_bytes_keeps_peaks():
    """An evicted worker's RAM died with its process: the CURRENT fleet
    gauge must drop its contribution (else preemption churn ratchets the
    gauge upward forever), while the peak watermark — which happened —
    survives, and a REUSED worker id re-contributes without
    double-counting."""
    servicer = MasterServicer(64, _dispatcher())
    servicer.heartbeat(_beat(1, 1.0, {"model_state": 100}, {"model_state": 100}))
    servicer.heartbeat(_beat(2, 1.0, {"model_state": 40}, {"model_state": 40}))
    servicer.forget_worker(1)
    totals = servicer.memory_stats_totals()
    assert totals["current"] == {"model_state": 40}
    assert totals["peak"] == {"model_state": 140}
    # the reform-replacement worker reuses id 1: its fresh beat
    # re-contributes current; its (smaller) peak is absorbed by the
    # retained per-worker watermark — no double count
    servicer.heartbeat(_beat(1, 2.0, {"model_state": 70}, {"model_state": 70}))
    totals = servicer.memory_stats_totals()
    assert totals["current"] == {"model_state": 110}
    assert totals["peak"] == {"model_state": 140}


def test_healthz_fleet_tracked_excludes_pseudo_components(tmp_path):
    """host_rss/device pseudo-keys ride the wire maps but are NOT
    tracked components: summing them into fleet_tracked_bytes would
    double-count each worker's whole RSS."""
    servicer = MasterServicer(64, _dispatcher())
    telemetry = _master_telemetry(tmp_path, servicer)
    servicer.heartbeat(
        _beat(
            1,
            1.0,
            {
                "model_state": 64,
                memory_mod.KEY_HOST_RSS: 10_000,
                memory_mod.KEY_DEVICE_IN_USE: 5_000,
            },
            {},
        )
    )
    health = telemetry.build_health_fn("training")()
    assert health["memory"]["fleet_tracked_bytes"] == 64


# ---- registry: prune + gauge semantics (the satellite fix pins) -------------


def test_prune_then_reseen_child_reregisters_cleanly():
    from elasticdl_tpu.telemetry.registry import MetricsRegistry

    registry = MetricsRegistry()
    gauge = registry.gauge("g_family", "help", labels={"x": "1"})
    gauge.set(5)
    dropped = registry.prune_children("g_family", [])
    assert dropped == 1
    assert 'g_family{x="1"}' not in registry.exposition()
    # re-seen after the prune: a FRESH child, registered cleanly
    again = registry.gauge("g_family", "help", labels={"x": "1"})
    assert again is not gauge
    again.set(9)
    assert 'g_family{x="1"} 9' in registry.exposition()


def test_gauge_is_exempt_from_monotone_mirroring():
    """Gauges are non-monotone by design: set() lowers the exposed
    value — exactly what the memory ledger's current series needs —
    while Counter.set_total stays a monotone mirror (never lowers)."""
    from elasticdl_tpu.telemetry.registry import MetricsRegistry

    registry = MetricsRegistry()
    gauge = registry.gauge("mem_g", "")
    gauge.set(100)
    gauge.set(40)
    assert gauge.value == 40
    counter = registry.counter("mem_c_total", "")
    counter.set_total(100)
    counter.set_total(40)
    assert counter.value == 100


def test_gauge_family_kind_conflict_still_raises():
    from elasticdl_tpu.telemetry.registry import MetricsRegistry

    registry = MetricsRegistry()
    registry.gauge("fam", "")
    with pytest.raises(ValueError):
        registry.counter("fam", "")


# ---- /metrics mirror + cardinality cap --------------------------------------


def _master_telemetry(tmp_path, servicer):
    from elasticdl_tpu.telemetry.master_hooks import MasterTelemetry

    telemetry = MasterTelemetry(telemetry_dir=str(tmp_path / "tel"))
    telemetry.attach(_dispatcher(), servicer)
    return telemetry


def test_metrics_mirror_renders_memory_bytes_and_release(tmp_path):
    servicer = MasterServicer(64, _dispatcher())
    telemetry = _master_telemetry(tmp_path, servicer)
    servicer.heartbeat(
        _beat(1, 1.0, {"model_state": 500}, {"model_state": 500})
    )
    text = telemetry.registry.exposition()
    assert (
        'elasticdl_memory_bytes{component="model_state",kind="current"} 500'
        in text
    )
    assert (
        'elasticdl_memory_bytes{component="model_state",kind="peak"} 500'
        in text
    )
    # a newer-stamped LOWER sample lowers the current gauge (the
    # non-monotone path end to end) while the peak holds
    servicer.heartbeat(
        _beat(1, 2.0, {"model_state": 120}, {"model_state": 500})
    )
    text = telemetry.registry.exposition()
    assert (
        'elasticdl_memory_bytes{component="model_state",kind="current"} 120'
        in text
    )
    assert (
        'elasticdl_memory_bytes{component="model_state",kind="peak"} 500'
        in text
    )


def test_metrics_mirror_cardinality_cap_and_prune(tmp_path, monkeypatch):
    from elasticdl_tpu.telemetry import master_hooks

    monkeypatch.setenv(master_hooks.WORKER_SERIES_MAX_ENV, "4")
    servicer = MasterServicer(64, _dispatcher())
    telemetry = _master_telemetry(tmp_path, servicer)
    flood = {f"component_{i:03d}": 1000 - i for i in range(32)}
    servicer.heartbeat(_beat(1, 1.0, flood, flood))
    text = telemetry.registry.exposition()
    lines = [
        line
        for line in text.splitlines()
        if line.startswith("elasticdl_memory_bytes{")
    ]
    # at most budget series per kind (3 kept + 1 "other"), both kinds
    assert len(lines) <= 8, lines
    assert 'component="other"' in text
    # the biggest components survive individually
    assert 'component="component_000"' in text
    # a later scrape with a small honest set prunes the flood children
    servicer2 = MasterServicer(64, _dispatcher())
    telemetry2 = _master_telemetry(tmp_path, servicer2)
    servicer2.heartbeat(_beat(1, 1.0, {"model_state": 5}, {"model_state": 5}))
    text2 = telemetry2.registry.exposition()
    assert 'component="model_state"' in text2


def test_healthz_memory_headroom_block(tmp_path):
    servicer = MasterServicer(64, _dispatcher())
    telemetry = _master_telemetry(tmp_path, servicer)
    servicer.heartbeat(_beat(1, 1.0, {"model_state": 64}, {"model_state": 64}))
    health = telemetry.build_health_fn("training")()
    assert "memory" in health
    memory = health["memory"]
    assert memory["fleet_tracked_bytes"] == 64
    if memory_mod.read_host_rss() is not None:
        assert memory["host_rss_bytes"] > 0
        assert 0.0 <= memory["headroom_share"] <= 1.0


# ---- report sections ---------------------------------------------------------


def _event(name, monotonic, **fields):
    return {"event": name, "monotonic": monotonic, **fields}


def test_memory_section_aggregates_and_budget():
    events = [
        _event(
            "memory_sample",
            1.0,
            components={"model_state": 100, "replica_store": 10},
            host_rss_bytes=1000,
        ),
        _event(
            "memory_sample",
            2.0,
            components={"model_state": 60, "replica_store": 30},
            host_rss_bytes=900,
        ),
        _event("memory_pressure", 2.5, entered=True,
               host_available_bytes=123),
    ]
    from elasticdl_tpu.telemetry.report import memory_section

    section = memory_section(events)
    model = section["components"]["model_state"]
    assert model["current_bytes"] == 60  # last sample wins
    assert model["peak_bytes"] == 100  # watermark survives
    assert section["tracked_bytes"] == 90
    assert section["host_rss_bytes"] == 900
    assert section["host_rss_peak_bytes"] == 1000
    assert section["unaccounted_bytes"] == 810
    assert section["unaccounted_over_budget"] is False
    assert section["pressure_events"][0]["entered"] is True
    # per-component peak >= current always
    for slot in section["components"].values():
        assert slot["peak_bytes"] >= slot["current_bytes"]


def test_memory_section_groups_by_emitting_process():
    """Multi-worker runs write memory_sample events from several
    processes into one log; ``monotonic`` restarts per process, so the
    section must aggregate per (worker_id, process_id) group — each
    group's LAST sample, summed across groups — never interleave the
    incomparable clocks into one arbitrary worker's reading."""
    from elasticdl_tpu.telemetry.report import memory_section

    events = [
        # worker 0: its clock happens to read HIGHER than worker 1's
        _event(
            "memory_sample",
            900.0,
            worker_id=0,
            process_id=0,
            components={"model_state": 100},
            host_rss_bytes=1000,
        ),
        _event(
            "memory_sample",
            901.0,
            worker_id=0,
            process_id=0,
            components={"model_state": 80},
            host_rss_bytes=950,
        ),
        # worker 1: fresh process, clock restarted near zero — a global
        # monotonic sort would make ITS samples look oldest
        _event(
            "memory_sample",
            1.0,
            worker_id=1,
            process_id=1,
            components={"model_state": 70},
            host_rss_bytes=800,
        ),
        _event(
            "memory_sample",
            2.0,
            worker_id=1,
            process_id=1,
            components={"model_state": 60},
            host_rss_bytes=780,
        ),
    ]
    section = memory_section(events)
    model = section["components"]["model_state"]
    assert model["current_bytes"] == 80 + 60  # each group's last, summed
    assert model["peak_bytes"] == 100 + 70
    assert section["tracked_bytes"] == 140
    assert section["host_rss_bytes"] == 950 + 780
    assert section["host_rss_peak_bytes"] == 1000 + 800
    assert section["samples"] == 4


def test_memory_section_absent_without_samples():
    from elasticdl_tpu.telemetry.report import memory_section

    assert memory_section([]) is None
    assert memory_section([_event("step", 1.0)]) is None


def test_serving_section_aggregates_percentiles_sheds_and_swaps():
    from elasticdl_tpu.telemetry.report import serving_section

    events = []
    for i in range(10):
        events.append(
            _event(
                "serving_request",
                float(i),
                rows=2,
                dispatches=1,
                total_ms=float(i + 1),
                queue_wait_ms=0.1,
                device_compute_ms=float(i),
                untracked_ms=0.0,
            )
        )
    events.append(
        _event("serving_request", 11.0, rows=4, error="overload", shed=True)
    )
    events.append(
        _event("serving_request", 12.0, rows=1, error="ShapeMismatchError")
    )
    events.append(
        _event(
            "model_swap",
            13.0,
            old_version=3,
            model_version=7,
            swap_ms=2.5,
            source="in-memory",
        )
    )
    section = serving_section(events)
    assert section["requests"] == 10
    assert section["rows"] == 20
    assert section["sheds"] == 1
    assert section["errors"] == 1
    assert section["errors_by_kind"] == {
        "overload": 1,
        "ShapeMismatchError": 1,
    }
    assert section["latency_p50_ms"] == 5.0
    assert section["phases"]["device_compute"]["p99_ms"] == 9.0
    assert section["swaps"][0]["model_version"] == 7
    assert section["swaps"][0]["old_version"] == 3


def test_serving_section_absent_without_serving_events():
    from elasticdl_tpu.telemetry.report import serving_section

    assert serving_section([_event("step", 1.0)]) is None


def test_report_no_data_discipline_memory_and_serving(tmp_path):
    """Empty events file / rotated-shards-only dirs: rc 0 with an
    explicit no_data marker, the memory/serving sections absent — the
    PR-9 section discipline extended."""
    from elasticdl_tpu.telemetry.report import analyze_events, main

    run = analyze_events([], [])
    assert "no_data" in run
    assert "memory" not in run and "serving" not in run

    # an empty events.jsonl on disk: rc 0, report renders
    empty_dir = tmp_path / "empty"
    empty_dir.mkdir()
    (empty_dir / "events.jsonl").write_text("")
    assert main([str(empty_dir)]) == 0

    # only a rotated shard (the active file rotated away): the reader
    # walks shards, rc stays 0
    rotated_dir = tmp_path / "rotated"
    rotated_dir.mkdir()
    (rotated_dir / "events.jsonl.1").write_text(
        json.dumps({"event": "memory_sample", "monotonic": 1.0,
                    "components": {"model_state": 5}}) + "\n"
    )
    (rotated_dir / "events.jsonl").write_text("")
    assert main([str(rotated_dir), "--json"]) == 0
    from elasticdl_tpu.telemetry.events import read_events

    events = read_events(str(rotated_dir / "events.jsonl"))
    from elasticdl_tpu.telemetry.report import memory_section

    assert memory_section(events)["components"]["model_state"][
        "current_bytes"
    ] == 5


# ---- on-demand profiler ------------------------------------------------------


class _FakeJaxProfiler:
    def __init__(self, monkeypatch):
        import jax

        self.calls = []
        monkeypatch.setattr(
            jax.profiler,
            "start_trace",
            lambda d, **_options: self.calls.append(("start", d)),
        )
        monkeypatch.setattr(
            jax.profiler, "stop_trace", lambda: self.calls.append(("stop",))
        )


def test_profiler_flag_window_unchanged(monkeypatch, tmp_path):
    """The launch-flag path keeps its exact open/close call indices."""
    from elasticdl_tpu.utils.profiling import StepProfiler

    fake = _FakeJaxProfiler(monkeypatch)
    out = str(tmp_path / "p")
    profiler = StepProfiler(out, start_step=2, num_steps=3)
    opens = []
    for step in range(1, 11):
        profiler.on_step(step)
        if fake.calls and fake.calls[-1][0] == "start" and len(opens) == 0:
            opens.append(step)
    assert fake.calls[0] == ("start", out)
    assert opens == [3]  # opened at call 3 (past start_step=2)
    assert ("stop",) in fake.calls  # closed when seen > 5 (call 6)
    profiler.stop()
    assert fake.calls.count(("stop",)) == 1  # idempotent


def test_profiler_arm_opens_next_step_and_dedupes(monkeypatch, tmp_path):
    from elasticdl_tpu.utils.profiling import StepProfiler

    fake = _FakeJaxProfiler(monkeypatch)
    profiler = StepProfiler("")  # no flag window
    for _ in range(5):
        profiler.on_step()
    assert fake.calls == []  # idle: truly off
    out = str(tmp_path / "w1")
    assert profiler.arm(out, num_steps=2, window_id=1) is True
    # replayed command (the master re-sends every beat): absorbed
    assert profiler.arm(out, num_steps=2, window_id=1) is False
    profiler.on_step()  # opens
    assert fake.calls == [("start", out)]
    # arming DURING a window is refused without consuming the id
    assert profiler.arm(str(tmp_path / "w2"), window_id=2) is False
    profiler.on_step()  # second in-window step
    profiler.on_step()  # seen > stop_at: closes
    assert fake.calls[-1] == ("stop",)
    # window 2 retries after the close and now arms
    assert profiler.arm(str(tmp_path / "w2"), window_id=2) is True
    profiler.on_step()
    assert fake.calls[-1] == ("start", str(tmp_path / "w2"))
    profiler.stop()


def test_profiler_emits_window_events(monkeypatch, tmp_path):
    from elasticdl_tpu.telemetry import worker_hooks
    from elasticdl_tpu.utils.profiling import StepProfiler

    _FakeJaxProfiler(monkeypatch)
    worker_hooks.install(str(tmp_path / "tel"))
    try:
        profiler = StepProfiler("")
        profiler.arm(str(tmp_path / "w"), num_steps=1, window_id=5)
        profiler.on_step()
        profiler.on_step()
        from elasticdl_tpu.telemetry.events import read_events

        events = read_events(str(tmp_path / "tel" / "events.jsonl"))
        names = [e["event"] for e in events]
        assert "profile_window_open" in names
        assert "profile_window_close" in names
        closed = next(
            e for e in events if e["event"] == "profile_window_close"
        )
        assert closed["window_id"] == 5
        assert closed["steps"] == 1
    finally:
        worker_hooks.uninstall()


def test_apply_profile_command_paths(monkeypatch, tmp_path):
    from elasticdl_tpu.utils.profiling import (
        StepProfiler,
        apply_profile_command,
    )

    _FakeJaxProfiler(monkeypatch)
    profiler = StepProfiler("")
    telemetry_dir = str(tmp_path / "tel")
    command = {"window_id": 1, "num_steps": 2, "out_dir": ""}
    assert apply_profile_command(
        profiler, command, telemetry_dir=telemetry_dir, tag="w0"
    )
    # replay: absorbed
    assert not apply_profile_command(
        profiler, command, telemetry_dir=telemetry_dir, tag="w0"
    )
    # no out_dir anywhere: refused
    assert not apply_profile_command(
        StepProfiler(""), {"window_id": 2, "num_steps": 1}
    )
    # malformed: refused, never raises
    assert not apply_profile_command(profiler, {})
    assert not apply_profile_command(profiler, {"window_id": "x"})


def test_servicer_request_profile_absorbed_and_ttl():
    clock = [100.0]
    servicer = MasterServicer(64, _dispatcher(), clock=lambda: clock[0])
    first = servicer.request_profile(
        msg.RequestProfileRequest(num_steps=3)
    )
    assert first.accepted and first.window_id == 1
    # a re-delivered arm while the command distributes: same window
    dup = servicer.request_profile(msg.RequestProfileRequest(num_steps=3))
    assert dup.accepted and dup.window_id == 1
    # the command rides the heartbeat response
    resp = servicer.heartbeat(msg.HeartbeatRequest(worker_id=0))
    assert resp.profile == {
        "window_id": 1,
        "num_steps": 3,
        "out_dir": "",
    }
    # after the TTL the command stops riding and a new arm advances
    clock[0] += MasterServicer.PROFILE_COMMAND_TTL_SECS + 1
    assert servicer.heartbeat(msg.HeartbeatRequest(worker_id=0)).profile == {}
    nxt = servicer.request_profile(msg.RequestProfileRequest())
    assert nxt.window_id == 2


def test_request_profile_wire_roundtrip_and_method_table():
    decoded = msg.decode(
        msg.encode(msg.RequestProfileRequest(num_steps=7, out_dir="/d"))
    )
    assert decoded.num_steps == 7 and decoded.out_dir == "/d"
    response = msg.decode(
        msg.encode(msg.RequestProfileResponse(accepted=True, window_id=4))
    )
    assert response.accepted and response.window_id == 4
    from elasticdl_tpu.rpc.idempotency import classification
    from elasticdl_tpu.rpc.service import _METHODS

    assert "request_profile" in _METHODS
    assert classification("request_profile") == "deduped"
    # old heartbeat responses decode without the profile field
    old = msg.decode(msg.encode(msg.HeartbeatResponse()))
    assert old.profile == {}


# ---- serving engine double residency ----------------------------------------


def test_engine_swap_records_double_residency(tmp_path):
    """A hot swap's ledger peak covers old + new leaves resident at
    once; after the swap the current drops back to one copy."""
    import jax
    import jax.numpy as jnp

    from elasticdl_tpu.serving.engine import ServingEngine
    from elasticdl_tpu.trainer.state import TrainState, init_model
    from elasticdl_tpu.trainer.step import resolve_optimizer
    from elasticdl_tpu.utils.export_utils import export_model
    from elasticdl_tpu.utils.model_utils import get_model_spec

    iris_def = "odps_iris_dnn_model.odps_iris_dnn_model.custom_model"
    spec = get_model_spec("", iris_def)
    model = spec.build_model()
    sample = {"features": np.zeros((1, 4), np.float32)}
    params, model_state = init_model(model, sample)
    state = TrainState.create(
        model.apply, params, resolve_optimizer(spec.optimizer), model_state
    )
    state = state.replace(step=jnp.asarray(3, jnp.int32))
    import argparse

    export_dir = export_model(
        str(tmp_path / "export"),
        state,
        spec,
        argparse.Namespace(
            model_zoo="", model_def=iris_def, model_params_dict={}
        ),
    )
    ledger = memory_mod.install()
    engine = ServingEngine(export_dir, canonical_rows=8)
    feats = {"features": np.zeros((2, 4), np.float32)}
    engine.predict_rows(feats)  # builds
    built = ledger.snapshot()["current"]["serving_model"]
    assert built > 0
    from elasticdl_tpu.trainer.state import state_to_checkpoint

    flat = state_to_checkpoint(state)
    flat_params = {
        k[len("params/"):]: np.asarray(v)
        for k, v in flat.items()
        if k.startswith("params/")
    }
    accepted, version, _reason = engine.swap_state_dicts(
        flat_params, {}, version=9
    )
    assert accepted and version == 9
    snap = ledger.snapshot()
    # the swap sample caught both copies resident; afterwards current
    # settles back to ~one copy (the release, observable)
    assert snap["peak"]["serving_model"] >= int(1.8 * built)
    assert snap["current"]["serving_model"] < snap["peak"]["serving_model"]
    jax.clear_caches()


# ---- the byte side of the train step, read on demand ---------------------------------


def _tiny_trainer(mesh="dp=4", **kwargs):
    import flax.linen as nn
    import jax.numpy as jnp
    import optax

    from elasticdl_tpu.parallel.distributed import SPMDTrainer
    from elasticdl_tpu.parallel.mesh import MeshConfig

    class Tiny(nn.Module):
        @nn.compact
        def __call__(self, features, training=False):
            return nn.Dense(16, name="head")(features["x"])

    features = {"x": np.ones((8, 32), np.float32)}
    trainer = SPMDTrainer(
        MeshConfig.from_string(mesh).create(), Tiny(),
        lambda labels, outputs: jnp.mean((outputs - labels) ** 2),
        optax.adam(0.1), features, **kwargs,
    )
    batch = (
        trainer.place_batch(features),
        trainer.place_batch(np.ones((8, 16), np.float32)),
        trainer.place_mask(8, 8),
    )
    return trainer, batch


def test_the_steps_bytes_are_not_read_before_a_step():
    trainer, batch = _tiny_trainer()
    assert memory_mod.read_step_memory() is None
    assert memory_mod.dump_step_memory(os.devnull) is False
    trainer.train_step(*batch)
    assert memory_mod.read_step_memory() is not None


def test_the_states_split_on_a_dp4_mesh_is_one_devices_share():
    """``gpt2s_seq1024_dp4`` replicates its parameters: a device holds the
    whole of them once, the four hold four times ``nbytes`` together, and a
    batch sharded over the mesh stands a quarter on each."""
    trainer, batch = _tiny_trainer()
    trainer.train_step(*batch)
    state = trainer.state
    kernel = 32 * 16 * 4 + 16 * 4
    by_device = memory_mod.device_bytes(state.params)
    assert len(by_device) == 4 and set(by_device.values()) == {kernel}
    assert pytree_bytes(state.params) == kernel  # the global array's, once
    assert set(memory_mod.device_bytes(batch[0]).values()) == {8 * 32 * 4 // 4}
    read = memory_mod.read_step_memory()
    split = read["state"]
    assert split["params"] == kernel
    assert split["opt_state"] == 2 * kernel + 4  # Adam's moments and count
    assert split["total"] == (
        split["params"] + split["opt_state"] + split["step"]
    )
    assert split["device"] in by_device
    # what else is alive on that device: its quarter of the batch at least,
    # and no buffer twice (a shard's own array is a live array too, and
    # reading the split has just made one for every leaf)
    assert 8 * 32 * 4 // 4 <= read["other_arrays"] < split["total"]
    assert memory_mod.read_step_memory()["other_arrays"] == read["other_arrays"]
    assert read["allocator"] == {}  # the CPU's allocator says nothing
    (program,) = read["programs"]
    assert program["xla"]["argument"] >= split["total"]


@pytest.mark.parametrize("donate", [True, False])
def test_a_state_leaf_the_step_does_not_donate_is_found(donate):
    trainer, batch = _tiny_trainer(mesh="dp=2", donate=donate)
    trainer.train_step(*batch)
    read = memory_mod.read_step_memory()
    paths = [path for path, _ in read["undonated"]]
    if donate:
        assert paths == []
        return
    assert "state.params['head']['kernel']" in paths
    assert "state.opt_state[0].mu['head']['kernel']" in paths
    assert all(size > 0 for _, size in read["undonated"])
    (program,) = read["programs"]
    assert program["xla"]["alias"] == 0  # the sum says the same


class _FakeDevice:
    def __init__(self, id_, stats):
        self.id, self._stats = id_, stats

    def memory_stats(self):
        return self._stats


GIB = 1 << 30


@pytest.mark.parametrize("devices,fullest,headroom", [
    # one chip under a train step: 6 GiB of arrays, 5 GiB reserved for the
    # step's temporaries and code, of 16
    ([{"bytes_in_use": 6 * GIB, "peak_bytes_in_use": 7 * GIB,
       "bytes_reserved": 5 * GIB, "peak_bytes_reserved": 5 * GIB,
       "largest_free_block_bytes": 4 * GIB, "bytes_limit": 16 * GIB}],
     0, 5 * GIB),
    # four chips: the fullest one decides, not the sum
    ([{"bytes_in_use": 2 * GIB, "bytes_reserved": GIB, "bytes_limit": 16 * GIB},
      {"bytes_in_use": 9 * GIB, "peak_bytes_in_use": 9 * GIB,
       "bytes_reserved": 4 * GIB, "peak_bytes_reserved": 6 * GIB,
       "bytes_limit": 16 * GIB},
      {"bytes_in_use": 2 * GIB, "bytes_limit": 16 * GIB}, None],
     1, 3 * GIB),
    # a backend whose allocator says nothing (the CPU's)
    ([None, None], None, None),
    # no limit reported: nothing to budget against
    ([{"bytes_in_use": GIB}], 0, None),
])
def test_device_memory_is_read_device_by_device_with_what_is_reserved(
    monkeypatch, devices, fullest, headroom
):
    import jax

    from elasticdl_tpu.trainer import device_pipeline

    jax.devices()  # a backend is running
    monkeypatch.setattr(
        jax, "local_devices",
        lambda: [_FakeDevice(at, stats) for at, stats in enumerate(devices)],
    )
    monkeypatch.delenv(device_pipeline.STAGING_BUDGET_ENV, raising=False)
    stats = memory_mod.read_device_memory()
    if fullest is None:
        assert stats == {} and memory_mod.fullest_device(stats) is None
    else:
        told = [d for d in devices if d]
        assert [d["id"] for d in stats["devices"]] == [
            at for at, d in enumerate(devices) if d
        ]
        assert all(
            set(d) == {"id", *memory_mod.ALLOCATOR_FIGURES} for d in stats["devices"]
        )
        # the summed figures the ledger's samples read are still there
        assert stats["bytes_in_use"] == sum(d["bytes_in_use"] for d in told)
        assert stats["bytes_reserved"] == sum(d.get("bytes_reserved", 0) for d in told)
        assert stats["peak_bytes_in_use"] >= stats["bytes_in_use"]
        assert memory_mod.fullest_device(stats)["id"] == fullest
    assert memory_mod.device_headroom_bytes() == headroom
    # the stager takes half of what is left beside the reserved bytes
    assert device_pipeline.staging_budget_bytes() == (
        None if headroom is None else headroom // 2
    )
