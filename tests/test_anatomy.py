"""Step anatomy (ISSUE 10): sum-exact per-dispatch phase attribution,
the heartbeat-shipped /metrics mirror, the report's goodput ledger, the
/healthz progress/degradation fields, and the flag-off byte-identity
contract."""

from __future__ import annotations

import json
import os
import time

import numpy as np
import pytest

from elasticdl_tpu.telemetry import anatomy
from elasticdl_tpu.telemetry.anatomy import (
    ALL_PHASES,
    PHASE_ASSEMBLE,
    PHASE_DEVICE_COMPUTE,
    PHASE_HOST_FETCH,
    PHASE_STEP_BOOKKEEPING,
    PHASE_UNTRACKED,
    AnatomyRecorder,
)


@pytest.fixture(autouse=True)
def _clean_installs(monkeypatch):
    monkeypatch.delenv(anatomy.STEP_ANATOMY_ENV, raising=False)
    yield
    anatomy.uninstall()
    from elasticdl_tpu.telemetry import tracing, worker_hooks

    worker_hooks.uninstall()
    tracing.uninstall()


# ---- recorder: the sum-exact contract ---------------------------------------


def test_phases_plus_untracked_sum_exactly_to_wall():
    rec = AnatomyRecorder()
    with rec.phase(PHASE_ASSEMBLE):
        pass
    with rec.phase(PHASE_DEVICE_COMPUTE, sub="enqueue"):
        pass
    phases = rec.commit(steps=1, records=4)
    assert set(phases) <= set(ALL_PHASES)
    # untracked is the residual BY CONSTRUCTION: reconstructing wall
    # from the committed phases is exact to float noise
    tracked = sum(v for k, v in phases.items() if k != PHASE_UNTRACKED)
    assert phases[PHASE_UNTRACKED] >= 0.0
    # a second commit with no intervals is a no-op
    assert rec.commit() is None
    assert rec.dispatches == 1
    assert tracked >= 0.0


def test_wrap_fetches_attributes_next_time_to_host_fetch():
    rec = AnatomyRecorder()
    items = list(rec.wrap_fetches([1, 2, 3]))
    assert items == [1, 2, 3]
    phases = rec.commit(steps=3, records=3)
    assert PHASE_HOST_FETCH in phases
    snap = rec.heartbeat_snapshot()
    assert snap[PHASE_HOST_FETCH]["count"] == 1
    assert snap[PHASE_HOST_FETCH]["ms"] >= 0.0
    # bucket counts are string-keyed (msgpack strict_map_key) and sum
    # to the dispatch count
    assert sum(snap[PHASE_HOST_FETCH]["buckets"].values()) == 1


def test_timed_hook_records_bookkeeping_for_the_recorder_to_commit():
    """The hooks' time is written by the loops themselves
    (``stacking.timed_hook``, always on); the recorder commits it."""
    from elasticdl_tpu.trainer.stacking import timed_hook

    rec = AnatomyRecorder()
    calls = []
    hook = timed_hook(calls.append)
    hook("x")
    assert calls == ["x"]
    phases = rec.commit()
    assert PHASE_STEP_BOOKKEEPING in phases
    assert timed_hook(None) is None


def test_heartbeat_snapshot_is_monotone_across_commits():
    rec = AnatomyRecorder()
    with rec.phase(PHASE_ASSEMBLE):
        pass
    rec.commit()
    first = rec.heartbeat_snapshot()[PHASE_ASSEMBLE]
    with rec.phase(PHASE_ASSEMBLE):
        pass
    rec.commit()
    second = rec.heartbeat_snapshot()[PHASE_ASSEMBLE]
    assert second["count"] == first["count"] + 1
    assert second["ms"] >= first["ms"]


# ---- disabled contract ------------------------------------------------------


def test_disabled_module_hooks_take_no_clock_reads(monkeypatch):
    anatomy.uninstall()

    def boom():
        raise AssertionError("clock read on the disabled path")

    monkeypatch.setattr("time.monotonic", boom)
    assert anatomy.get_recorder() is None
    assert anatomy.heartbeat_snapshot() == {}


def test_install_if_enabled_honors_flag_and_env(monkeypatch):
    assert anatomy.install_if_enabled(None) is None
    assert anatomy.get_recorder() is None
    assert anatomy.install_if_enabled(True) is not None
    anatomy.uninstall()
    monkeypatch.setenv(anatomy.STEP_ANATOMY_ENV, "1")
    assert anatomy.install_from_env() is not None


# ---- run_stacked_steps integration ------------------------------------------


class _Trainer:
    step = 7

    def pad_to(self, tree, rows):
        import jax

        def _pad(x):
            x = np.asarray(x)
            if x.shape[0] == rows:
                return x
            return np.concatenate(
                [x, np.repeat(x[-1:], rows - x.shape[0], axis=0)]
            )

        return jax.tree_util.tree_map(_pad, tree)

    def row_mask(self, n, rows):
        mask = np.zeros(rows, np.float32)
        mask[:n] = 1.0
        return mask

    # placement and enqueue record themselves on the timeline, as
    # SPMDTrainer's do: the loops do not time them from outside

    def place_batch(self, tree):
        anatomy.TIMELINE.record(
            anatomy.PHASE_H2D_TRANSFER, time.perf_counter_ns()
        )
        return tree

    place_stacked = place_batch

    def train_step(self, features, labels, weights=None):
        out = np.float32(0.0)
        anatomy.TIMELINE.record_enqueue(time.perf_counter_ns(), out)
        return out

    def train_steps_stacked(self, features, labels, weights=None):
        return self.train_step(features, labels, weights)


def _batches(sizes):
    return [
        (np.ones((n, 2), np.float32), np.arange(n, dtype=np.int32))
        for n in sizes
    ]


def test_run_stacked_steps_commits_one_anatomy_per_group():
    from elasticdl_tpu.trainer.stacking import run_stacked_steps

    rec = AnatomyRecorder()
    processed = run_stacked_steps(
        lambda: _Trainer(),
        iter(_batches([4, 4, 3])),
        3,
        canonical_rows=4,
        anatomy=rec,
    )
    assert processed == 11
    assert rec.dispatches == 1
    snap = rec.heartbeat_snapshot()
    for phase in (
        PHASE_HOST_FETCH,
        PHASE_ASSEMBLE,
        "h2d_transfer",
        PHASE_DEVICE_COMPUTE,
        PHASE_UNTRACKED,
    ):
        assert phase in snap, f"missing {phase}: {sorted(snap)}"


def test_run_stacked_steps_partial_group_still_one_commit():
    from elasticdl_tpu.trainer.stacking import run_stacked_steps

    rec = AnatomyRecorder()
    run_stacked_steps(
        lambda: _Trainer(),
        iter(_batches([4, 4, 3])),
        2,
        canonical_rows=4,
        anatomy=rec,
    )
    # groups: [4,4] stacked + [3] trailing single = 2 commits
    assert rec.dispatches == 2


def test_run_stacked_steps_prestacked_group_committed():
    from elasticdl_tpu.trainer.stacking import PreStacked, run_stacked_steps

    rec = AnatomyRecorder()
    feats = np.ones((2, 4, 2), np.float32)
    labels = np.zeros((2, 4), np.int32)
    run_stacked_steps(
        lambda: _Trainer(),
        iter([PreStacked(feats, labels, 8, feats[0])]),
        2,
        canonical_rows=4,
        anatomy=rec,
    )
    assert rec.dispatches == 1
    snap = rec.heartbeat_snapshot()
    assert "h2d_transfer" in snap and PHASE_DEVICE_COMPUTE in snap


def test_run_stacked_steps_emits_events_with_exact_sums(tmp_path):
    from elasticdl_tpu.telemetry import worker_hooks
    from elasticdl_tpu.telemetry.events import read_events
    from elasticdl_tpu.trainer.stacking import run_stacked_steps

    worker_hooks.install(str(tmp_path), worker_id=3, generation=2)
    rec = AnatomyRecorder()
    run_stacked_steps(
        lambda: _Trainer(),
        iter(_batches([4, 4, 3])),
        2,
        canonical_rows=4,
        anatomy=rec,
    )
    events = [
        e
        for e in read_events(str(tmp_path / "events.jsonl"))
        if e["event"] == "step_anatomy"
    ]
    assert len(events) == 2
    for event in events:
        assert event["worker_id"] == 3 and event["generation"] == 2
        tracked = sum(
            event.get(f"{p}_ms", 0.0) for p in ALL_PHASES
        )
        assert abs(event["wall_ms"] - tracked) < 1e-6
        # the device_compute sub-split sums to the phase
        split = event.get("enqueue_ms", 0.0) + event.get(
            "ready_wait_ms", 0.0
        )
        assert abs(split - event["device_compute_ms"]) < 1e-6
    assert events[0]["records"] == 8 and events[1]["records"] == 3


def test_sampled_step_anatomy_spans(tmp_path):
    from elasticdl_tpu.telemetry import tracing
    from elasticdl_tpu.telemetry.tracing import (
        SPAN_STEP_ANATOMY,
        read_spans,
    )
    from elasticdl_tpu.trainer.stacking import run_stacked_steps

    tracing.install(str(tmp_path), sample_rate=1.0)
    rec = AnatomyRecorder()
    run_stacked_steps(
        lambda: _Trainer(),
        iter(_batches([4, 4])),
        2,
        canonical_rows=4,
        anatomy=rec,
    )
    tracing.flush()
    spans = [
        s
        for s in read_spans(str(tmp_path / "spans.jsonl"))
        if s["span"] == SPAN_STEP_ANATOMY
    ]
    assert spans, "no step_anatomy spans at sample_rate=1.0"
    assert {s["phase"] for s in spans} >= {
        PHASE_ASSEMBLE,
        PHASE_DEVICE_COMPUTE,
    }


def test_anatomy_none_keeps_dispatch_behavior_and_no_clock(monkeypatch):
    """The disabled path: identical dispatches, no anatomy calls."""
    from elasticdl_tpu.trainer.stacking import run_stacked_steps

    processed = run_stacked_steps(
        lambda: _Trainer(),
        iter(_batches([4, 3])),
        1,
        canonical_rows=4,
        anatomy=None,
    )
    assert processed == 7


# ---- heartbeat merge + /metrics mirror --------------------------------------


def _servicer():
    from elasticdl_tpu.master.servicer import MasterServicer
    from elasticdl_tpu.master.task_dispatcher import TaskDispatcher

    shards = {"s": (0, 8)}
    return MasterServicer(4, TaskDispatcher(shards, records_per_task=4))


def test_heartbeat_phase_merge_is_monotone_and_summed():
    from elasticdl_tpu.rpc import messages as msg

    servicer = _servicer()
    beat = {
        "device_compute": {
            "ms": 100.0,
            "count": 4,
            "buckets": {"0.025": 4},
        }
    }
    servicer.heartbeat(
        msg.HeartbeatRequest(worker_id=0, step=1, phases=beat)
    )
    # a REORDERED (older) beat can't walk anything backward
    servicer.heartbeat(
        msg.HeartbeatRequest(
            worker_id=0,
            step=1,
            phases={
                "device_compute": {
                    "ms": 50.0,
                    "count": 2,
                    "buckets": {"0.025": 2},
                }
            },
        )
    )
    servicer.heartbeat(
        msg.HeartbeatRequest(worker_id=1, step=1, phases=beat)
    )
    totals = servicer.phase_stats_totals()
    assert totals["device_compute"]["ms"] == 200.0
    assert totals["device_compute"]["count"] == 8
    assert totals["device_compute"]["buckets"]["0.025"] == 8


def test_master_telemetry_mirrors_phase_families():
    from elasticdl_tpu.rpc import messages as msg
    from elasticdl_tpu.telemetry.master_hooks import MasterTelemetry

    servicer = _servicer()
    servicer.heartbeat(
        msg.HeartbeatRequest(
            worker_id=0,
            step=1,
            phases={
                "host_fetch": {
                    "ms": 30.0,
                    "count": 3,
                    "buckets": {"0.01": 3},
                }
            },
        )
    )
    telemetry = MasterTelemetry()
    telemetry._servicer = servicer
    text = telemetry.registry.exposition()
    assert (
        'elasticdl_step_phase_ms_total{phase="host_fetch"} 30' in text
    )
    assert 'elasticdl_step_phase_seconds_bucket{phase="host_fetch"' in text
    assert 'elasticdl_step_phase_seconds_count{phase="host_fetch"} 3' in text


def test_histogram_set_totals_monotone_mirror():
    from elasticdl_tpu.telemetry.registry import Histogram

    hist = Histogram()
    hist.set_totals({"0.01": 3, "inf": 1}, 0.5, 4)
    snap = hist.snapshot()
    assert snap["count"] == 4 and snap["sum"] == 0.5
    assert snap["buckets"][0.01] == 3
    # lower mirror input never walks the exposed counts backward
    hist.set_totals({"0.01": 1}, 0.1, 2)
    snap = hist.snapshot()
    assert snap["count"] == 4 and snap["buckets"][0.01] == 3


# ---- /healthz: progress vs liveness -----------------------------------------


def test_healthz_last_step_age_and_degraded_network():
    from elasticdl_tpu.rpc import messages as msg
    from elasticdl_tpu.telemetry.master_hooks import MasterTelemetry

    servicer = _servicer()
    telemetry = MasterTelemetry()
    telemetry._servicer = servicer
    health = telemetry.build_health_fn("training")
    payload = health()
    assert payload["last_step_age_secs"] is None
    assert payload["degraded_network"] is False

    servicer.heartbeat(msg.HeartbeatRequest(worker_id=0, step=5))
    payload = health()
    assert payload["last_step_age_secs"] is not None
    assert payload["last_step_age_secs"] < 5.0
    # liveness without PROGRESS does not reset the staleness clock
    age_before = servicer.last_step_age_secs()
    servicer.heartbeat(msg.HeartbeatRequest(worker_id=0, step=5))
    assert servicer.last_step_age_secs() >= age_before

    # an outage-class RPC counter rising flags the network degraded
    servicer.heartbeat(
        msg.HeartbeatRequest(
            worker_id=0, step=5, rpc={"deadline_exceeded": 2}
        )
    )
    assert health()["degraded_network"] is True
    # ...but a worker's FIRST beat to a (restarted) master carrying
    # stale lifetime totals seeds silently — rpc/stats.py counters are
    # process-lifetime, and re-learning an hours-old failure as a
    # fresh degradation would page on every master restart
    fresh = _servicer()
    fresh.heartbeat(
        msg.HeartbeatRequest(
            worker_id=0, step=5, rpc={"deadline_exceeded": 2}
        )
    )
    assert fresh.network_degraded() is False
    # a subsequent RISE on the same link does flag
    fresh.heartbeat(
        msg.HeartbeatRequest(
            worker_id=0, step=5, rpc={"deadline_exceeded": 3}
        )
    )
    assert fresh.network_degraded() is True
    # version reports also advance the progress clock
    servicer.report_version(
        msg.ReportVersionRequest(model_version=9, worker_id=0)
    )
    assert servicer.last_step_age_secs() < 1.0


# ---- goodput section --------------------------------------------------------


def _anat_event(gen=0, worker=0, wall=10.0, fetch=2.0, compute=6.0, **extra):
    fields = {
        "event": "step_anatomy",
        "monotonic": 1.0,
        "generation": gen,
        "worker_id": worker,
        "steps": 1,
        "records": 4,
        "wall_ms": wall,
        "host_fetch_ms": fetch,
        "assemble_ms": 0.5,
        "h2d_transfer_ms": 0.5,
        "device_compute_ms": compute,
        "step_bookkeeping_ms": wall - fetch - compute - 1.0,
        "untracked_ms": 0.0,
        "n_chips": 1,
    }
    fields.update(extra)
    return fields


def test_goodput_section_computes_roofline_and_percentiles():
    from elasticdl_tpu.telemetry.report import goodput_section

    events = [_anat_event() for _ in range(5)]
    section = goodput_section(events)
    overall = section["overall"]
    assert overall["dispatches"] == 5
    # device path = 0.5 + 0.5 + 6.0 = 7.0 of 10.0 wall
    assert overall["binding"] == "device_path"
    assert abs(overall["e2e_vs_roofline"] - 0.7) < 1e-6
    assert overall["phases"]["device_compute"]["p50_ms"] == 6.0
    assert overall["phases"]["host_fetch"]["p99_ms"] == 2.0
    assert overall["max_sum_residual_ms"] < 1e-6
    assert overall["untracked_share"] == 0.0
    # no flops info -> explicit reason, never an invented number
    assert overall["mfu"] is None
    assert "unknown" in overall["mfu_reason"]


def test_goodput_mfu_when_costs_known():
    from elasticdl_tpu.telemetry.report import goodput_section

    events = [
        _anat_event(
            flops_per_record=1e9,
            peak_flops_per_chip=1e12,
        )
        for _ in range(2)
    ]
    overall = goodput_section(events)["overall"]
    # 2 dispatches x 4 records x 1e9 / (12ms x 1e12) = 8/12 = 0.6667
    assert abs(overall["mfu"] - 8e9 / (0.012 * 1e12)) < 1e-3


def test_goodput_straggler_attribution_names_the_phase():
    from elasticdl_tpu.telemetry.report import goodput_section

    # worker 1's dispatches take 2x wall, and the excess is fetch
    events = [_anat_event(worker=0) for _ in range(4)] + [
        _anat_event(worker=1, fetch=15.0, compute=1.0, wall=20.0)
        for _ in range(4)
    ]
    overall = goodput_section(events)["overall"]
    workers = overall["workers"]
    assert workers[1]["straggler"] is True
    assert workers[1]["lagging_phase"] == "host_fetch"
    # a worker whose WALL keeps fleet pace is not a straggler, even
    # though the bimodal per-phase medians would naively flag it
    assert workers[0]["straggler"] is False


def test_goodput_absent_without_anatomy_events():
    from elasticdl_tpu.telemetry.report import analyze_events

    out = analyze_events(
        [{"event": "step", "monotonic": 1.0, "generation": 0}], []
    )
    assert "goodput" not in out


# ---- report: empty/partial run dirs -----------------------------------------


def test_report_empty_events_file_reports_no_data(tmp_path):
    from elasticdl_tpu.telemetry import report as report_cli

    run = tmp_path / "telemetry"
    run.mkdir()
    (run / "events.jsonl").write_text("")
    assert report_cli.main([str(tmp_path)]) == 0
    report = report_cli.build_report(str(tmp_path))
    rel = os.path.join("telemetry", "events.jsonl")
    assert report["runs"][rel]["no_data"]


def test_report_events_without_spans_no_traceback(tmp_path, capsys):
    from elasticdl_tpu.telemetry import report as report_cli

    run = tmp_path / "telemetry"
    run.mkdir()
    with open(run / "events.jsonl", "w", encoding="utf-8") as f:
        f.write(
            json.dumps(
                {
                    "event": "step",
                    "monotonic": 1.0,
                    "generation": 0,
                    "step": 1,
                }
            )
            + "\n"
        )
    assert report_cli.main([str(tmp_path)]) == 0
    assert "Traceback" not in capsys.readouterr().err


def test_report_rotated_shards_mid_run(tmp_path):
    from elasticdl_tpu.telemetry import report as report_cli

    run = tmp_path / "telemetry"
    run.mkdir()
    # a rotated shard (.1) plus an active file: both must be read
    with open(run / "events.jsonl.1", "w", encoding="utf-8") as f:
        f.write(
            json.dumps(
                {
                    "event": "step",
                    "monotonic": 1.0,
                    "generation": 0,
                    "step": 1,
                    "duration_secs": 0.1,
                }
            )
            + "\n"
        )
    with open(run / "events.jsonl", "w", encoding="utf-8") as f:
        f.write(
            json.dumps(
                {
                    "event": "step",
                    "monotonic": 2.0,
                    "generation": 0,
                    "step": 2,
                    "duration_secs": 0.1,
                }
            )
            + "\n"
        )
    report = report_cli.build_report(str(tmp_path))
    rel = os.path.join("telemetry", "events.jsonl")
    assert report["runs"][rel]["generations"][0]["steps"] == 2
    assert report_cli.main([str(tmp_path)]) == 0


# ---- trace analyze steady-state mode ----------------------------------------


def test_trace_analyze_steady_state_section(tmp_path):
    from elasticdl_tpu.telemetry.trace import analyze_telemetry_dir

    run = tmp_path / "telemetry"
    run.mkdir()
    with open(run / "events.jsonl", "w", encoding="utf-8") as f:
        for event in [_anat_event(), _anat_event(gen=1)]:
            f.write(json.dumps(event) + "\n")
    (run / "spans.jsonl").write_text("")
    analysis = analyze_telemetry_dir(str(run))
    steady = analysis["steady_state"]
    assert steady[0]["dispatches"] == 1 and steady[1]["dispatches"] == 1
    phases = steady[0]["phases"]
    assert phases["device_compute"]["total_ms"] == 6.0
    # shares of ONE generation's wall sum to ~1 (untracked was 0)
    assert (
        abs(
            sum(p["share"] for p in phases.values())
            - 1.0
        )
        < 1e-3
    )


# ---- flag-off byte identity -------------------------------------------------


def test_step_anatomy_flag_never_reaches_worker_argv():
    from elasticdl_tpu.utils.args import (
        build_worker_arguments,
        parse_master_args,
    )

    base = [
        "--model_def",
        "mnist_functional_api.mnist_functional_api.custom_model",
        "--training_data",
        "/tmp/x",
    ]
    off = parse_master_args(base)
    on = parse_master_args(base + ["--step_anatomy", "true"])
    argv_off = build_worker_arguments(off, 0, "localhost:1")
    argv_on = build_worker_arguments(on, 0, "localhost:1")
    # even when SET it travels by env, never worker argv — and the off
    # argv is byte-identical to a build without the flag
    assert "--step_anatomy" not in argv_on
    assert argv_on == argv_off


def test_model_flops_table_and_peak_env(monkeypatch):
    assert (
        anatomy.model_flops_per_record(
            "mnist_functional_api.mnist_functional_api.custom_model"
        )
        == anatomy.MODEL_FLOPS_PER_RECORD["mnist_functional_api"]
    )
    assert anatomy.model_flops_per_record("unknown_model.custom") is None
    monkeypatch.setenv(anatomy.PEAK_FLOPS_ENV, "123.5")
    assert anatomy.peak_flops_per_chip() == 123.5


def test_peak_table_agrees_with_the_benchmarks():
    """Two peak tables with two owners (this package's goodput report,
    the benchmark's ``perf/peaks.json``): one peak per device kind."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "perf", "peaks.json")) as f:
        benchmark = {
            kind: peaks["bf16_flops_per_s"]
            for kind, peaks in json.load(f).items()
            if isinstance(peaks, dict)
        }
    assert benchmark, "perf/peaks.json names no device kind"
    ours = {
        kind: anatomy._PEAK_FLOPS_BY_DEVICE_KIND.get(kind)
        for kind in benchmark
    }
    assert ours == benchmark
