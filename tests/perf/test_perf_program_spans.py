"""The readers of what the program records about itself: each on a
synthetic timeline, the rule that picks the untraced dispatches, a program
without a timeline, and a profile window's spans put on a trace's clock."""

import json
import subprocess
import sys
import os
from collections import namedtuple

import pytest
from perf_testlib import ROOT, TINY_FAMILY_CELL, manifest_with_tiny_cell, repo_manifest

from perf import kernel_rooflines, manifest as manifest_lib, program_spans, trace_reduce

Span = namedtuple("Span", "name thread start_ns duration_ns cpu_ns ordinal count")
MS = 1_000_000
MAIN, PRODUCER = "MainThread", "task-prefetch"


def timeline(dispatches=12, step_ms=100, first_ordinal=40):
    """``dispatches`` dispatches of one thread, one every ``step_ms``: a
    0.5 ms fetch, 0.25 + 0.75 ms of hooks, 1 ms of assembly, three
    placements of 0.5 ms, a 2 ms enqueue; the producer makes a batch in 6 ms
    (4 ms of CPU) and is blocked for 90 ms of every 100."""
    spans = []
    for i in range(dispatches):
        t = i * step_ms * MS
        n = first_ordinal + i
        spans += [
            Span("host_fetch", MAIN, t, MS // 2, None, 500 + i, 1),
            Span("step_bookkeeping", MAIN, t + MS, MS // 4, None, n, None),
            Span("assemble", MAIN, t + 2 * MS, MS, None, n, None),
        ]
        spans += [
            Span("h2d_transfer", MAIN, t + (3 + j) * MS, MS // 2, None, n, 64)
            for j in range(3)
        ]
        spans += [
            Span("enqueue", MAIN, t + 6 * MS, 2 * MS, None, n, None),
            Span("step_bookkeeping", MAIN, t + 8 * MS, 3 * MS // 4, None, n, None),
            Span("produce_batch", PRODUCER, t + MS, 6 * MS, 4 * MS, 500 + i, 8192),
            Span("produce_blocked", PRODUCER, t + 8 * MS, 90 * MS, None, 0, None),
        ]
    return sorted(spans, key=lambda s: s.start_ns + s.duration_ns)


def run_of(spans, untraced, traced):
    return {
        "host": {"batches": untraced},
        "host_traced": {"batches": traced},
        program_spans._KEY: spans,
    }


def test_the_untraced_dispatches_are_those_before_the_newest_traced():
    window = program_spans.select_dispatches(timeline(), untraced=5, traced=4)
    # ordinals 40..51: the newest four are traced, the five before measured
    assert (window["lo"], window["hi"]) == (43, 47)
    assert window["dispatches"] == 5 and window["thread"] == MAIN
    assert {s.ordinal for s in window["spans"]} == {43, 44, 45, 46, 47}
    assert "host_fetch" not in {s.name for s in window["spans"]}
    assert window["start_ns"] == 3 * 100 * MS + MS
    assert window["end_ns"] == 7 * 100 * MS + 8 * MS


def test_a_ring_that_dropped_the_oldest_dispatches_counts_what_is_left():
    spans = [s for s in timeline() if s.start_ns >= 5 * 100 * MS]
    window = program_spans.select_dispatches(spans, untraced=5, traced=4)
    assert window["dispatches"] == 3  # ordinals 45, 46, 47 of 43..47


@pytest.mark.parametrize(
    "untraced,traced", [(0, 4), (5, 12), (5, 40)]
)
def test_no_untraced_dispatch_in_the_ring_reads_nothing(untraced, traced):
    assert program_spans.select_dispatches(timeline(), untraced, traced) is None
    assert program_spans.mean_ms_per_dispatch(
        run_of(timeline(), untraced, traced), "enqueue"
    ) is None


@pytest.mark.parametrize(
    "metric,expected",
    [
        ("bookkeeping_ms.lm", 1.0),
        ("assemble_ms.lm", 1.0),
        ("place_ms.lm", 1.5),
        ("enqueue_ms.lm", 2.0),
        ("enqueue_ms.vision", 2.0),
        ("producer_batch_ms.lm", 6.0),
    ],
)
def test_span_reader_on_a_synthetic_timeline(metric, expected):
    cell = manifest_lib.Cell(repo_manifest(), "gpt2s_seq1024")
    read = cell.reader(metric)
    assert read(run_of(timeline(), untraced=5, traced=4)) == pytest.approx(expected)


def test_fetch_wait_is_taken_by_time_on_the_dispatching_thread():
    cell = manifest_lib.Cell(repo_manifest(), "gpt2s_seq1024")
    read = cell.reader("fetch_wait_ms.lm")
    # the window starts after dispatch 43's own fetch (taken by time, from
    # the first span carrying the ordinal): four fetches over five dispatches
    assert read(run_of(timeline(), 5, 4)) == pytest.approx(4 * 0.5 / 5)
    no_stream = [s for s in timeline() if s.name != "host_fetch"]
    assert read(run_of(no_stream, 5, 4)) is None


def test_producer_busy_share_is_the_time_not_blocked():
    cell = manifest_lib.Cell(repo_manifest(), "gpt2s_seq1024")
    read = cell.reader("producer_busy_share.lm")
    spans = timeline()
    window = program_spans.select_dispatches(spans, 5, 4)
    wall = window["end_ns"] - window["start_ns"]
    # blocked 90 of every 100 ms; the last block is cut at the window's end
    blocked = 4 * 90 * MS + (8 - 8) * MS
    assert read(run_of(spans, 5, 4)) == pytest.approx(100 * (1 - blocked / wall))
    resident = [s for s in spans if s.thread == MAIN]
    assert read(run_of(resident, 5, 4)) is None  # no producer: nothing to read


def test_a_program_without_a_timeline_reads_nothing(monkeypatch):
    """The parent of the PR that added the timeline: the readers are laid
    over its checkout too, return None and do not raise."""
    from elasticdl_tpu.telemetry import anatomy, compile_tracker

    monkeypatch.delattr(anatomy, "snapshot")
    monkeypatch.delattr(compile_tracker, "trace_secs_total")
    run = {"host": {"batches": 5}, "host_traced": {"batches": 4}, "trace": None}
    cell = manifest_lib.Cell(repo_manifest(), "gpt2s_seq1024")
    for metric in cell.metrics("per_layer"):
        if metric["source"] in ("program_span",):
            assert cell.reader(metric["name"])(dict(run)) is None
    assert cell.reader("setup_trace_s")(dict(run)) is None
    assert cell.reader("setup_compile_s")(dict(run)) is not None
    assert cell.reader("flash_dq_roofline.lm")(dict(run)) is None


def test_the_real_timeline_is_what_snapshot_of_takes():
    from elasticdl_tpu.telemetry import anatomy

    anatomy.TIMELINE.record_enqueue(1, None)
    run = {}
    spans = program_spans.snapshot_of(run)
    assert spans and spans[-1].name == "enqueue"
    assert program_spans.snapshot_of(run) is spans  # taken once per run


@pytest.mark.parametrize("stage", ["trace", "lower", "compile"])
def test_setup_counters_read_the_compile_listener(stage):
    import jax
    import numpy as np
    from elasticdl_tpu.telemetry import compile_tracker

    compile_tracker.install()
    jax.jit(lambda x: x * 24.0 + 1.0)(np.ones(5, np.float32))
    cell = manifest_lib.Cell(repo_manifest(), "resnet50_imagenet_resident")
    assert cell.reader(f"setup_{stage}_s")({}) > 0.0


@pytest.mark.parametrize("kernel", ["flash_fwd", "flash_dq", "flash_dkv"])
def test_kernel_roofline_takes_a_third_of_attention_over_its_own_time(kernel):
    reduced = {
        "op_self_s": {
            "flash_fwd.1": 0.010, "flash_fwd.2": 0.010,
            "flash_dq.7": 0.030, "flash_dkv.9": 0.040,
            "fusion.3": 1.0, "attn.41": 0.5,
        },
        "details": {"fusion.3": "fusion(... %flash_dq.7 ...)"},
        "busy_s": 2.0,
    }
    run = {
        "trace": reduced,
        "traced_steps": 10,
        "flops_per_step_chip": {"causal_attention": 3e11, "train": 1e13},
        "peaks": {"bf16_flops_per_s": 1e14},
    }
    seconds = {"flash_fwd": 0.020, "flash_dq": 0.030, "flash_dkv": 0.040}[kernel]
    assert kernel_rooflines.kernel_seconds(run, kernel) == pytest.approx(seconds)
    cell = manifest_lib.Cell(repo_manifest(), "gpt2s_seq8192")
    read = cell.reader(f"{kernel}_roofline.lm")
    assert read(run) == pytest.approx(100 * 1e11 * 10 / seconds / 1e14)
    # a trace of the program before the kernels had names: nothing to read
    unnamed = dict(run, trace=dict(reduced, op_self_s={"attn.41": 0.5}))
    assert read(unnamed) is None
    assert read(dict(run, trace=None)) is None


# the fourteen per-layer entries PR 24 added, by name: entries after them,
# and layers they never heard of, are a later PR's own business
PR24_ENTRIES = (
    "bookkeeping_ms.lm", "assemble_ms.lm", "place_ms.lm", "enqueue_ms.lm",
    "enqueue_ms.vision", "fetch_wait_ms.lm", "producer_batch_ms.lm",
    "producer_busy_share.lm", "flash_fwd_roofline.lm", "flash_dq_roofline.lm",
    "flash_dkv_roofline.lm", "setup_trace_s", "setup_lower_s", "setup_compile_s",
)
PR24_LAYERS = (
    "data plane (data/, trainer/host_pipeline.py)",
    "dispatch (trainer/stacking.py, device_pipeline.py, local_executor.py)",
    "SPMD step (parallel/distributed.py, trainer/step.py)",
    "kernels (ops/attention.py)",
)


def test_new_entries_name_layers_as_the_manifest_spells_them():
    manifest = repo_manifest()
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    new = [by_name[name] for name in PR24_ENTRIES]
    assert all(m["layer"] in PR24_LAYERS for m in new)
    # each of those layers is one the entries before them already named
    older = {
        m["layer"] for m in manifest["per_layer"] if m["name"] not in PR24_ENTRIES
    }
    assert set(PR24_LAYERS) <= older
    assert {m["source"] for m in new} == {
        "program_span", "device_trace", "program_counter"
    }
    cells = {w["name"] for w in manifest["workloads"]}
    for m in new:
        if m["name"].startswith("setup_"):
            # every cell reports set-up, those a later PR adds too
            assert set(m.get("workloads", cells)) == cells
            assert m["moves"] == "setup_s"


def test_flash_share_counts_the_three_kernels_by_name_and_no_other_mosaic_call():
    """A later model's grouped matmul is a Mosaic custom-call too
    (``tpu_custom_call`` in its detail): not attention."""
    from perf import layer_readers

    reduced = {
        "op_self_s": {
            "flash_fwd.1": 0.010, "flash_dq.7": 0.030, "flash_dkv.9": 0.040,
            "gmm.5": 0.5, "flash_fwd_wrapper_fusion": 0.3, "fusion.3": 1.0,
        },
        "details": {
            "gmm.5": "(bf16[8192,1024]) custom-call(...) tpu_custom_call",
            "flash_fwd.1": "(bf16[96,1024,64]) custom-call(...) tpu_custom_call",
            "fusion.3": "fusion(... %flash_dq.7 ...)",
        },
        "busy_s": 2.0,
    }
    run = {
        "trace": reduced, "traced_steps": 10,
        "flops_per_step_chip": {"causal_attention": 3e11, "train": 1e13},
        "peaks": {"bf16_flops_per_s": 1e14},
    }
    assert layer_readers.flash_seconds(run) == pytest.approx(0.080)
    assert layer_readers.flash_time_share(run) == pytest.approx(4.0)
    assert layer_readers.flash_roofline(run) == pytest.approx(
        100 * 3e11 * 10 / 0.080 / 1e14
    )
    # a model with other kernels only has no flash share to report
    run["trace"] = dict(reduced, op_self_s={"gmm.5": 0.5, "fusion.3": 1.0})
    assert layer_readers.flash_time_share(run) is None


def test_profile_window_spans_go_on_the_traces_clock(tmp_path):
    """``host_spans.json`` as the program's profile window writes it, put
    on a device trace's clock by its ``sync`` span: the host's clock runs
    5 s ahead of the trace's, and the readback takes 0.1 ms."""
    offset, way_back = 5_000_000_000, 100_000
    device_ops = [["fusion.1", 1_000_000 * i, 900_000] for i in range(1, 9)]
    last_op_end = device_ops[-1][1] + device_ops[-1][2]
    fields = ["name", "thread", "start_ns", "duration_ns", "cpu_ns", "ordinal", "count"]
    spans = [
        ["enqueue", MAIN, offset + 1_000_000 * i - 200_000, 50_000, None, i, None]
        for i in range(1, 9)
    ]
    sync_end = offset + last_op_end + way_back
    spans.append(["sync", MAIN, sync_end - 700_000, 700_000, None, 9, None])
    path = tmp_path / "host_spans.json"
    path.write_text(json.dumps({"clock": "time.perf_counter_ns", "fields": fields, "spans": spans}))
    host = program_spans.load_host_spans(str(path))
    assert [s[0] for s in host].count(trace_reduce.SPAN_INTERVAL) == 1
    events = trace_reduce.align_host_spans(
        {"devices": {"/device:TPU:0": device_ops}, "async": {}, "details": {}, "host": []},
        host,
    )
    assert events["clock_offset_ns"] == offset + way_back
    enqueues = [s for s in events["host"] if s[0] == "enqueue"]
    # every step's enqueue starts before that step's device op does
    for (_, start, _), (_, op_start, _) in zip(enqueues, device_ops):
        assert start < op_start
    reduced = trace_reduce.reduce(events)
    assert reduced["busy_s"] == pytest.approx(8 * 900_000 / 1e9)


@pytest.mark.compiles_a_model
def test_rehearsal_still_passes_with_the_new_entries(tmp_path):
    """``--rehearse-cpu`` of the MNIST rehearsal cell against the manifest
    as this PR leaves it (the new ``per_layer`` entries present)."""
    manifest = manifest_with_tiny_cell()
    assert any(m["name"] == "setup_trace_s" for m in manifest["per_layer"])
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(manifest))
    env = {
        k: v for k, v in os.environ.items()
        if k not in ("XLA_FLAGS", "JAX_PLATFORMS", "BENCH_RUN")
    }
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cache")
    done = subprocess.run(
        [
            sys.executable, os.path.join(ROOT, "perf", "run.py"),
            "--workload", TINY_FAMILY_CELL, "--seed", "24", "--seconds", "2",
            "--trace", "1", "--manifest", str(path), "--rehearse-cpu",
        ],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300,
    )
    assert done.returncode == 0, done.stderr[-2000:]
    info, result = map(json.loads, done.stdout.strip().splitlines()[-2:])
    assert result["correct"] is True and result["metrics"] == {}
    # a configuration that names no reference says so and does not fail
    assert info["reference"] == "none"
    assert "reference_agrees" not in info["checks"]
