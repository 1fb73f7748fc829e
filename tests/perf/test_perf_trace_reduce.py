"""The reduction from trace events to numbers, on synthetic events whose
answers are known by construction (the recorded chip traces are in
test_perf_recorded_trace.py)."""

import pytest
from perf_testlib import ROOT  # noqa: F401 — puts the repo on sys.path

from perf import layer_readers, trace_reduce as tr

US = 1000


def events_one_device():
    # window [0, 1000us): two steps of a matmul fusion, a flash kernel
    # nested in a call, and an all-reduce half hidden behind compute
    ops = [
        ["fusion.1", 0 * US, 300 * US],
        ["call.2", 300 * US, 200 * US],
        ["flash_fwd.3", 320 * US, 100 * US],  # inside call.2
        ["all-reduce.4", 450 * US, 150 * US],  # 450..600, call.2 ends at 500
        ["fusion.1", 700 * US, 200 * US],
    ]
    host = [
        ["perf:interval", 0, 1000 * US],
        ["perf:dispatch", 0, 590 * US],
        ["perf:input_wait", 590 * US, 60 * US],  # covers 600..650 of the gap
        ["perf:dispatch", 650 * US, 30 * US],
        ["perf:readback", 880 * US, 120 * US],
    ]
    details = {"flash_fwd.3": "(bf16[96,1024,64]) custom-call(...) tpu_custom_call"}
    return {"devices": {"/device:TPU:0": ops}, "host": host, "details": details}


def test_interval_arithmetic():
    assert tr.merge([(5, 7), (0, 2), (1, 3), (7, 7)]) == [(0, 3), (5, 7)]
    assert tr.total([(0, 3), (5, 7)]) == 5
    assert tr.subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]
    assert tr.subtract([(0, 4), (6, 9)], [(3, 7)]) == [(0, 3), (7, 9)]
    assert tr.subtract([(0, 4)], []) == [(0, 4)]


def test_busy_is_the_union_not_the_sum():
    reduced = tr.reduce(events_one_device())
    assert reduced["window_s"] == pytest.approx(1000e-6)
    # 0..600 and 700..900: nested and overlapping events count once
    assert reduced["busy_s"] == pytest.approx(800e-6)


def test_self_time_takes_children_out_of_their_parent():
    reduced = tr.reduce(events_one_device())
    ops = reduced["op_self_s"]
    assert ops["fusion.1"] == pytest.approx(500e-6)
    assert ops["flash_fwd.3"] == pytest.approx(100e-6)
    assert ops["call.2"] == pytest.approx(50e-6)  # 200 - kernel 100 - overlap 50
    assert tr.matching_seconds(reduced, "tpu_custom_call") == pytest.approx(100e-6)
    assert tr.breakdown(reduced)["device_ops"][0] == ["fusion.1", pytest.approx(500e-6)]


def test_exposed_collective_is_the_part_no_compute_covers():
    reduced = tr.reduce(events_one_device())
    assert reduced["collective_exposed_s"] == pytest.approx(100e-6)  # 500..600


def test_gaps_go_to_what_the_host_was_doing():
    reduced = tr.reduce(events_one_device())
    gaps = reduced["idle_gaps_s"]
    # gap 600..700: input wait 600..650, dispatch 650..680, unspanned 680..700
    assert gaps["perf:input_wait"] == pytest.approx(50e-6)
    assert gaps["perf:dispatch"] == pytest.approx(30e-6)
    assert gaps[tr.UNSPANNED] == pytest.approx(20e-6)
    # gap 900..1000: the readback's round trip after the last op
    assert gaps["perf:readback"] == pytest.approx(100e-6)
    assert sum(gaps.values()) == pytest.approx(
        reduced["window_s"] - reduced["busy_s"]
    )
    named = tr.breakdown(reduced)["idle_gaps"]
    assert named[0][0] == "perf:readback" and len(named) == 4


def test_short_gaps_are_not_attributed():
    events = events_one_device()
    events["devices"]["/device:TPU:0"].append(["fusion.9", 600 * US, 95 * US])
    gaps = tr.reduce(events)["idle_gaps_s"]
    assert "perf:input_wait" not in gaps  # 695..700 is under MIN_GAP_NS


def test_several_devices_are_averaged():
    events = events_one_device()
    events["devices"]["/device:TPU:1"] = [["fusion.1", 0, 400 * US]]
    reduced = tr.reduce(events)
    assert reduced["devices"] == 2
    assert reduced["busy_s"] == pytest.approx((800e-6 + 400e-6) / 2)
    assert reduced["collective_exposed_s"] == pytest.approx(50e-6)


def test_window_falls_back_to_the_device_extent_and_empty_is_an_error():
    events = events_one_device()
    events["host"] = []
    assert tr.window_of(events) == (0, 900 * US)
    with pytest.raises(ValueError):
        tr.reduce({"devices": {}, "host": [], "details": {}})


def test_readers_on_a_reduced_trace():
    reduced = tr.reduce(events_one_device())
    run = {
        "trace": reduced,
        "traced_steps": 2,
        "flops_per_step_chip": {
            "train": 0.25 * 197e12 * 400e-6,
            "causal_attention": 0.1 * 197e12 * 50e-6,
        },
        "peaks": {"bf16_flops_per_s": 197e12},
        "host": {"wall_s": 2.0, "input_wait_s": 0.5, "dispatch_s": 0.03, "batches": 10},
    }
    assert layer_readers.step_device_ms(run) == pytest.approx(0.4)
    assert layer_readers.step_mfu(run) == pytest.approx(25.0)
    assert layer_readers.flash_time_share(run) == pytest.approx(12.5)
    assert layer_readers.flash_roofline(run) == pytest.approx(10.0)
    assert layer_readers.input_wait_share(run) == pytest.approx(25.0)
    assert layer_readers.dispatch_ms(run) == pytest.approx(3.0)
    assert layer_readers.collective_exposed_share(run) is None  # one device
    untraced = dict(run, trace=None)
    for reader in (
        layer_readers.step_device_ms, layer_readers.step_mfu,
        layer_readers.flash_time_share, layer_readers.flash_roofline,
        layer_readers.collective_exposed_share,
    ):
        assert reader(untraced) is None


def test_host_spans_are_put_on_the_trace_clock():
    events = events_one_device()
    on_trace_clock = events.pop("host")
    # the same spans as the harness takes them: on a host clock 7 s ahead;
    # each interval ends when its readback returns, 100 us after the
    # device's last op (900 us) for the first, 140 us for a second interval
    offset = 7_000_000 * US
    host_clock = [[n, s + offset, d] for n, s, d in on_trace_clock]
    events["devices"]["/device:TPU:0"].append(["fusion.1", 1500 * US, 400 * US])
    host_clock.append(["perf:interval", 1000 * US + offset, 1040 * US])
    aligned = tr.align_host_spans(events, host_clock)
    # the smallest end-to-last-op distance (100 us) is taken for the offset:
    # the spans land 100 us early, the latency of the shortest way back
    assert aligned["clock_offset_ns"] == offset + 100 * US
    first = next(e for e in aligned["host"] if e[0] == "perf:interval")
    assert first[1] == -100 * US and first[2] == 1000 * US
    assert tr.align_host_spans({"devices": {}}, host_clock)["host"] == []
    assert tr.align_host_spans(events, [])["host"] == []
