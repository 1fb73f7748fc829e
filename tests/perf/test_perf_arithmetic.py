"""The yardstick's arithmetic: FLOP functions, peaks, the reading rule."""

import pytest
from perf_testlib import TINY_FAMILY_CELL, manifest_with_tiny_cell, repo_manifest

from perf import manifest as manifest_lib, meter, peaks, trafficgen
from perf.flop_functions import resnet50, transformer_lm
from perf.record_kinds import template_images, token_chain


@pytest.mark.parametrize("seq_len,mflop", [(1024, 799), (8192, 1195)])
def test_lm_flops_per_token(seq_len, mflop):
    got = transformer_lm.train_flops_per_token(12, 768, 50257, seq_len)
    # ISSUE 23 rounds to 799 and 1,195; the formula gives 797.8 and 1,194.2
    assert abs(got / 1e6 - mflop) < 2
    attention = transformer_lm.causal_attention_train_flops_per_token(12, 768, seq_len)
    assert attention == 6 * 12 * seq_len * 768


def test_resnet50_flops_come_from_the_layer_shapes():
    macs = resnet50.forward_macs()
    assert abs(macs / 1e9 - 3.86) < 0.01
    # striding the 3x3 instead (v1.5) is the other published figure
    assert abs(resnet50.forward_macs(stride_on_first_1x1=False) / 1e9 - 4.09) < 0.01
    spec = {"image_size": 224, "num_classes": 1000}
    assert resnet50.per_record(spec, {}) == {"train": 6 * macs}


@pytest.mark.parametrize(
    "cell,expected",
    [
        ("gpt2s_seq1024", 1024 * 797.815296e6),
        ("gpt2s_seq8192", 8192 * 1194.177024e6),
        ("resnet50_imagenet_resident", 6 * 3857973248.0),
    ],
)
def test_flops_per_record_by_cell(cell, expected):
    resolved = manifest_lib.Cell(repo_manifest(), cell)
    got = resolved.flops_per_record()
    assert got["train"] == pytest.approx(expected, rel=1e-9)
    # only a model with attention names that part
    seq_len = resolved.traffic["records"].get("seq_len")
    if seq_len:
        assert got["causal_attention"] == 6 * 12 * seq_len * seq_len * 768
    else:
        assert set(got) == {"train"}


def test_unknown_flop_function_or_record_kind_is_an_error():
    cell = manifest_lib.Cell(repo_manifest(), "gpt2s_seq1024")
    cell.config["flops"]["function"] = "nope"
    with pytest.raises(manifest_lib.ManifestError):
        cell.flops_per_record()
    cell.traffic["records"]["kind"] = "nope"
    with pytest.raises(manifest_lib.ManifestError):
        cell.record_kind()
    cell.traffic["mode"] = "nope"
    with pytest.raises(manifest_lib.ManifestError):
        cell.driver()


def test_a_model_family_is_added_by_files_alone():
    """Its FLOP arithmetic and its kind of record are files under one of
    ``paths`` (here tests/perf), named by the configuration and the traffic
    file: nothing under perf/ knows the MNIST CNN or its 28x28 records."""
    import numpy as np

    cell = manifest_lib.Cell(manifest_with_tiny_cell(), TINY_FAMILY_CELL)
    macs = 26 * 26 * 9 * 32 + 24 * 24 * 9 * 32 * 64 + 12 * 12 * 64 * 10
    assert cell.flops_per_record() == {"train": 6.0 * macs}
    kind = cell.record_kind()
    assert trafficgen.units_per_record(kind, cell.traffic, "records") == 1
    with pytest.raises(ValueError):
        trafficgen.units_per_record(kind, cell.traffic, "tokens")
    features, labels = trafficgen.one_batch(kind, cell.traffic, 8, 2**31 + 5)
    assert features["image"].shape == (8, 28, 28)
    assert features["image"].dtype == np.uint8 and labels.dtype == np.int32
    assert cell.driver().__name__.endswith("path")


def test_peaks_known_and_unknown():
    assert peaks.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    for kind in ("TPU v9", "cpu", "_source", ""):
        with pytest.raises(KeyError):
            peaks.peaks_for(kind)


def test_a_stall_in_one_interval_moves_the_rate():
    """The rate is all the work over all the time of the window: a 3 s
    stall in one of thirty intervals costs it 9%, where the median of the
    readings (kept beside it) would not move."""
    steady = [(1000, 1.0)] * 30
    clean = meter.summarize(steady)
    assert clean["total_over_window"] == 1000 and clean["median"] == 1000
    stalled = list(steady)
    stalled[7] = (1000, 4.0)  # one interval held up for 3 s
    got = meter.summarize(stalled)
    assert got["readings"] == 30
    assert got["total_over_window"] == pytest.approx(30000 / 33.0)
    assert got["total_over_window"] < 0.92 * clean["total_over_window"]
    assert got["window_s"] == 33.0 and got["units"] == 30000
    # the statistics beside it say the stall was one interval's
    assert got["median"] == 1000 and got["q1"] == 1000 and got["q3"] == 1000
    assert got["min"] == 250


@pytest.mark.parametrize("stalled_s", [0.5, 3.0, 10.0])
def test_the_rate_is_work_over_time_whatever_the_intervals(stalled_s):
    readings = [(500, 0.5)] * 10 + [(500, 0.5 + stalled_s)]
    got = meter.summarize(readings)
    assert got["total_over_window"] == pytest.approx(5500 / (5.5 + stalled_s))


def test_readings_are_per_chip_and_need_one_interval():
    got = meter.summarize([(4000, 1.0), (4400, 1.0), (3600, 1.0)], chips=4)
    assert got["median"] == 1000 and got["total_over_window"] == 1000
    assert meter.summarize([(10, 2.0)])["total_over_window"] == 5
    with pytest.raises(ValueError):
        meter.summarize([])


@pytest.mark.parametrize(
    "cell", [w["name"] for w in manifest_with_tiny_cell()["workloads"]]
)
def test_traffic_plan_counts(cell):
    resolved = manifest_lib.Cell(manifest_with_tiny_cell(), cell)
    plan = trafficgen.plan(resolved.traffic, resolved.chips)
    assert plan["minibatch_size"] == resolved.traffic["batch_per_chip"] * resolved.chips
    assert plan["records_per_shard"] % plan["records_per_task"] == 0
    assert plan["num_records"] == plan["records_per_shard"] * plan["num_shards"]
    unit = resolved.config["work"]["unit"]
    per_record = trafficgen.units_per_record(
        resolved.record_kind(), resolved.traffic, unit
    )
    assert per_record >= 1
    if resolved.config["name"] == "gpt2_small":
        # the GPT-2 mixes hold the same tokens per chip per step (PERF.md,
        # section 4): a fact about these cells, not a rule for the next
        assert per_record * resolved.traffic["batch_per_chip"] == 8192


def test_token_chain_follows_the_permutation():
    import numpy as np

    tokens = token_chain.token_chain(np.random.default_rng(3), 32, 256, 256, 0.05)
    perm = np.random.RandomState(1234).permutation(256)
    follows = (perm[tokens[:, :-1]] == tokens[:, 1:]).mean()
    assert tokens.shape == (32, 257) and 0.93 < follows < 0.97
    again = token_chain.token_chain(np.random.default_rng(3), 32, 256, 256, 0.05)
    assert (tokens == again).all()
    spec = {"seq_len": 256, "alphabet": 256, "noise": 0.05}
    features, labels = token_chain.batch(
        token_chain.columns(np.random.default_rng(3), spec, 32)
    )
    assert (features["tokens"] == tokens[:, :-1]).all()
    assert (labels == tokens[:, 1:]).all() and labels.dtype == np.int32
    assert token_chain.units(spec) == {"tokens": 256, "records": 1}


def test_template_images_are_seeded_uint8():
    import numpy as np

    spec = {"height": 8, "width": 8, "channels": 3, "num_classes": 5}
    templates = template_images.shared_state(spec)
    labels = np.array([0, 4, 4])
    a = template_images.template_images(np.random.default_rng(1), labels, templates, 32)
    b = template_images.template_images(np.random.default_rng(1), labels, templates, 32)
    assert a.dtype == np.uint8 and a.shape == (3, 8, 8, 3) and (a == b).all()
    assert np.abs(a[1].astype(int) - templates[4].astype(int)).max() <= 32
