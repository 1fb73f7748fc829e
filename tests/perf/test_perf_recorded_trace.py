"""The reduction on slices of traces recorded on the chip by PR 23 (TPU v5
lite; ``perf/run.py --trace 1 --keep-trace``, cut with
``trace_reduce.save_events``): six steps of ``resnet50_imagenet_e2e`` with
the idle gaps between its bursts, one step of ``gpt2s_seq8192``, and the
28 ms of a ``gpt2s_seq1024_dp4`` step in which its gradient all-reduces run,
on all four chips.  The
numbers pinned here are what the reduction gave when they were recorded: a
change to the reduction that moves them is a change to the yardstick."""

import os

import pytest
from perf_testlib import ROOT

from perf import layer_readers, trace_reduce as tr

TESTDATA = os.path.join(ROOT, "perf", "testdata")
# the recorded slices predate the kernels' names (PR 24): their Mosaic
# custom-calls are ``attn.N`` on the op line and the only mark of a kernel is
# the call target in the op's detail.  The tests on them read through this
# pattern; ``layer_readers.FLASH_KERNELS`` finds nothing there
UNNAMED_FLASH_KERNELS = r"tpu_custom_call"


@pytest.fixture(scope="module")
def resnet():
    return tr.reduce(tr.load_events(os.path.join(TESTDATA, "resnet50_e2e_slice.json.gz")))


@pytest.fixture(scope="module")
def lm():
    return tr.reduce(tr.load_events(os.path.join(TESTDATA, "gpt2s_seq8192_slice.json.gz")))


def test_resnet_busy_union(resnet):
    assert resnet["devices"] == 1
    assert resnet["window_s"] == pytest.approx(0.45)
    # six steps of 44.99 ms: the union, where the plain sum of the op
    # line's events would count nested ones twice
    assert resnet["busy_s"] == pytest.approx(0.269941303, rel=1e-6)
    assert sum(resnet["op_self_s"].values()) == pytest.approx(resnet["busy_s"], rel=1e-3)


def test_resnet_idle_gaps_are_the_host_waiting_for_input(resnet):
    gaps = resnet["idle_gaps_s"]
    assert gaps["perf:input_wait"] == pytest.approx(0.12540121, rel=1e-6)
    assert gaps["perf:dispatch"] == pytest.approx(0.043497059, rel=1e-6)
    assert sum(gaps.values()) == pytest.approx(
        resnet["window_s"] - resnet["busy_s"], rel=1e-3
    )
    assert tr.breakdown(resnet)["idle_gaps"][0][0] == "perf:input_wait"


def test_resnet_has_no_kernel_and_no_collective(resnet):
    assert tr.matching_seconds(resnet, layer_readers.FLASH_KERNELS) == 0
    assert tr.matching_seconds(resnet, UNNAMED_FLASH_KERNELS) == 0
    assert resnet["collective_exposed_s"] == 0
    top = tr.breakdown(resnet)["device_ops"]
    assert len(top) == 10 and top[0][0] == "convert_reduce_fusion"


def test_lm_step_and_its_flash_kernels(lm, monkeypatch):
    assert lm["busy_s"] == pytest.approx(0.215772458, rel=1e-6)
    assert tr.matching_seconds(lm, layer_readers.FLASH_KERNELS) == 0
    monkeypatch.setattr(layer_readers, "FLASH_KERNELS", UNNAMED_FLASH_KERNELS)
    flash = tr.matching_seconds(lm, layer_readers.FLASH_KERNELS)
    # 36 Mosaic calls a step: 12 layers x (forward, dQ, dK/dV)
    kernels = [
        n for n, d in lm["details"].items()
        if "tpu_custom_call" in d and n in lm["op_self_s"]
    ]
    assert len(kernels) == 36 and all(n.startswith("attn.") for n in kernels)
    assert flash == pytest.approx(0.114112512, rel=1e-6)
    run = {
        "trace": lm, "traced_steps": 1,
        "flops_per_step_chip": {
            "train": 8192 * 1194.177024e6,
            "causal_attention": 6 * 12 * 8192 * 8192 * 768,
        },
        "peaks": {"bf16_flops_per_s": 197e12},
    }
    assert layer_readers.flash_time_share(run) == pytest.approx(52.885, abs=0.01)
    assert layer_readers.flash_roofline(run) == pytest.approx(16.506, abs=0.01)
    assert layer_readers.step_mfu(run) == pytest.approx(23.014, abs=0.01)
    assert 0 < layer_readers.flash_roofline(run) < 100
    # a model whose FLOP function names no attention part has no such share
    run["flops_per_step_chip"] = {"train": 1e12}
    assert layer_readers.flash_roofline(run) is None
    # the loss's row loop: a while op whose body is taken out of its time
    assert lm["op_self_s"]["dynamic-update-slice.8"] == pytest.approx(0.010408353, rel=1e-6)


def test_dp4_all_reduces_are_exposed_on_every_chip():
    events = tr.load_events(
        os.path.join(TESTDATA, "gpt2s_dp4_allreduce_slice.json.gz")
    )
    reduced = tr.reduce(events)
    assert reduced["devices"] == 4
    assert reduced["window_s"] == pytest.approx(0.028)
    assert reduced["busy_s"] == pytest.approx(0.027309526, rel=1e-6)
    # four synchronous all-reduces on the op line: no compute op runs beside
    # them, so all of their time is exposed (5.64 ms of the 28)
    collectives = {
        n: s for n, s in reduced["op_self_s"].items() if tr.COLLECTIVE.search(n)
    }
    assert sorted(collectives) == [
        "all-reduce.197", "all-reduce.199", "all-reduce.200", "all-reduce.201",
    ]
    assert reduced["collective_exposed_s"] == pytest.approx(0.00563771525, rel=1e-6)
    assert sum(collectives.values()) == pytest.approx(
        reduced["collective_exposed_s"], rel=1e-6
    )
    run = {"trace": reduced}
    assert layer_readers.collective_exposed_share(run) == pytest.approx(
        20.135, abs=0.01
    )
