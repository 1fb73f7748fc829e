"""``peak_hbm_gb``'s five per-layer readers (``perf/memory_shares.py``, the
files under ``perf/layer_metrics/`` of their names): their entries, listed
in ``BENCHMARK.json`` since PR 59, obey the manifest's rules and every cell
that reports ``peak_hbm_gb`` reports them; each reader gives its number on
a synthetic reading, None for a program without the function (the parent:
these files are laid over its checkout too) and None for the split at the
peak where the reading is off XLA's figure; the account adds up to the
allocator's peak to the byte; and on a real trainer on the CPU the readers
find what the CPU can give.  The reading itself is
``tests/test_op_scopes.py``, ``tests/test_live_bytes.py`` and
``tests/test_memory.py``."""

import json
import os

import pytest
from perf_testlib import (
    CONV_READERS as CONV,
    HBM_READERS as READERS,
    ROOT,
    SWA_READERS,
    repo_manifest,
    stand_together_after,
)

from perf import manifest as manifest_lib
from perf import memory_shares

LAYER = "SPMD step (parallel/distributed.py, trainer/step.py)"
GIB = 1 << 30
# what waited beside its readers up to PR 59, in the order it was listed in
LOOP = ("exit_heads_share.loop", "loop_overhead_share.loop")


def synthetic_run(ratio_in_range=True) -> dict:
    """A run whose reading is a 2 GiB state on a 16 GiB chip under a step of
    3 GiB of temporaries."""
    live = [
        ["opt_state", "", "argument", GIB], ["params", "", "argument", GIB // 2],
        ["block/mlp/mlp_up", "forward", "residual", GIB],
        ["block/attn/out", "forward", "residual", GIB // 2],
        ["lm_head", "forward", "residual", GIB // 4],
        ["loss", "backward", "temporary", GIB // 8],
        ["loss/lm_head", "recompute", "temporary", GIB // 8],
        ["lm_head", "optimizer", "temporary", GIB // 16],
        ["block/mlp/mlp_down", "backward", "temporary", GIB // 2],
    ]
    program = {
        "xla": {
            "argument": 2 * GIB + 4096, "output": 2 * GIB + 512, "alias": 2 * GIB,
            "temp": 3 * GIB, "generated_code": GIB // 8, "peak": 4 * GIB,
        },
        "peak_bytes": sum(row[-1] for row in live), "ratio": 1.02,
        "live": live if ratio_in_range else None,
    }
    small = {"xla": {**program["xla"], "temp": GIB}, "live": [["x", "", "argument", 1]]}
    return {
        "cell": None,
        "_step_memory": {
            "programs": [program, small],
            "state": {"device": 3, "params": GIB // 2, "opt_state": GIB,
                      "batch_stats": GIB // 2, "step": 4, "total": 2 * GIB + 4},
            "undonated": [], "other_arrays": 3 * 65536,
            "allocator": {
                "id": 3, "bytes_in_use": 2 * GIB, "peak_bytes_in_use": 2 * GIB + 400000,
                "bytes_reserved": 3 * GIB, "peak_bytes_reserved": 4 * GIB,
                "largest_free_block_bytes": GIB, "bytes_limit": 16 * GIB,
            },
        },
    }


EXPECTED = {
    "state_hbm_gb": (2 * GIB + 4) / 1e9,
    "step_temp_hbm_gb": 3 * GIB / 1e9,
    "residuals_at_peak_hbm_gb": (GIB + GIB // 2 + GIB // 4) / 1e9,
    # the head's and the loss's own, every phase; never the parameters
    "head_loss_at_peak_hbm_gb": (GIB // 4 + 2 * (GIB // 8) + GIB // 16) / 1e9,
    # the step by XLA's peak over its arguments, not by ``temp``
    "hbm_unexplained_gb": (
        6 * GIB + 400000
        - (2 * GIB + 4) - 3 * 65536 - (4 * GIB - 2 * GIB - 4096) - GIB // 8
    ) / 1e9,
}


@pytest.mark.parametrize("name", READERS)
def test_an_entry_stands_in_the_manifest_and_obeys_its_rules(name):
    """The entry keeps the manifest's rules, and every cell that reports
    ``peak_hbm_gb`` reports it through the file that is there."""
    manifest = repo_manifest()
    listed = [m["name"] for m in manifest["per_layer"]]
    assert len(set(listed)) == len(listed)
    (entry,) = [m for m in manifest["per_layer"] if m["name"] == name]
    assert list(entry) == [
        "name", "unit", "better", "source", "layer", "moves", "workloads"
    ]
    assert (entry["unit"], entry["better"], entry["source"]) == (
        "GB", "lower", "program_counter"
    )
    # the end-to-end metric with no per-layer metric at all before these
    assert entry["moves"] == "peak_hbm_gb"
    moved = next(m for m in manifest["end_to_end"] if m["name"] == "peak_hbm_gb")
    assert (moved["unit"], moved["better"]) == (entry["unit"], entry["better"])
    assert entry["layer"] == LAYER
    before = manifest["per_layer"][: listed.index(READERS[0])]
    assert LAYER in {m["layer"] for m in before}
    # the five lists are alike, name only cells of the manifest, and name
    # EVERY cell that reports peak_hbm_gb: a cell added at the end of
    # ``workloads`` goes at the end of these lists too
    cells = [w["name"] for w in manifest["workloads"]]
    five = [m["workloads"] for m in manifest["per_layer"] if m["name"] in READERS]
    assert all(one == entry["workloads"] for one in five)
    assert len(set(entry["workloads"])) == len(entry["workloads"])
    assert set(entry["workloads"]) <= set(cells)
    assert set(moved.get("workloads", cells)) <= set(entry["workloads"])
    for workload in entry["workloads"]:
        cell = manifest_lib.Cell(manifest, workload)
        assert name in {m["name"] for m in cell.metrics("per_layer")}
    assert callable(manifest_lib.Cell(manifest, entry["workloads"][0]).reader(name))
    assert len(name) <= 64 and os.path.exists(
        os.path.join(ROOT, "perf", "layer_metrics", name + ".py")
    )


def test_the_fourteen_that_waited_stand_in_their_order():
    """``conv_entries.json``'s seven, ``loop_entries.json``'s two and
    ``memory_entries.json``'s five, one after another after the eight
    ``.swa`` entries; what follows them is held by nothing."""
    listed = [m["name"] for m in repo_manifest()["per_layer"]]
    assert stand_together_after(listed, CONV + LOOP + READERS, SWA_READERS)
    assert len(set(CONV + LOOP + READERS)) == 14


@pytest.mark.parametrize("name", READERS)
def test_a_reader_gives_its_number_on_a_synthetic_reading(name):
    read = manifest_lib.Cell(repo_manifest(), "gpt2s_seq1024").reader(name)
    assert read(synthetic_run()) == pytest.approx(EXPECTED[name], rel=1e-12)


@pytest.mark.parametrize("name", READERS)
def test_a_reader_returns_none_for_a_program_without_the_function(name, monkeypatch):
    """The parent's ``telemetry/memory.py`` has no ``read_step_memory``."""
    from elasticdl_tpu.telemetry import memory

    monkeypatch.delattr(memory, "read_step_memory")
    read = manifest_lib.Cell(repo_manifest(), "gpt2s_seq1024").reader(name)
    run = {"cell": None}
    assert read(run) is None
    assert run["_step_memory"] is None  # asked once a run


@pytest.mark.parametrize("name", READERS)
def test_a_reading_off_xlas_figure_gives_no_split_and_keeps_the_totals(name):
    read = manifest_lib.Cell(repo_manifest(), "gpt2s_seq1024").reader(name)
    value = read(synthetic_run(ratio_in_range=False))
    if name in ("residuals_at_peak_hbm_gb", "head_loss_at_peak_hbm_gb"):
        assert value is None
    else:
        assert value == pytest.approx(EXPECTED[name], rel=1e-12)


def test_the_account_adds_up_to_the_allocators_peak_to_the_byte():
    run = synthetic_run()
    account = memory_shares.account(run)
    terms = ("state", "other_arrays", "step", "code", "unexplained")
    assert sum(account[term] for term in terms) == account["peak"]
    assert account["unexplained"] >= 0  # the allocator's packing
    # the benchmark's peak_hbm_gb of that device (perf/run.py::describe_device)
    allocator = run["_step_memory"]["allocator"]
    assert account["peak"] == (
        allocator["peak_bytes_in_use"] + allocator["peak_bytes_reserved"]
    )
    assert account["temp"] == 3 * GIB  # the largest program's, not the newest's
    assert account["step"] == 2 * GIB - 4096
    # without allocator figures (the CPU) there is nothing to account for
    run["_step_memory"]["allocator"] = {}
    assert memory_shares.account(run) is None
    assert memory_shares.hbm_unexplained_gb(run) is None
    assert memory_shares.state_hbm_gb(run) is not None


def test_the_readers_on_a_trainer_that_ran_a_step(tmp_path, monkeypatch):
    """The real path on the CPU: the program's counter through the readers,
    and the whole reading kept where ``PERF_KEEP_STEP_MEMORY`` says."""
    import flax.linen as nn
    import jax.numpy as jnp
    import numpy as np
    import optax

    from elasticdl_tpu.parallel.distributed import SPMDTrainer
    from elasticdl_tpu.parallel.mesh import MeshConfig

    class Tiny(nn.Module):
        @nn.compact
        def __call__(self, features, training=False):
            return nn.Dense(256, name="lm_head")(features["x"])

    features = {"x": np.ones((64, 256), np.float32)}
    trainer = SPMDTrainer(
        MeshConfig.from_string("dp=2").create(), Tiny(),
        lambda labels, outputs: jnp.mean((outputs - labels) ** 2),
        optax.adam(0.1), features,
    )
    run = {"cell": type("Cell", (), {"name": "tiny"})()}
    assert memory_shares.state_hbm_gb(dict(run)) is None  # before the first step
    trainer.train_step(
        trainer.place_batch(features),
        trainer.place_batch(np.ones((64, 256), np.float32)),
        trainer.place_mask(64, 64),
    )
    monkeypatch.setenv(memory_shares.KEEP_ENV, str(tmp_path))
    kernel = (256 * 256 + 256) * 4
    assert memory_shares.state_hbm_gb(run) == pytest.approx((3 * kernel + 8) / 1e9)
    assert memory_shares.step_temp_hbm_gb(run) > 0
    assert memory_shares.hbm_unexplained_gb(run) is None  # no allocator figures
    residuals = memory_shares.residuals_at_peak_hbm_gb(run)
    head = memory_shares.head_loss_at_peak_hbm_gb(run)
    program = run["_step_memory"]["programs"][0]
    if program["live"] is None:  # off XLA's total at this size: no split
        assert residuals is None and head is None
    else:
        assert 0 <= residuals <= program["peak_bytes"] / 1e9
        assert 0 < head <= program["peak_bytes"] / 1e9
    with open(tmp_path / "tiny.step_memory.json") as f:
        kept = json.load(f)
    assert kept["account"] is None and kept["read_s"] >= 0
    assert kept["state"]["total"] == 3 * kernel + 8

