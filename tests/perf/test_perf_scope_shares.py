"""The ``.scope_lm`` / ``.scope_vision`` per-layer metrics: shares of
device-busy time by the model's own scopes, read from the program's op ->
scope map (``elasticdl_tpu/telemetry/op_scopes.py``) after the window."""

import copy
import json
import os
import subprocess
import sys

import numpy as np
import pytest
from perf_testlib import ROOT, TINY_CELL, manifest_with_tiny_cell, repo_manifest

from perf import manifest as manifest_lib
from perf import scope_shares

LM_CELLS = [
    "gpt2s_seq1024", "gpt2s_seq8192", "gpt2s_seq1024_dp4",
    "olmoe_1b7b_seq4096", "nemotron_twotower_seq8192", "joyai_flash_seq8192",
]
EXPERT_CELLS = LM_CELLS[3:]
REMAT_CELLS = LM_CELLS[4:]
VISION_CELLS = ["resnet50_imagenet_resident"]
NEW = {
    "forward_share.scope_lm": LM_CELLS,
    "backward_share.scope_lm": LM_CELLS,
    "optimizer_share.scope_lm": LM_CELLS,
    "recompute_share.scope_lm": REMAT_CELLS,
    "head_loss_share.scope_lm": LM_CELLS,
    "attention_other_share.scope_lm": LM_CELLS,
    "experts_other_share.scope_lm": EXPERT_CELLS,
    "block_other_share.scope_lm": LM_CELLS,
    "fused_across_share.scope_lm": LM_CELLS,
    "unattributed_share.scope_lm": LM_CELLS,
    "forward_share.scope_vision": VISION_CELLS,
    "backward_share.scope_vision": VISION_CELLS,
    "optimizer_share.scope_vision": VISION_CELLS,
    "unattributed_share.scope_vision": VISION_CELLS,
}

# a made-up step: seconds of self time an op, and the map that places it
MAP = {
    "fusion.1": ("block/attn/query", "forward", "matmul", ()),
    "flash_fwd.2": ("block/attn/flash_fwd", "forward", "kernel", ()),
    "fusion.3": ("block/attn/rope", "forward", "other", ()),
    "fusion.4": ("mtp/block/attn/join", "backward", "other", ()),
    "fusion.5": ("block/moe/dispatch", "recompute", "other", ()),
    "expert_gmm_fwd.6": ("block/moe/experts/expert_gmm_fwd", "recompute", "kernel", ()),
    "fusion.7": ("block/mlp", "backward", "other", ()),
    "fusion.8": ("lm_head", "backward", "matmul", ("optimizer",)),
    "fusion.9": ("loss", "forward", "other", ()),
    "fusion.10": ("optimizer", "optimizer", "other", ()),
    "fusion.11": ("block/moe/experts", "optimizer", "other", ()),
    "all-reduce.12": ("block/mlp/mlp_up", "backward", "collective", ()),
    "copy.13": (None, "forward", "other", ()),
}
SECONDS = {
    "fusion.1": 4.0, "flash_fwd.2": 8.0, "fusion.3": 2.0, "fusion.4": 1.0,
    "fusion.5": 3.0, "expert_gmm_fwd.6": 5.0, "fusion.7": 6.0,
    "fusion.8": 7.0, "fusion.9": 1.5, "fusion.10": 2.5, "fusion.11": 0.5,
    "all-reduce.12": 4.5, "copy.13": 2.0, "fusion.99": 3.0,
}
BUSY = sum(SECONDS.values())  # 50.0


def run_of(op_self_s=SECONDS, busy_s=BUSY):
    return {"trace": {"op_self_s": dict(op_self_s), "busy_s": busy_s}}


@pytest.fixture
def program_with_the_map(monkeypatch):
    from elasticdl_tpu.telemetry import op_scopes

    monkeypatch.setattr(op_scopes, "read", lambda: [dict(MAP)])


def reader(name, cell="joyai_flash_seq8192"):
    return manifest_lib.Cell(repo_manifest(), cell).reader(name)


@pytest.mark.parametrize(
    "name,percent",
    [
        ("forward_share.scope_lm", 2 * (4.0 + 8.0 + 2.0 + 1.5)),
        ("backward_share.scope_lm", 2 * (1.0 + 6.0 + 7.0)),
        ("recompute_share.scope_lm", 2 * (3.0 + 5.0)),
        ("optimizer_share.scope_lm", 2 * (2.5 + 0.5)),
        # lm_head and loss, whatever XLA fused into them
        ("head_loss_share.scope_lm", 2 * (7.0 + 1.5)),
        # under attn (the module's too), neither kernel nor matmul
        ("attention_other_share.scope_lm", 2 * (2.0 + 1.0)),
        # under moe, kind other, outside the optimizer
        ("experts_other_share.scope_lm", 2 * 3.0),
        ("block_other_share.scope_lm", 2 * (2.0 + 1.0 + 3.0 + 6.0 + 0.5)),
        ("fused_across_share.scope_lm", 2 * 7.0),
        # held without a part, and an op the map does not hold
        ("unattributed_share.scope_lm", 2 * (2.0 + 3.0)),
    ],
)
def test_a_share_sums_its_scopes(program_with_the_map, name, percent):
    assert reader(name)(run_of()) == pytest.approx(percent)


def test_the_phases_the_collectives_and_the_rest_add_up(program_with_the_map):
    run = run_of()
    total = sum(
        scope_shares.phase_share(run, phase) for phase in scope_shares.PHASES
    ) + scope_shares.collective_share(run) + scope_shares.unattributed_share(run)
    assert total == pytest.approx(100.0)
    assert scope_shares.collective_share(run) == pytest.approx(9.0)
    # the join is made once a run and kept in it
    assert scope_shares.attributed(run) is scope_shares.attributed(run)


def test_a_part_that_took_no_time_reads_zero_and_not_nothing(
    program_with_the_map,
):
    """A later PR that fuses, renames or removes a kernel cannot make one
    of these vanish from a line."""
    run = run_of({"fusion.1": 4.0}, 4.0)
    for name in NEW:
        cell = NEW[name][-1]
        value = reader(name, cell)(dict(run))
        assert value == (100.0 if name.startswith("forward_share") else 0.0), name


def test_without_a_trace_or_without_the_programs_map_nothing_is_read(
    monkeypatch, program_with_the_map
):
    """The parent of the PR that added ``op_scopes``: the readers are laid
    over its checkout too, return None and do not raise."""
    from elasticdl_tpu.telemetry import op_scopes

    for name, cells in NEW.items():
        assert reader(name, cells[0])({"trace": None}) is None
        assert reader(name, cells[0])(run_of(busy_s=0.0)) is None
    # a program whose op_scopes cannot be read, and one that has none
    monkeypatch.delattr(op_scopes, "read")
    for name, cells in NEW.items():
        assert reader(name, cells[0])(run_of()) is None
    monkeypatch.setitem(sys.modules, "elasticdl_tpu.telemetry.op_scopes", None)
    for name, cells in NEW.items():
        assert reader(name, cells[0])(run_of()) is None


def test_before_the_first_step_nothing_is_read(monkeypatch):
    from elasticdl_tpu.telemetry import op_scopes

    monkeypatch.setattr(op_scopes, "read", lambda: None)
    assert reader("forward_share.scope_lm")(run_of()) is None


@pytest.mark.parametrize("name", sorted(NEW))
def test_new_entry(name):
    manifest = repo_manifest()
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    entry = by_name[name]
    older = [m for m in manifest["per_layer"] if m["name"] not in NEW]
    # a list of cells only grows, at its end: a later cell joins behind these
    assert entry["workloads"][: len(NEW[name])] == NEW[name]
    assert entry["unit"] == "%" and entry["source"] == "device_trace"
    assert entry["layer"] in {m["layer"] for m in older}
    vision = name.endswith(".scope_vision")
    assert entry["moves"] == (
        "records_per_s_chip" if vision else "tokens_per_s_chip"
    )
    higher = name.startswith(("forward_share", "backward_share"))
    assert entry["better"] == ("higher" if higher else "lower")
    # the pinned ``.lm`` sets are left as they were
    assert not name.endswith(".lm")
    with open(os.path.join(ROOT, "perf", "layer_metrics", name + ".py")) as f:
        assert len(f.read().splitlines()) == 3


def test_the_manifest_holds_the_fourteen_and_stays_small():
    """Where an entry stands in ``per_layer`` is nobody's to pin: the driver
    takes a new entry at the end of the list, so the fourteen are held to
    being there, once each."""
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024
    names = [m["name"] for m in repo_manifest()["per_layer"]]
    assert sorted(n for n in names if n in NEW) == sorted(NEW)


@pytest.mark.compiles_a_model
def test_the_readers_on_a_real_trainers_map():
    """A tiny model's real compiled step on the CPU: each new metric's
    reader, found by name as the harness finds it, reads a number from the
    program's own map (a microsecond an op: counts, not a device time)."""
    from elasticdl_tpu.models import long_seq_transformer as lm
    from elasticdl_tpu.parallel.distributed import SPMDTrainer
    from elasticdl_tpu.parallel.mesh import MeshConfig
    from elasticdl_tpu.telemetry import op_scopes

    with open(os.path.join(ROOT, "tests", "perf", "configs", "tiny_joyai.json")) as f:
        params = json.load(f)["run"]["model_params"]
    features = {"tokens": np.zeros((2, 64), np.int32)}
    trainer = SPMDTrainer(
        MeshConfig.from_string("dp=1").create(), lm.custom_model(**params),
        lm.loss, lm.optimizer(), features,
    )
    trainer.train_step(
        trainer.place_batch(features),
        trainer.place_batch(np.zeros((2, 64), np.int32)),
        trainer.place_mask(2, 2),
    )
    (scopes,) = op_scopes.read()
    run = run_of({name: 1e-6 for name in scopes}, 1e-6 * len(scopes))
    cell = manifest_lib.Cell(repo_manifest(), "joyai_flash_seq8192")
    read = {
        name: cell.reader(name)(run) for name in NEW if name.endswith("_lm")
    }
    assert all(isinstance(v, float) for v in read.values()), read
    assert read["unattributed_share.scope_lm"] == 0.0
    assert read["recompute_share.scope_lm"] > 10.0
    assert read["optimizer_share.scope_lm"] > 1.0
    assert read["attention_other_share.scope_lm"] > 1.0
    assert read["experts_other_share.scope_lm"] > 1.0
    assert sum(
        read[f"{phase}_share.scope_lm"] for phase in scope_shares.PHASES
    ) == pytest.approx(100.0)


def manifest_with_the_new_metrics_in_the_tiny_cell():
    manifest = copy.deepcopy(manifest_with_tiny_cell())
    for metric in manifest["per_layer"]:
        if metric["name"] in NEW and metric["name"].endswith("_lm"):
            metric["workloads"].append(TINY_CELL)
    return manifest


@pytest.mark.compiles_a_model
def test_a_rehearsal_of_a_cell_that_lists_them_passes_as_before(tmp_path):
    """The harness reports per-layer metrics only from a chip
    (``tests/perf/test_perf_rehearsal.py``): the traced rehearsal of a cell
    that lists the new ones walks the same control flow, and writes none."""
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(manifest_with_the_new_metrics_in_the_tiny_cell()))
    env = {
        k: v for k, v in os.environ.items()
        if k not in ("XLA_FLAGS", "JAX_PLATFORMS", "BENCH_RUN")
    }
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cache")
    done = subprocess.run(
        [
            sys.executable, os.path.join(ROOT, "perf", "run.py"),
            "--workload", TINY_CELL, "--seed", str(2**31 + 37),
            "--seconds", "2", "--trace", "1", "--manifest", str(path),
            "--rehearse-cpu",
        ],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300,
    )
    assert done.returncode == 0, done.stderr[-2000:]
    result = json.loads(done.stdout.strip().splitlines()[-1])
    assert result["correct"] is True and result["metrics"] == {}
    cell = manifest_lib.Cell(
        manifest_with_the_new_metrics_in_the_tiny_cell(), TINY_CELL
    )
    listed = {m["name"] for m in cell.metrics("per_layer")}
    assert {n for n in NEW if n.endswith("_lm")} <= listed
