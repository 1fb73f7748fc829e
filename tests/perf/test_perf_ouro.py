"""The ``ouro_2p6b`` configuration's files: the parameter count of the cut
from the built tree, every published width under its own key, the FLOP
figures against a count by hand (layers x passes, head x passes), the two
``.loop`` entries that wait beside their readers and the cell read through a
manifest that carries them, the readers on synthetic runs, the plain
reference's constants, and the cell's control flow rehearsed on the CPU
through a test-only configuration (``configs/tiny_ouro.json``), its
comparison with the reference included.  The model against the reference at
tiny sizes is ``tests/test_looped_lm.py``; the compiled step's scopes are
``tests/test_op_scopes.py`` (family ``looped_stack``)."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from perf_testlib import (
    CONV_READERS,
    ROOT,
    manifest_with_tiny_cell,
    repo_manifest,
    stand_together_after,
)

from perf import manifest as manifest_lib

CELL = "ouro_2p6b_seq4096x2"
TINY_CELL = "tiny_ouro_tiny"
OWN_READERS = ("exit_heads_share.loop", "loop_overhead_share.loop")
LAYER = "looped stack (models/long_seq_transformer.py)"


def cell(manifest=None):
    return manifest_lib.Cell(manifest or repo_manifest(), CELL)


# ---- the configuration ---------------------------------------------------------------


def test_parameters_of_the_cut_are_the_files_count():
    """The model ``run.model_params`` builds has the 612,438,017 parameters
    ``reduced_why`` counts (shapes alone: nothing is allocated): 8 blocks,
    whatever the passes."""
    from elasticdl_tpu.models import long_seq_transformer as zoo

    config = cell().config
    model = zoo.custom_model(**config["run"]["model_params"])
    shapes = jax.eval_shape(
        lambda: model.init(
            jax.random.PRNGKey(0), {"tokens": jnp.zeros((1, 8), jnp.int32)}
        )
    )
    count = lambda tree: sum(  # noqa: E731
        int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(tree)
    )
    params = shapes["params"]
    assert set(params) == {
        "tok_embed", *(f"block_{i}" for i in range(8)), "RMSNorm_0", "exit_gate",
        "lm_head",
    }
    block = params["block_0"]
    assert count(block["attn"]) == 4 * 2048 * 2048
    assert block["attn"]["query"]["kernel"].shape == (2048, 16, 128)
    assert count({k: block[k] for k in ("mlp_gate", "mlp_up", "mlp_down")}) == 3 * 2048 * 5632
    assert {k for k in block if k.startswith("RMSNorm")} == {
        f"RMSNorm_{i}" for i in range(4)
    }
    assert all(count(params[f"block_{i}"]) == 51_388_416 for i in range(8))
    assert count(params["tok_embed"]) == count(params["lm_head"]) == 100_663_296
    assert count(params["RMSNorm_0"]) == 2048 and count(params["exit_gate"]) == 2049
    assert count(params) == 612_438_017
    assert "612,438,017" in config["reduced_why"] and "51,388,416" in config["reduced_why"]
    # what the step leaves in the state: the loss's parts and what it saw
    assert set(shapes) == {"params", "loss_parts", "loss_observed"}
    assert len(shapes["loss_observed"]) == 2 * config["total_ut_steps"]


def test_configuration_keeps_every_published_width():
    """Every number of the catalog row under its own key, the two cuts
    listed, and the model's fields equal to the keys they come from."""
    config = cell().config
    assert config["source"] == (
        "https://huggingface.co/ByteDance/Ouro-2.6B/blob/main/config.json"
    )
    assert config["reduced"] == ["num_hidden_layers", "layer_types"]
    assert config["published"] == {
        "num_hidden_layers": 48, "layer_types": ["full_attention"] * 48,
    }
    assert config["layer_types"] == ["full_attention"] * 8
    published = {
        "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 5632, "max_position_embeddings": 65536,
        "max_window_layers": 48, "model_type": "ouro", "num_attention_heads": 16,
        "num_key_value_heads": 16, "rms_norm_eps": 1e-06, "rope_scaling": None,
        "rope_theta": 1000000, "sliding_window": None, "tie_word_embeddings": False,
        "total_ut_steps": 4, "early_exit_threshold": 1, "use_sliding_window": False,
        "vocab_size": 49152,
    }
    assert {k: config[k] for k in published} == published
    assert config["num_hidden_layers"] == 8
    params = config["run"]["model_params"]
    fields = {
        "hidden_size": "embed_dim", "num_attention_heads": "num_heads",
        "head_dim": "head_dim", "intermediate_size": "mlp_width",
        "rms_norm_eps": "norm_eps", "rope_theta": "rope_theta",
        "vocab_size": "vocab_size", "num_hidden_layers": "num_layers",
        "total_ut_steps": "loop_steps",
    }
    assert {k: config[k] for k in fields} == {k: params[v] for k, v in fields.items()}
    assert "num_kv_heads" not in params  # 16 : 16, no grouping
    assert (params["norm"], params["mlp"], params["positions"]) == (
        "rmsnorm", "swiglu", "rope"
    )
    assert params["norm_outputs"] is True and params["use_bias"] is False
    assert params["remat_layers"] is True and "tie_embedding" not in params
    assert params["exit_entropy_weight"] == 0.1
    flops = config["flops"]
    assert (flops["layers"], flops["passes"], flops["vocab"]) == (8, 4, 49152)
    # the reference's constants are the file's
    module = cell().module("references", "ouro")
    assert (module.ROPE_THETA, module.RMS_NORM_EPS) == (1e6, 1e-6)
    assert module.TOTAL_UT_STEPS == config["total_ut_steps"] == 4
    assert module.EXIT_ENTROPY_WEIGHT == params["exit_entropy_weight"]
    assert "six chips" in config["deployment"] and "three times" in config["deployment"]
    assert len(config["assumed"]) >= 8 and len(config["not_built"]) >= 3
    for key in ("deployment", "assumed", "departures", "not_built", "reference",
                "reduced_why", "published"):
        assert config[key], key
    text = json.dumps(config)
    assert "TODO" not in text and "TO BE FOUND" not in text
    group = config["reference"]
    assert set(group["tolerance"]) == {"loss", "grad"} and group["module"] == "ouro"
    assert group["does_not_cover"] and "chip" in group["why"]


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(ROOT, "perf", "references", "ouro.py")) as f:
        source = f.read()
    imports = [line for line in source.splitlines() if line.startswith(("import ", "from "))]
    assert imports == [
        "from __future__ import annotations", "import math", "import jax",
        "import jax.numpy as jnp",
    ]
    assert 'default_matmul_precision("highest")' in source
    assert "pallas" not in source and "lax.scan" not in source


# ---- arithmetic -----------------------------------------------------------------


def test_flops_follow_the_work_and_not_the_parameters():
    """Layers x passes and the head x passes, counted by hand; with the
    layers counted once the step's share of the peak would read a quarter of
    what the chip does."""
    seq = 4096
    per_token = {k: v / seq for k, v in cell().flops_per_record().items()}
    d = 2048
    layer = 4 * d * d + 3 * d * 5632
    assert layer == 51_380_224
    assert per_token["layers"] == 6 * 8 * 4 * layer
    assert per_token["head"] == 6 * 4 * d * 49152 == 4 * 6 * 100_663_296
    assert per_token["gate"] == 6 * 4 * d
    pairs = seq * (seq + 1) // 2
    assert seq * per_token["causal_attention"] == 6 * 16 * 2 * 128 * 8 * 4 * pairs
    assert per_token["train"] == pytest.approx(
        sum(v for k, v in per_token.items() if k != "train")
    )
    # ISSUE 53: 113.8 T a step of 2 x 4,096 tokens: layers 80.8, the four
    # heads 19.8, causal attention 13.2; the head 17%
    step = {k: 2 * seq * v for k, v in per_token.items()}
    assert step["train"] == pytest.approx(113.8e12, rel=2e-3)
    assert step["layers"] == pytest.approx(80.8e12, rel=2e-3)
    assert step["head"] == pytest.approx(19.8e12, rel=2e-3)
    assert step["causal_attention"] == pytest.approx(13.2e12, rel=2e-3)
    assert step["head"] / step["train"] == pytest.approx(0.174, abs=2e-3)
    # the parameters' count (each layer once) would be a quarter of the layers
    assert per_token["layers"] / (6 * 8 * layer) == 4


def test_traffic_is_two_sequences_a_step_one_step_a_task():
    traffic = cell().traffic
    assert traffic["records"] == {
        "kind": "token_chain", "seq_len": 4096, "alphabet": 256, "noise": 0.05,
    }
    assert (traffic["batch_per_chip"], traffic["steps_per_task"]) == (2, 1)
    assert (traffic["tasks_per_interval"], traffic["num_shards"]) == (1, 8)
    assert (traffic["tasks_per_shard"], traffic["warmup_tasks"]) == (12, 1)
    assert (traffic["fill_intervals"], traffic["trace_min_steps"]) == (3, 8)


# ---- the readers -----------------------------------------------------------------


def synthetic_run():
    from perf import scope_shares

    run = {
        "cell": cell(),
        "trace": {"busy_s": 10.0, "op_self_s": {}, "details": {}},
        "traced_steps": 8,
    }
    run[scope_shares._KEY] = {
        "scopes": {
            ("block/attn/query", "forward", "matmul"): 2.0,
            ("block/mlp/mlp_up", "recompute", "matmul"): 1.5,
            ("exit/norm/RMSNorm", "forward", "other"): 0.1,
            ("exit/exit_gate", "backward", "matmul"): 0.05,
            ("loss/lm_head", "forward", "matmul"): 0.6,
            ("loss/lm_head", "recompute", "matmul"): 0.6,
            ("loss/lm_head", "backward", "matmul"): 1.2,
            ("loss", "backward", "other"): 0.45,
            ("lm_head", "optimizer", "other"): 0.3,
            ("loop", "forward", "other"): 0.04,
            ("loop", "backward", "other"): 0.06,
            ("optimizer", "optimizer", "other"): 0.2,
        },
        "unattributed": 0.0, "fused_across": 0.0,
    }
    return run


def test_share_readers_on_a_synthetic_run():
    read = {name: cell().reader(name) for name in OWN_READERS}
    run = synthetic_run()
    # the exits' norm and gate, the head's three products, the loss; not the
    # optimizer's update of the head
    assert read["exit_heads_share.loop"](run) == pytest.approx(30.0)
    # the loop's own ops alone: a block's ops inside the loop are the block's
    assert read["loop_overhead_share.loop"](run) == pytest.approx(1.0)
    for name in OWN_READERS[:2]:
        assert read[name]({**run, "trace": None, "_scope_shares": None}) is None, name
    # a program with no loop: the exits' share is the head's and the loss's
    run["_scope_shares"]["scopes"] = {
        ("lm_head", "forward", "matmul"): 1.0, ("loss", "forward", "other"): 0.5,
        ("block/attn/query", "forward", "matmul"): 2.0,
    }
    assert read["exit_heads_share.loop"](run) == pytest.approx(15.0)
    assert read["loop_overhead_share.loop"](run) == 0.0


def test_cell_reports_the_lm_metrics_it_can():
    """What the cell reports at least: a later PR may put it on further lists
    and add cells and configurations beside it."""
    manifest = repo_manifest()
    this = cell(manifest)
    names = {m["name"] for m in this.metrics("per_layer")}
    assert {
        "input_wait_share.lm", "dispatch_ms.lm", "step_device_ms.lm", "step_mfu.lm",
        "bookkeeping_ms.lm", "assemble_ms.lm", "place_ms.lm", "enqueue_ms.lm",
        "fetch_wait_ms.lm", "producer_batch_ms.lm", "producer_busy_share.lm",
        "setup_trace_s", "setup_lower_s", "setup_compile_s",
        "flash_time_share.lm", "flash_roofline.lm", "flash_fwd_roofline.lm",
        "flash_dq_roofline.lm", "flash_dkv_roofline.lm",
        "forward_share.scope_lm", "backward_share.scope_lm",
        "optimizer_share.scope_lm", "recompute_share.scope_lm",
        "head_loss_share.scope_lm", "attention_other_share.scope_lm",
        "block_other_share.scope_lm", "fused_across_share.scope_lm",
        "unattributed_share.scope_lm",
    } <= names
    assert "collective_exposed_share.lm" not in names  # dp4's alone
    assert not [n for n in names if "expert" in n]  # a dense model
    assert {m["name"] for m in this.metrics("end_to_end")} == {
        "tokens_per_s_chip", "peak_hbm_gb", "setup_s"
    }
    assert (this.chips, this.traffic_name) == (1, "seq4096x2")
    assert this.reference().__name__.endswith("ouro")
    entry = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert entry["config"] == "ouro_2p6b" and len(entry["why"]) <= 200
    config = next(c for c in manifest["configs"] if c["name"] == "ouro_2p6b")
    assert config["reduced"] == this.config["reduced"]
    assert config["source"] == this.config["source"] and len(config["why"]) <= 200
    # at most a quarter of the cells, rounded down, on four chips
    four = sum(w["chips"] == 4 for w in manifest["workloads"])
    assert four <= max(1, len(manifest["workloads"]) // 4)


def test_the_cells_own_entries_stand_in_the_manifest():
    """The two readers of what this configuration adds, each this cell's
    first and each a share of the step's device time, so moving its rate
    (the passes a token takes is the program's counter and no metric: every
    pass runs in training whatever the gate says).  They waited as data
    beside their readers (``loop_entries.json``) until PR 59 listed them,
    after ``lfm2_24b_a2b_seq4096x4``'s seven ``.conv`` entries.  Held here:
    they keep the manifest's rules and the cell reports them through the
    files that are there; what follows them in the list is held by nothing."""
    manifest = repo_manifest()
    listed = [m["name"] for m in manifest["per_layer"]]
    assert len(set(listed)) == len(listed)
    own = [m for m in manifest["per_layer"] if m["name"] in OWN_READERS]
    assert tuple(m["name"] for m in own) == OWN_READERS
    assert stand_together_after(listed, OWN_READERS, CONV_READERS)
    keys = ["name", "unit", "better", "source", "layer", "moves", "workloads"]
    assert all(list(m) == keys for m in own)
    assert all(m["workloads"][:1] == [CELL] for m in own)
    assert all(m["moves"] == "tokens_per_s_chip" for m in own)
    assert all(m["better"] in ("lower", "higher") for m in own)
    assert [m["source"] for m in own] == ["device_trace", "device_trace"]
    assert [m["unit"] for m in own] == ["%", "%"]
    assert all(m["better"] == "lower" for m in own)
    assert {m["layer"] for m in own} == {LAYER} and len(LAYER) <= 200
    this = cell(manifest)
    assert set(OWN_READERS) <= {m["name"] for m in this.metrics("per_layer")}
    for name in OWN_READERS:
        assert callable(this.reader(name)), name


# ---- the cell's control flow on the CPU ---------------------------------------


def manifest_with_tiny_ouro() -> dict:
    manifest = manifest_with_tiny_cell()
    manifest["configs"].append({
        "name": "tiny_ouro",
        "source": "none: CPU rehearsal of the harness only",
        "file": "tests/perf/configs/tiny_ouro.json",
        "reduced": [],
        "why": "two layers of width 128 run four times, an exit gate and the head a pass: control flow only",
    })
    manifest["workloads"].append({
        "name": TINY_CELL, "config": "tiny_ouro", "traffic": "tiny",
        "chips": 1, "why": "2 x 64 tokens a step on the CPU backend through the loop",
    })
    for metric in manifest["end_to_end"] + manifest["per_layer"]:
        if CELL in metric.get("workloads", []):
            metric["workloads"].append(TINY_CELL)
    return manifest


@pytest.mark.compiles_a_model
def test_cell_rehearsal_on_cpu(tmp_path):
    """Two tiny layers four times through ``perf/run.py --rehearse-cpu`` (the
    traced run, which measures untraced first): the path driver, the stacked
    dispatch, the flash kernels interpreted inside the scan, each layer
    recomputed, the loss by its rows with the head inside it, and the
    comparison with the shipped reference, whose constants are this shape's
    too."""
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(manifest_with_tiny_ouro()))
    env = {
        k: v for k, v in os.environ.items()
        if k not in ("XLA_FLAGS", "JAX_PLATFORMS", "BENCH_RUN")
    }
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cache")
    done = subprocess.run(
        [
            sys.executable, os.path.join(ROOT, "perf", "run.py"),
            "--workload", TINY_CELL, "--seed", str(2**31 + 53), "--seconds", "2",
            "--trace", "1", "--manifest", str(path), "--rehearse-cpu",
        ],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600,
    )
    assert done.returncode == 0, done.stderr[-2000:]
    info, result = map(json.loads, done.stdout.strip().splitlines()[-2:])
    assert result["correct"] is True, info["checks"]
    assert result["metrics"] == {} and result["failed"] == 0
    assert info["compiles_in_window"] == 0
    assert info["last_loss"] < info["first_loss"]
    compared = info["reference"]
    assert compared["agrees"] is True and info["checks"]["reference_agrees"] is True
    assert set(compared["by_block"]) == {
        "tok_embed", "block_0", "block_1", "RMSNorm_0", "exit_gate", "lm_head",
    }
    assert 0 < compared["by_block"]["exit_gate"] < 0.15
