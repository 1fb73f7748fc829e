"""The ``olmoe_1b7b`` configuration's files: the plain reference against the
zoo model with OLMoE's fields at sizes a CPU holds, the FLOP figures from
shapes, the expert layer's readers on hand-made runs, and the cell's control
flow rehearsed on the CPU through a test-only configuration of one tiny
layer (``configs/tiny_olmoe.json``, ``references/plain_olmoe.py``)."""

import copy
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from perf_testlib import ROOT, manifest_with_tiny_cell, repo_manifest

from perf import expert_rooflines, manifest as manifest_lib, reference

CELL = "olmoe_1b7b_seq4096"
TINY_OLMOE_CELL = "tiny_olmoe_tiny"


def manifest_with_tiny_olmoe() -> dict:
    manifest = copy.deepcopy(manifest_with_tiny_cell())
    manifest["configs"].append({
        "name": "tiny_olmoe",
        "source": "none: CPU rehearsal of the harness only",
        "file": "tests/perf/configs/tiny_olmoe.json",
        "reduced": [],
        "why": "OLMoE's block at one layer of width 64, 8 experts: control flow only",
    })
    manifest["workloads"].append({
        "name": TINY_OLMOE_CELL, "config": "tiny_olmoe", "traffic": "tiny",
        "chips": 1, "why": "2 x 64 tokens a step on the CPU backend through the expert path",
    })
    for metric in manifest["end_to_end"] + manifest["per_layer"]:
        if CELL in metric.get("workloads", []):
            metric["workloads"].append(TINY_OLMOE_CELL)
    return manifest


def shipped_reference():
    return manifest_lib.Cell(repo_manifest(), CELL).module("references", "olmoe")


# ---- the reference against the zoo model ---------------------------------------


def tiny_olmoe(dtype: str, experts=8, per_token=2, layers=2, normed=False):
    from elasticdl_tpu.models import long_seq_transformer as zoo

    model = zoo.custom_model(
        vocab_size=512, embed_dim=64, num_heads=2, num_layers=layers, dtype=dtype,
        norm="rmsnorm", norm_eps=1e-5, use_bias=False, positions="rope",
        qk_norm=True, num_experts=experts, experts_per_token=per_token,
        expert_width=32, norm_topk_prob=normed,
    )
    tokens = np.random.default_rng(3).integers(512, size=(4, 65)).astype(np.int32)
    features, labels = {"tokens": tokens[:, :-1]}, tokens[:, 1:]
    variables = model.init(jax.random.PRNGKey(1), features, training=False)
    leaves, tree = jax.tree_util.tree_flatten(variables["params"])
    keys = jax.random.split(jax.random.PRNGKey(7), len(leaves))
    params = tree.unflatten(
        [x + 0.05 * jax.random.normal(k, x.shape) for x, k in zip(leaves, keys)]
    )
    state = {k: v for k, v in variables.items() if k != "params"}

    def system(p):
        logits, sown = model.apply(
            {"params": p, **state}, features, training=True, mutable=list(state)
        )
        loss = zoo.loss(labels, logits)
        for leaf in jax.tree_util.tree_leaves(sown["losses"]):
            loss = loss + jnp.sum(leaf)
        return loss.astype(jnp.float32)

    return system, params, features, labels


def compared(system, module, params, features, labels, **constants) -> dict:
    """The module's constants (what the tree does not carry) are set for the
    call; a fresh lambda keeps a jit cache from remembering older ones."""
    for name, value in constants.items():
        setattr(module, name, value)
    loss_sys, grads_sys = jax.jit(jax.value_and_grad(system))(params)
    loss_ref, grads_ref = jax.jit(
        lambda p, f, l: module.loss_and_grads(p, f, l)
    )(params, features, labels)
    assert jax.tree_util.tree_structure(grads_ref) == jax.tree_util.tree_structure(params)
    return jax.device_get(reference.errors(loss_sys, grads_sys, loss_ref, grads_ref))


# float32 against float32: the two differ by the order of their sums (measured
# here: loss equal, gradient 6e-7..9e-7).  bfloat16 activations against
# float32: 0.4% a rounding, and the experts' weights (1/8 at 8 experts, raw
# softmax) multiply rounded products (measured: loss 1.4e-7, gradient 5.8%).
# A wrong term moves the loss or the gradient past these limits (below)
TOLERANCE = {"float32": (1e-5, 1e-5), "bfloat16": (1e-3, 0.08)}


@pytest.mark.parametrize(
    "dtype,shape",
    [
        ("float32", dict(experts=8, per_token=2, layers=2)),
        ("bfloat16", dict(experts=8, per_token=2, layers=2)),
        ("float32", dict(experts=64, per_token=8, layers=1)),
        ("float32", dict(experts=8, per_token=2, layers=2, normed=True)),
    ],
    ids=["float32", "bfloat16", "float32_top8_of_64", "float32_norm_topk_prob"],
)
@pytest.mark.compiles_a_model
def test_olmoe_reference_agrees_with_the_zoo_model(dtype, shape):
    system, params, features, labels = tiny_olmoe(dtype, **shape)
    got = compared(
        system, shipped_reference(), params, features, labels,
        EXPERTS_PER_TOKEN=shape["per_token"], NORM_TOPK_PROB=shape.get("normed", False),
    )
    loss_limit, grad_limit = TOLERANCE[dtype]
    assert got["loss_err"] <= loss_limit and got["grad_err"] <= grad_limit, got
    assert set(got["by_block"]) == {
        "tok_embed", "RMSNorm_0", "lm_head",
        *(f"block_{i}" for i in range(shape["layers"])),
    }


def without_qk_norm(module):
    original = module.attention

    def attention(x, a):
        identity = {"scale": jnp.ones_like(a["q_norm"]["scale"])}
        return original(x, {**a, "q_norm": identity, "k_norm": identity})

    return {"attention": attention}


FAULTS = {
    "causal_mask_dropped": lambda m: {
        "visible": lambda rows, cols: jnp.ones((len(rows), len(cols)), bool)
    },
    "top7_in_place_of_top8": lambda m: {"EXPERTS_PER_TOKEN": 7},
    "weights_renormalised": lambda m: {"NORM_TOPK_PROB": True},
    "no_qk_norm": without_qk_norm,
    "no_rope": lambda m: {"rotary": lambda x: x},
}


@pytest.mark.parametrize("fault", FAULTS)
@pytest.mark.compiles_a_model
def test_olmoe_comparison_fails_on_wrong_mathematics(monkeypatch, fault):
    """Each wrong term, in float32 where nothing else differs, is outside the
    bf16 limits (measured: the eighth expert dropped moves the loss 2.9e-3
    and the gradient 9.5%; no QK-norm 14%; the others 40% and more) and four
    orders over the float32 agreement."""
    shape = dict(experts=64, per_token=8, layers=1)
    system, params, features, labels = tiny_olmoe("float32", **shape)
    module = shipped_reference()
    constants = {"EXPERTS_PER_TOKEN": 8, "NORM_TOPK_PROB": False}
    for name, value in FAULTS[fault](module).items():
        if name in constants:
            constants[name] = value
        else:
            monkeypatch.setattr(module, name, value)
    got = compared(system, module, params, features, labels, **constants)
    loss_limit, grad_limit = TOLERANCE["bfloat16"]
    assert not (got["loss_err"] <= loss_limit and got["grad_err"] <= grad_limit), got
    assert got["grad_err"] > 0.05, (fault, got)


@pytest.mark.compiles_a_model
def test_olmoe_control_in_fp8_fails():
    """The reference in the program's place with its weights rounded through
    float8 (e4m3), the nearest precision below the bfloat16 the
    configuration states: not correct under the bf16 tolerance."""
    _, params, features, labels = tiny_olmoe("bfloat16")
    module = shipped_reference()
    module.EXPERTS_PER_TOKEN, module.NORM_TOPK_PROB = 2, False
    rounded = jax.tree_util.tree_map(
        lambda x: x.astype(jnp.float8_e4m3fn).astype(jnp.float32), params
    )
    loss_sys, grads_sys = module.loss_and_grads(rounded, features, labels)
    loss_ref, grads_ref = module.loss_and_grads(params, features, labels)
    got = jax.device_get(reference.errors(loss_sys, grads_sys, loss_ref, grads_ref))
    assert got["grad_err"] > 1.5 * TOLERANCE["bfloat16"][1], got


# ---- arithmetic -----------------------------------------------------------------


def test_olmoe_flops_come_from_the_published_shapes():
    cell = manifest_lib.Cell(repo_manifest(), CELL)
    per_record = cell.flops_per_record()
    per_token = {k: v / 4096 for k, v in per_record.items()}
    assert per_token["experts"] == 6 * 8 * 3 * 2048 * 1024
    assert per_token["causal_attention"] == 6 * 1 * 4096 * 2048
    assert per_token["head"] == 6 * 2048 * 50304
    assert per_token["train"] == pytest.approx(1071.9e6, rel=1e-4)
    step = {k: 2 * v for k, v in per_record.items()}  # 2 sequences a step
    assert step["train"] == pytest.approx(8.78e12, rel=1e-3)
    assert step["head"] / step["train"] == pytest.approx(0.577, abs=0.002)
    assert step["experts"] == pytest.approx(2.474e12, rel=1e-3)
    # parameters, from the same shapes: one layer, embedding, head
    d, f, experts, vocab = 2048, 1024, 64, 50304
    layer = experts * 3 * d * f + 4 * d * d + d * experts + 4 * d
    assert layer == 419_569_664 and experts * 3 * d * f == 402_653_184
    assert layer + 2 * vocab * d + d == 625_616_896


def test_expert_kernels_are_compute_bound_at_the_cells_shapes():
    from perf.peaks import peaks_for

    peaks = peaks_for("TPU v5 lite")
    pairs, experts, d, f = 65536, 64, 2048, 1024
    flops = expert_rooflines.kernel_flops(pairs, d, f)
    assert 3 * flops == pytest.approx(2.474e12, rel=1e-3)
    moved = expert_rooflines.kernel_bytes(pairs, experts, d, f)
    # 1,024 rows an expert: ~400 FLOP/B against the ridge at 240
    assert flops / moved["expert_gmm_fwd"] == pytest.approx(409, abs=5)
    assert all(expert_rooflines.compute_bound(pairs, experts, d, f, peaks).values())
    # at 64 rows an expert the weights' traffic bounds it
    assert not any(expert_rooflines.compute_bound(4096, experts, d, f, peaks).values())


# ---- the readers ----------------------------------------------------------------


def hand_made_run():
    return {
        "trace": {
            "busy_s": 2.0,
            "op_self_s": {
                "expert_gmm_fwd.1": 0.10, "expert_gmm_fwd.2": 0.10,
                "expert_gmm_dx.3": 0.25, "expert_gmm_dw.4": 0.05,
                "flash_fwd.5": 0.30, "fusion.6": 1.20,
            },
            "details": {},
        },
        "traced_steps": 10,
        "flops_per_step_chip": {"train": 9e12, "experts": 3e12},
        "peaks": {"bf16_flops_per_s": 200e12},
    }


@pytest.mark.parametrize(
    "metric,expected",
    [
        ("expert_gmm_time_share.lm", 25.0),
        # 3e12 * 10 steps / 0.5 s / 200e12
        ("expert_gmm_roofline.lm", 30.0),
        ("expert_gmm_fwd_roofline.lm", 25.0),
        ("expert_gmm_dx_roofline.lm", 20.0),
        ("expert_gmm_dw_roofline.lm", 100.0),
    ],
)
def test_expert_reader_on_a_hand_made_run(metric, expected):
    read = manifest_lib.Cell(repo_manifest(), CELL).reader(metric)
    assert read(hand_made_run()) == pytest.approx(expected)
    # nothing to read: no trace, no expert kernel on the op line (the parent
    # commit), no expert FLOPs in the configuration
    assert read({**hand_made_run(), "trace": None}) is None
    no_kernel = hand_made_run()
    no_kernel["trace"]["op_self_s"] = {"flash_fwd.5": 0.3, "fusion.6": 1.2}
    assert read(no_kernel) is None
    if "roofline" in metric:
        dense = hand_made_run()
        dense["flops_per_step_chip"] = {"train": 9e12}
        assert read(dense) is None


def test_router_load_reader_reads_the_programs_counter(monkeypatch):
    from elasticdl_tpu.telemetry import router_load

    read = manifest_lib.Cell(repo_manifest(), CELL).reader("router_load_max_over_mean.lm")
    monkeypatch.setattr(router_load, "_watched", None)
    assert read({}) is None  # no trainer, or a model without experts
    monkeypatch.setattr(
        router_load, "read",
        lambda: {"max_over_mean": 1.75, "dropped_pairs": 0, "experts_without_tokens": 0},
    )
    assert read({}) == 1.75
    monkeypatch.setattr(
        router_load, "read", lambda: {"max_over_mean": 1.75, "dropped_pairs": 3}
    )
    with pytest.raises(RuntimeError, match="dropped"):
        read({})
    # a program without the counter (the parent commit): nothing, no error
    import elasticdl_tpu.telemetry as telemetry

    monkeypatch.delattr(telemetry, "router_load")
    monkeypatch.setitem(sys.modules, "elasticdl_tpu.telemetry.router_load", None)
    assert read({}) is None


def test_new_cell_reports_every_lm_metric_but_the_collective_one():
    cell = manifest_lib.Cell(repo_manifest(), CELL)
    names = {m["name"] for m in cell.metrics("per_layer")}
    lm_metrics = {
        m["name"] for m in repo_manifest()["per_layer"] if m["name"].endswith(".lm")
    }
    # one chip has no collective; a later PR's ``.lm`` entry need not list it
    assert lm_metrics - names >= {"collective_exposed_share.lm"}
    # every ``.lm`` metric the cell reported when PR 41 opened the lists
    assert names >= {
        "input_wait_share.lm",
        "dispatch_ms.lm",
        "step_device_ms.lm",
        "step_mfu.lm",
        "flash_time_share.lm",
        "flash_roofline.lm",
        "bookkeeping_ms.lm",
        "assemble_ms.lm",
        "place_ms.lm",
        "enqueue_ms.lm",
        "fetch_wait_ms.lm",
        "producer_batch_ms.lm",
        "producer_busy_share.lm",
        "flash_fwd_roofline.lm",
        "flash_dq_roofline.lm",
        "flash_dkv_roofline.lm",
        "expert_gmm_time_share.lm",
        "expert_gmm_roofline.lm",
        "expert_gmm_fwd_roofline.lm",
        "expert_gmm_dx_roofline.lm",
        "expert_gmm_dw_roofline.lm",
        "router_load_max_over_mean.lm",
    }
    assert {"setup_trace_s", "setup_lower_s", "setup_compile_s"} <= names
    # the six it brought, which another expert cell may join behind it
    experts = [
        m for m in cell.metrics("per_layer")
        if m["workloads"][:1] == [CELL] and m["name"].endswith(".lm")
    ]
    assert {m["name"] for m in experts} >= {
        "expert_gmm_time_share.lm", "expert_gmm_roofline.lm",
        "expert_gmm_fwd_roofline.lm", "expert_gmm_dx_roofline.lm",
        "expert_gmm_dw_roofline.lm", "router_load_max_over_mean.lm",
    }
    assert {m["layer"] for m in experts} >= {"experts (layers/moe.py, ops/grouped_matmul.py)"}
    assert {m["name"] for m in cell.metrics("end_to_end")} == {
        "tokens_per_s_chip", "peak_hbm_gb", "setup_s"
    }
    params = cell.config["run"]["model_params"]
    published = {k: cell.config[k] for k in (
        "hidden_size", "num_attention_heads", "num_experts", "num_experts_per_tok",
        "intermediate_size", "vocab_size", "rms_norm_eps", "rope_theta",
    )}
    assert published == {
        "hidden_size": params["embed_dim"], "num_attention_heads": params["num_heads"],
        "num_experts": params["num_experts"],
        "num_experts_per_tok": params["experts_per_token"],
        "intermediate_size": params["expert_width"], "vocab_size": params["vocab_size"],
        "rms_norm_eps": params["norm_eps"], "rope_theta": params["rope_theta"],
    }
    assert cell.traffic["records"]["seq_len"] == cell.config["max_position_embeddings"]
    assert cell.config["reduced"] == ["num_hidden_layers"]


# ---- the cell's control flow on the CPU ---------------------------------------


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.compiles_a_model
def test_olmoe_cell_rehearsal_on_cpu(tmp_path, trace):
    """One tiny layer of OLMoE's block through ``perf/run.py --rehearse-cpu``:
    the path driver, the stacked dispatch, the expert kernels interpreted,
    and (traced) the comparison with ``plain_olmoe``."""
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(manifest_with_tiny_olmoe()))
    env = {
        k: v for k, v in os.environ.items()
        if k not in ("XLA_FLAGS", "JAX_PLATFORMS", "BENCH_RUN")
    }
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cache")
    done = subprocess.run(
        [
            sys.executable, os.path.join(ROOT, "perf", "run.py"),
            "--workload", TINY_OLMOE_CELL, "--seed", str(2**31 + 27), "--seconds", "2",
            "--trace", str(trace), "--manifest", str(path), "--rehearse-cpu",
        ],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600,
    )
    assert done.returncode == 0, done.stderr[-2000:]
    info, result = map(json.loads, done.stdout.strip().splitlines()[-2:])
    assert result["correct"] is True, info["checks"]
    assert result["metrics"] == {} and result["failed"] == 0
    assert info["compiles_in_window"] == 0
    assert info["last_loss"] < info["first_loss"]
    if trace:
        compared = info["reference"]
        assert info["checks"]["reference_agrees"] is compared["agrees"] is True
        assert set(compared["by_block"]) == {"tok_embed", "block_0", "RMSNorm_0", "lm_head"}
