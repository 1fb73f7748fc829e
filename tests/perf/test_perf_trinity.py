"""The ``trinity_mini_26b_a3b`` configuration's files: the plain reference
against the zoo model with the configuration's fields at sizes a CPU holds
(a dense layer under a window part and an expert layer under a full part, a
non-zero selection bias), wrong terms it must catch, the chip's share
tied to the whole layer, the FLOP figures against a count by hand, the
``.swa`` readers on synthetic runs, the block plan's counter at the cell's
shape, and the cell's control flow rehearsed on the CPU through a test-only
configuration (``configs/tiny_trinity.json``)."""

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

from perf import manifest as manifest_lib, reference

CELL = "trinity_mini_seq16384"
TINY_CELL = "tiny_trinity_tiny"
EXPERTS, HELD, WINDOW, SEQ = 16, 8, 24, 64
SLIDING, FULL = "sliding_attention", "full_attention"

FIELDS = dict(
    vocab_size=64, embed_dim=32, num_heads=4, num_kv_heads=2, head_dim=16,
    num_layers=4, layer_pattern="w-*E", norm="rmsnorm", norm_eps=1e-5,
    use_bias=False, positions="rope", rope_theta=1e4, full_attention_rope=False,
    sliding_window=WINDOW, qk_norm_per_head=True, output_gate=True,
    norm_outputs=True, scale_embedding=True, mlp="swiglu", mlp_width=48,
    num_experts=EXPERTS, experts_per_token=2, expert_width=16, norm_topk_prob=True,
    router_scoring="sigmoid", selection_bias=True, routed_scaling=2.826,
    expert_kind="swiglu", shared_expert_width=16, experts_held=HELD, first_expert=0,
    router_aux_weight=0.0, router_z_weight=0.0,
)
# what the parameter tree does not carry, at this size
CONSTANTS = {
    "EXPERTS_PER_TOKEN": 2, "SLIDING_WINDOW": WINDOW,
    "LAYER_TYPES": (SLIDING, FULL),
}


def shipped_reference():
    module = manifest_lib.Cell(repo_manifest(), CELL).module("references", "afmoe")
    for name, value in CONSTANTS.items():
        setattr(module, name, value)
    return module


def tiny_trinity(dtype: str, **fields):
    """The zoo model, seeded parameters nudged off their init (norm scales
    too), and a selection bias large enough to change which experts are
    chosen.  The sequence is longer than two windows."""
    from elasticdl_tpu.models import long_seq_transformer as zoo

    model = zoo.custom_model(dtype=dtype, **{**FIELDS, **fields})
    tokens = np.random.default_rng(3).integers(64, size=(2, SEQ + 1)).astype(np.int32)
    features, labels = {"tokens": tokens[:, :-1]}, tokens[:, 1:]
    variables = model.init(jax.random.PRNGKey(1), features, training=False)
    leaves, tree = jax.tree_util.tree_flatten(variables["params"])
    keys = jax.random.split(jax.random.PRNGKey(7), len(leaves))
    params = tree.unflatten(
        [x + 0.05 * jax.random.normal(k, x.shape) for x, k in zip(leaves, keys)]
    )
    state = {k: v for k, v in variables.items() if k != "params"}
    bias = 0.3 * jax.random.normal(jax.random.PRNGKey(5), (EXPERTS,))
    assert set(state["router_stats"]) == {"block_3"}
    assert set(state["block_plan"]) == {"block_0"}
    for block in state["router_stats"].values():
        block["moe"]["selection_bias"] = bias

    def system(p):
        outputs, _ = model.apply(
            {"params": p, **state}, features, training=True, mutable=list(state)
        )
        return zoo.loss(labels, outputs).astype(jnp.float32)

    return system, params, state["router_stats"], features, labels, bias


@pytest.fixture(scope="module")
def float32_system():
    system, params, buffers, features, labels, bias = tiny_trinity(
        "float32", remat_layers=True  # as the configuration runs it
    )
    loss, grads = jax.jit(jax.value_and_grad(system))(params)
    return loss, grads, params, buffers, features, labels, bias


def reference_errors(module, loss_sys, grads_sys, params, buffers, features, labels):
    # a fresh lambda keeps a jit cache from remembering older constants
    loss_ref, grads_ref = jax.jit(
        lambda p, f, l, b: module.loss_and_grads(p, f, l, b)
    )(params, features, labels, buffers)
    assert jax.tree_util.tree_structure(grads_ref) == jax.tree_util.tree_structure(params)
    return jax.device_get(reference.errors(loss_sys, grads_sys, loss_ref, grads_ref))


# float32 against float32: the order of the sums.  bfloat16 activations
# against float32: 0.4% a rounding, through two attention parts, four norms
# on part outputs and an embedding 5.7 times its size.  A wrong term moves
# the loss or the gradient past the float32 limits by orders (below)
TOLERANCE = {"float32": (1e-5, 2e-5), "bfloat16": (5e-3, 0.15)}


@pytest.mark.compiles_a_model
def test_reference_agrees_with_the_zoo_model_in_float32(float32_system):
    loss, grads, *rest = float32_system
    got = reference_errors(shipped_reference(), loss, grads, *rest[:-1])
    loss_limit, grad_limit = TOLERANCE["float32"]
    assert got["loss_err"] <= loss_limit and got["grad_err"] <= grad_limit, got
    assert set(got["by_block"]) == {
        "tok_embed", "RMSNorm_0", "lm_head", *(f"block_{i}" for i in range(4)),
    }
    assert max(got["by_block"].values()) <= 1e-4, got


@pytest.mark.compiles_a_model
def test_reference_agrees_with_the_zoo_model_in_bfloat16():
    system, params, buffers, features, labels, _ = tiny_trinity("bfloat16")
    loss, grads = jax.jit(jax.value_and_grad(system))(params)
    got = reference_errors(
        shipped_reference(), loss, grads, params, buffers, features, labels
    )
    loss_limit, grad_limit = TOLERANCE["bfloat16"]
    assert got["loss_err"] <= loss_limit and got["grad_err"] <= grad_limit, got


def gate_before_the_heads_are_merged_in_another_order(module):
    """The gate's 64 numbers a token met head-major where the merged output
    lies width-major: the same product over a permuted gate."""
    def attention(x, a, layer_type):
        kernel = a["gate"]["kernel"]
        turned = jnp.swapaxes(kernel, 1, 2).reshape(kernel.shape)
        return original(x, {**a, "gate": {"kernel": turned}}, layer_type)
    original = module.attention
    return attention


def no_gate(module):
    def attention(x, a, layer_type):
        # sigmoid(0 x) = 1/2 everywhere, and twice the output projection
        zero = {"kernel": jnp.zeros_like(a["gate"]["kernel"])}
        twice = {"kernel": 2.0 * a["out"]["kernel"]}
        return original(x, {**a, "gate": zero, "out": twice}, layer_type)
    original = module.attention
    return attention


def no_norm_on_the_attention_output(module):
    def block(x, p, bias, layer_type):
        if "attn" not in p:
            return original(x, p, bias, layer_type)
        y = module.attention(module.rms_norm(x, p["RMSNorm_0"]), p["attn"], layer_type)
        return x + y
    original = module.block
    return block


def rope_in_the_full_layer(module):
    def attention(x, a, layer_type):
        if layer_type != FULL:
            return original(x, a, layer_type)
        # as a window layer whose window holds the sequence: rotary positions
        # and every earlier key
        kept, module.SLIDING_WINDOW = module.SLIDING_WINDOW, 10 * SEQ
        try:
            return original(x, a, SLIDING)
        finally:
            module.SLIDING_WINDOW = kept
    original = module.attention
    return attention


def bias_inside_the_weights(module):
    def route(tokens, m, bias):
        experts = m["router"]["kernel"].shape[1]
        scores = jax.nn.sigmoid(tokens @ m["router"]["kernel"]) + bias
        top, chosen = jax.lax.top_k(scores, module.EXPERTS_PER_TOKEN)
        top = top / jnp.sum(top, axis=-1, keepdims=True)
        one_hot = jax.nn.one_hot(chosen, experts, dtype=tokens.dtype)
        return jnp.einsum("tk,tke->te", top * module.ROUTE_SCALE, one_hot)
    return route


FAULTS = {
    "window_a_key_short": lambda m, bias: {"SLIDING_WINDOW": WINDOW - 1},
    "window_a_key_long": lambda m, bias: {"SLIDING_WINDOW": WINDOW + 1},
    "rope_in_the_full_layer": lambda m, bias: {"attention": rope_in_the_full_layer(m)},
    "no_rope_in_a_window_layer": lambda m, bias: {"rotary": lambda x: x},
    "no_gate": lambda m, bias: {"attention": no_gate(m)},
    "gate_in_another_order": lambda m, bias: {
        "attention": gate_before_the_heads_are_merged_in_another_order(m)
    },
    "no_output_norm": lambda m, bias: {"block": no_norm_on_the_attention_output(m)},
    "embedding_unscaled": lambda m, bias: {"MUP_ENABLED": False},
    "bias_inside_the_weights": lambda m, bias: {"route": bias_inside_the_weights(m)},
    "no_route_scale": lambda m, bias: {"ROUTE_SCALE": 1.0},
    "bias_left_out": lambda m, bias: {
        "selection_bias": lambda buffers, name, moe: jnp.zeros_like(bias)
    },
    "every_layer_a_window_layer": lambda m, bias: {"LAYER_TYPES": (SLIDING,) * 2},
    "other_experts_held": lambda m, bias: {"FIRST_EXPERT": 4},
}


@pytest.mark.parametrize("fault", FAULTS)
@pytest.mark.compiles_a_model
def test_comparison_fails_on_wrong_mathematics(monkeypatch, float32_system, fault):
    """Each wrong term, in float32 where nothing else differs, is far outside
    the float32 agreement (a hundred times its limits at least)."""
    loss, grads, params, buffers, features, labels, bias = float32_system
    module = shipped_reference()
    for name, value in FAULTS[fault](module, bias).items():
        monkeypatch.setattr(module, name, value)
    got = reference_errors(module, loss, grads, params, buffers, features, labels)
    loss_limit, grad_limit = TOLERANCE["float32"]
    assert not (got["loss_err"] <= 100 * loss_limit and got["grad_err"] <= 100 * grad_limit), got


@pytest.mark.compiles_a_model
def test_control_in_fp8_fails(float32_system):
    """The reference in the program's place with its weights rounded through
    float8 (e4m3), the nearest precision below the bfloat16 the configuration
    states: not correct under the bf16 tolerance."""
    _, _, params, buffers, features, labels, _ = float32_system
    module = shipped_reference()
    loss_sys, grads_sys = jax.jit(
        lambda p, f, l, b: module.loss_and_grads(p, f, l, b)
    )(reference.float8_weights(params), features, labels, buffers)
    got = reference_errors(
        module, loss_sys, grads_sys, params, buffers, features, labels
    )
    assert got["grad_err"] > 1.5 * TOLERANCE["bfloat16"][1], got


# ---- the chip's share tied to the model ------------------------------------------


@pytest.mark.compiles_a_model
def test_eight_shares_of_sixteen_experts_add_up_to_the_whole_layer():
    """8 chips, 16 of 128 experts each (``experts_held`` / ``first_expert``),
    the shared expert counted once: the parts add up to what the uncut
    reference gives for the whole expert layer; and each share's pair counts
    add up to every pair, none dropped, none counted twice."""
    from elasticdl_tpu.layers.moe import MoEMLP
    from elasticdl_tpu.telemetry import router_load

    experts, held, per_token, width, shared = 128, 16, 8, 16, 16
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(2, 40, 32), jnp.float32)

    def matrix(*shape):
        return jnp.asarray(rng.randn(*shape) * 0.2, jnp.float32)

    whole = {
        "router": {"kernel": jnp.asarray(rng.randn(32, experts) * 0.5, jnp.float32)},
        "w_gate": matrix(experts, 32, width), "w_up": matrix(experts, 32, width),
        "w_down": matrix(experts, width, 32),
        "shared_gate": {"kernel": matrix(32, shared)},
        "shared_up": {"kernel": matrix(32, shared)},
        "shared_down": {"kernel": matrix(shared, 32)},
    }
    bias = jnp.asarray(rng.randn(experts) * 0.2, jnp.float32)
    module = shipped_reference()
    module.EXPERTS_PER_TOKEN = per_token
    want = module.experts(x, whole, bias)
    shared_part = module.swiglu(
        x, *(whole[f"shared_{name}"]["kernel"] for name in ("gate", "up", "down"))
    )

    total, pairs_held, pairs = jnp.zeros_like(x), 0, None
    for chip in range(experts // held):
        first = chip * held
        layer = MoEMLP(
            num_experts=experts, experts_per_token=per_token, expert_width=width,
            norm_topk_prob=True, scoring="sigmoid", selection_bias=True,
            routed_scaling=2.826, expert_kind="swiglu", shared_width=shared,
            experts_held=held, first_expert=first, aux_loss_weight=0.0,
            z_loss_weight=0.0,
        )
        params = {
            **whole,
            **{k: whole[k][first:first + held] for k in ("w_gate", "w_up", "w_down")},
        }
        y, sown = layer.apply(
            {"params": params, router_load.ROUTER_STATS: {"selection_bias": bias}},
            x, mutable=["losses", router_load.ROUTER_STATS],
        )
        total = total + (y - shared_part)
        load = router_load.read(sown)
        assert load["dropped_pairs"] == 0
        assert load["held_pairs"] + load["absent_pairs"] == load["pairs"]
        pairs_held += load["held_pairs"]
        pairs = load["pairs"]
    assert pairs_held == pairs == 2 * 40 * per_token
    np.testing.assert_allclose(total + shared_part, want, rtol=2e-5, atol=2e-6)


# ---- arithmetic -----------------------------------------------------------------


def test_flops_come_from_the_published_shapes_counted_by_hand():
    cell = manifest_lib.Cell(repo_manifest(), CELL)
    seq = 16384
    per_token = {k: v / seq for k, v in cell.flops_per_record().items()}
    d, heads = 2048, 32
    # q, gate and output 2,048 x 4,096 each, k and v 2,048 x 512 each
    projections = 3 * d * 4096 + 2 * d * 512
    assert projections == 27_262_976
    assert per_token["attention_projections"] == 6 * 5 * projections
    window_pairs = 2048 * 2049 // 2 + (seq - 2048) * 2048
    causal_pairs = seq * (seq + 1) // 2
    assert (window_pairs, causal_pairs) == (31_458_304, 134_225_920)
    a_pair = 6 * heads * 2 * 128  # scores and values, forward and backward
    assert seq * per_token["window_attention"] == 4 * window_pairs * a_pair
    assert seq * per_token["causal_attention"] == 1 * causal_pairs * a_pair
    assert per_token["dense_mlp"] == 6 * 3 * d * 6144
    assert per_token["shared_expert"] == 6 * 4 * 3 * d * 1024
    assert per_token["experts"] == 6 * 4 * (8 * 16 / 128) * 3 * d * 1024
    assert per_token["router"] == 6 * 4 * d * 128
    assert per_token["head"] == 6 * d * 25024
    assert per_token["train"] == pytest.approx(
        sum(v for k, v in per_token.items() if k != "train")
    )
    # ISSUE 42: 13.3 T forward a step; the attention kernels 32% of it, all
    # of attention 65%; a window layer reads 23.4% of a full layer's pairs
    step = seq * per_token["train"]
    assert step / 3 == pytest.approx(13.3e12, rel=5e-3)
    kernels = per_token["window_attention"] + per_token["causal_attention"]
    assert kernels / per_token["train"] == pytest.approx(0.32, abs=5e-3)
    assert (kernels + per_token["attention_projections"]) / per_token[
        "train"
    ] == pytest.approx(0.655, abs=5e-3)
    assert window_pairs / causal_pairs == pytest.approx(0.2344, abs=1e-4)


def test_parameters_of_the_cut_are_the_files_count():
    """The model ``run.model_params`` builds has the 705,473,792 parameters
    ``reduced_why`` counts (shapes alone: nothing is allocated)."""
    from elasticdl_tpu.models import long_seq_transformer as zoo

    config = manifest_lib.Cell(repo_manifest(), CELL).config
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
    assert count(params["block_0"]["attn"]) == 27_263_232
    assert params["block_0"]["attn"]["gate"]["kernel"].shape == (2048, 32, 128)
    # the dense layer, an expert layer (attention + its four norms + the rest)
    assert count(params["block_0"]) + count(params["block_1"]) == 65_020_160
    assert count(params["block_2"]) + count(params["block_3"]) == 134_488_320
    assert count(params["block_3"]["moe"]) == 16 * 6_291_456 + 262_144 + 6_291_456
    assert (
        count(params["tok_embed"]) + count(params["lm_head"]) + count(params["RMSNorm_0"])
        == 102_500_352
    )
    assert count(params) == 705_473_792
    assert "705,473,792" in config["reduced_why"]
    assert set(shapes["router_stats"]) == {"block_3", "block_5", "block_7", "block_9"}
    # the four window parts; block_6 is the full layer
    assert set(shapes["block_plan"]) == {"block_0", "block_2", "block_4", "block_8"}


def test_the_programs_counter_reads_the_plans_skipped_blocks():
    """``block_plan`` as the program would sow it at the cell's shape (shapes
    alone): of a window layer's 32 x 1,024 score blocks 32 x 874 are never
    visited, 85.4%; ``router_load.read_block_plan`` sums the four layers."""
    from elasticdl_tpu.ops import attention as attention_ops
    from elasticdl_tpu.telemetry import router_load

    params = manifest_lib.Cell(repo_manifest(), CELL).config["run"]["model_params"]
    q = jax.ShapeDtypeStruct(
        (1, 16384, params["num_heads"], params["head_dim"]), jnp.bfloat16
    )
    kv = jax.ShapeDtypeStruct(
        (1, 16384, params["num_kv_heads"], params["head_dim"]), jnp.bfloat16
    )
    plan = attention_ops.window_block_plan(q, kv, kv, params["sliding_window"])
    assert plan == (32 * 150, 32 * 60, 32 * 874)
    sown = {
        router_load.BLOCK_PLAN: {
            f"block_{i}": {"attn": dict(zip(("visited", "masked", "skipped"), plan))}
            for i in (0, 2, 4, 8)
        }
    }
    read = router_load.read_block_plan(sown)
    assert read["layers"] == 4 and read["skipped"] == 4 * 32 * 874
    assert read["skipped_share"] == pytest.approx(874 / 1024)
    # against the full layer's 528: 28.4% of its blocks, 23.4% of its pairs
    full = attention_ops.flash_block_plan(16384, 16384, 512, 512, True)[0]
    assert plan[0] / (32 * full) == pytest.approx(0.284, abs=1e-3)


# ---- the readers ----------------------------------------------------------------


def synthetic_run():
    cell = manifest_lib.Cell(repo_manifest(), CELL)
    return {
        "cell": cell,
        "trace": {
            "busy_s": 4.0,
            "op_self_s": {
                "swa_fwd.1": 0.40, "swa_dq.2": 0.30, "swa_dkv.3": 0.30,
                "flash_fwd.4": 0.30, "flash_dq.5": 0.20, "flash_dkv.6": 0.25,
                "expert_gmm_fwd.7": 0.02, "expert_gmm_dx.8": 0.03,
                "expert_gmm_dw.9": 0.05, "fusion.10": 2.15,
            },
            "details": {},
        },
        "traced_steps": 8,
        "flops_per_step_chip": cell.flops_per_record(),
        "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
    }


OWN_READERS = (
    "attention_kernels_time_share.swa", "window_attention_time_share.swa",
    "swa_fwd_roofline.swa", "swa_dq_roofline.swa", "swa_dkv_roofline.swa",
    "held_pair_share.swa", "router_load_max_over_mean.swa",
    "expert_gmm_time_share.swa",
)


def test_time_share_readers_on_a_synthetic_run():
    cell = manifest_lib.Cell(repo_manifest(), CELL)
    run = synthetic_run()
    assert cell.reader("window_attention_time_share.swa")(run) == pytest.approx(25.0)
    assert cell.reader("attention_kernels_time_share.swa")(run) == pytest.approx(43.75)
    assert cell.reader("expert_gmm_time_share.swa")(run) == pytest.approx(2.5)
    # the dense kernels' readers see the full layer's calls alone
    assert cell.reader("flash_time_share.lm")(run) == pytest.approx(18.75)
    # a program with no window kernel (the parent, or full layers alone)
    dense = synthetic_run()
    dense["trace"]["op_self_s"] = {"flash_fwd.4": 0.3, "fusion.10": 1.2}
    for name in OWN_READERS[:5]:
        assert cell.reader(name)(dense) is None, name
        assert cell.reader(name)({**run, "trace": None}) is None, name


@pytest.mark.parametrize("kernel", ["swa_fwd", "swa_dq", "swa_dkv"])
def test_roofline_readers_on_a_synthetic_run(kernel):
    """A kernel's share: the window's pairs, two products, four layers, eight
    steps, over its time and the peak; compute bounds all three."""
    cell = manifest_lib.Cell(repo_manifest(), CELL)
    rooflines = cell.module("layer_metrics", f"{kernel}_roofline.swa")
    run = synthetic_run()
    flops = 2 * 31_458_304 * 32 * 2 * 128
    seconds = run["trace"]["op_self_s"][
        next(k for k in run["trace"]["op_self_s"] if k.startswith(kernel + "."))
    ]
    want = 100.0 * 8 * 4 * flops / 197e12 / seconds
    assert rooflines.read(run) == pytest.approx(want)
    assert 0 < want < 100
    from perf import window_rooflines

    least = window_rooflines.least_seconds(
        kernel, 16384, cell.config["flops"], run["peaks"]
    )
    assert least["compute_bound"] and least["compute_s"] == pytest.approx(flops / 197e12)
    # the dense kernels' readers divide the full layer's count alone
    dense = cell.reader("flash_fwd_roofline.lm")(run)
    assert dense == pytest.approx(
        100.0 * 8 * (2 * 134_225_920 * 32 * 2 * 128) / 197e12 / 0.30
    )


@pytest.mark.parametrize(
    "metric,value",
    [("held_pair_share.swa", 12.5), ("router_load_max_over_mean.swa", 3.5)],
)
def test_counter_readers_read_the_programs_counter(monkeypatch, metric, value):
    from elasticdl_tpu.telemetry import router_load

    read = manifest_lib.Cell(repo_manifest(), CELL).reader(metric)
    monkeypatch.setattr(router_load, "_watched", None)
    assert read({}) is None  # no trainer, or a model without experts
    load = {
        "pairs": 4000, "held_pairs": 500, "absent_pairs": 3500, "dropped_pairs": 0,
        "max_over_mean": 3.5,
    }
    monkeypatch.setattr(router_load, "read", lambda: load)
    assert read({}) == value
    monkeypatch.setattr(router_load, "read", lambda: {**load, "dropped_pairs": 3})
    with pytest.raises(RuntimeError, match="dropped"):
        read({})


def test_cell_reports_the_lm_metrics_it_can_and_its_own():
    """What the cell reports at least: a later PR may put it on further lists
    and add cells and configurations beside it."""
    manifest = repo_manifest()
    cell = manifest_lib.Cell(manifest, CELL)
    names = {m["name"] for m in cell.metrics("per_layer")}
    assert {
        "input_wait_share.lm", "dispatch_ms.lm", "step_device_ms.lm", "step_mfu.lm",
        "bookkeeping_ms.lm", "assemble_ms.lm", "place_ms.lm", "enqueue_ms.lm",
        "fetch_wait_ms.lm", "producer_batch_ms.lm", "producer_busy_share.lm",
        "setup_trace_s", "setup_lower_s", "setup_compile_s",
    } <= names
    assert set(OWN_READERS) <= names
    own = [m for m in cell.metrics("per_layer") if m["name"].endswith(".swa")]
    assert all(m["workloads"][:1] == [CELL] for m in own)
    assert all(m["moves"] == "tokens_per_s_chip" for m in own)
    assert {m["layer"] for m in own} == {
        "kernels (ops/attention.py)", "experts (layers/moe.py, ops/grouped_matmul.py)"
    }
    # no share of a roofline on a balanced expert count (ISSUE 34)
    assert not [n for n in names if "expert" in n and "roofline" in n]
    assert {m["name"] for m in cell.metrics("end_to_end")} == {
        "tokens_per_s_chip", "peak_hbm_gb", "setup_s"
    }
    assert (cell.chips, cell.traffic_name) == (1, "seq16384")
    assert cell.traffic["records"]["seq_len"] == 16384


def test_configuration_keeps_every_published_width():
    """Every number of the catalog row under its own key, the five cuts
    listed, and the model's fields equal to the keys they come from."""
    config = manifest_lib.Cell(repo_manifest(), CELL).config
    assert config["reduced"] == [
        "num_hidden_layers", "num_dense_layers", "layer_types", "num_experts",
        "vocab_size",
    ]
    types = [SLIDING, SLIDING, SLIDING, FULL] * 8
    assert config["published"] == {
        "num_hidden_layers": 32, "num_dense_layers": 2, "layer_types": types,
        "num_experts": 128, "vocab_size": 200192,
    }
    assert config["layer_types"] == types[:5]
    assert (config["num_hidden_layers"], config["num_dense_layers"]) == (5, 1)
    assert (config["num_experts"], config["vocab_size"]) == (16, 200192 // 8)
    params = config["run"]["model_params"]
    published = {
        "hidden_size": "embed_dim", "num_attention_heads": "num_heads",
        "num_key_value_heads": "num_kv_heads", "head_dim": "head_dim",
        "sliding_window": "sliding_window", "rope_theta": "rope_theta",
        "rms_norm_eps": "norm_eps", "intermediate_size": "mlp_width",
        "num_experts_per_tok": "experts_per_token",
        "moe_intermediate_size": "expert_width", "route_norm": "norm_topk_prob",
        "route_scale": "routed_scaling", "score_func": "router_scoring",
        "num_experts": "experts_held", "load_balance_coeff": "selection_bias_rate",
        "mup_enabled": "scale_embedding", "vocab_size": "vocab_size",
    }
    assert {k: config[k] for k in published} == {k: params[v] for k, v in published.items()}
    assert (config["hidden_size"], config["head_dim"]) == (2048, 128)
    assert (config["sliding_window"], config["route_scale"]) == (2048, 2.826)
    assert params["num_experts"] == config["published"]["num_experts"] == 128
    assert params["shared_expert_width"] == (
        config["num_shared_experts"] * config["moe_intermediate_size"]
    ) == 1024
    # a layer is two letters: w or * by its type, then - (dense) or E
    letters = {SLIDING: "w", FULL: "*"}
    assert params["layer_pattern"] == "".join(
        letters[kind] + ("-" if i < config["num_dense_layers"] else "E")
        for i, kind in enumerate(config["layer_types"])
    ) == "w-wEwE*EwE"
    assert params["num_layers"] == 2 * config["num_hidden_layers"]
    assert (params["mlp"], config["hidden_act"]) == ("swiglu", "silu")
    assert params["use_bias"] is False and params["full_attention_rope"] is False
    assert params["output_gate"] and params["norm_outputs"] and params["qk_norm_per_head"]
    assert (params["router_aux_weight"], params["router_z_weight"]) == (0.0, 0.0)
    flops = config["flops"]
    assert (flops["window_layers"], flops["full_layers"]) == (4, 1)
    assert (flops["dense_layers"], flops["expert_layers"], flops["window"]) == (1, 4, 2048)
    # the reference's constants are the file's
    module = manifest_lib.Cell(repo_manifest(), CELL).module("references", "afmoe")
    assert list(module.LAYER_TYPES) == config["layer_types"]
    assert (module.SLIDING_WINDOW, module.ROPE_THETA) == (2048, 1e4)
    assert (module.RMS_NORM_EPS, module.ROUTE_SCALE) == (1e-5, 2.826)
    assert (module.EXPERTS_PER_TOKEN, module.MUP_ENABLED) == (8, True)
    assert "8 chips share each layer" in config["deployment"]
    assert len(config["assumed"]) >= 10
    for key in ("deployment", "assumed", "departures", "not_built", "reference",
                "reduced_why"):
        assert config[key], key


# ---- the cell's control flow on the CPU ---------------------------------------


def manifest_with_tiny_trinity() -> dict:
    manifest = copy.deepcopy(manifest_with_tiny_cell())
    manifest["configs"].append({
        "name": "tiny_trinity",
        "source": "none: CPU rehearsal of the harness only",
        "file": "tests/perf/configs/tiny_trinity.json",
        "reduced": [],
        "why": "a window part and a full part, a dense and an expert layer at width 64: control flow only",
    })
    manifest["workloads"].append({
        "name": TINY_CELL, "config": "tiny_trinity", "traffic": "tiny",
        "chips": 1, "why": "2 x 64 tokens a step on the CPU backend through the window path",
    })
    for metric in manifest["end_to_end"] + manifest["per_layer"]:
        if CELL in metric.get("workloads", []):
            metric["workloads"].append(TINY_CELL)
    return manifest


@pytest.mark.compiles_a_model
def test_cell_rehearsal_on_cpu(tmp_path, trace=1):
    """Two tiny layers through ``perf/run.py --rehearse-cpu`` (the traced
    run, which measures untraced first): the path driver, the stacked
    dispatch, the window and the dense flash kernels and the expert kernels
    interpreted, the layers recomputed, the selection bias and the block plan
    riding in the state."""
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(manifest_with_tiny_trinity()))
    env = {
        k: v for k, v in os.environ.items()
        if k not in ("XLA_FLAGS", "JAX_PLATFORMS", "BENCH_RUN")
    }
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cache")
    done = subprocess.run(
        [
            sys.executable, os.path.join(ROOT, "perf", "run.py"),
            "--workload", TINY_CELL, "--seed", str(2**31 + 42), "--seconds", "2",
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
    assert info["reference"] == "none"  # the tiny configuration names none
