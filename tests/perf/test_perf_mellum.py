"""The ``mellum2_12b_a2p5b`` configuration's files: the plain reference
against the zoo model with the configuration's fields at sizes a CPU holds
(three window layers to one full layer whose rotary positions are YaRN-scaled,
softmax-routed experts renormalised over the chosen, half of them held),
wrong terms it must catch, the chip's share tied to the whole layer, the FLOP
figures against the tiny model's own matrices and against a count by hand, the
parameter count of the cut, the configuration against the catalog's row, the
readers the cell lists on a synthetic run, the block plan's counter at the
cell's shape, and the cell's control flow rehearsed on the CPU through a
test-only configuration (``configs/tiny_mellum.json``)."""

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

CELL = "mellum2_seq16384"
TINY_CELL = "tiny_mellum_tiny"
EXPERTS, HELD, WINDOW, SEQ = 16, 8, 24, 64
SLIDING, FULL = "sliding_attention", "full_attention"

# the catalog's row (architectures.jsonl, Mellum2-12B-A2.5B-Instruct): config
CATALOG = {
    "attention_bias": False, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2304, "intermediate_size": 7168,
    "layer_types": [SLIDING, SLIDING, SLIDING, FULL] * 7,
    "mlp_layer_types": ["sparse"] * 28, "max_position_embeddings": 131072,
    "max_window_layers": 0, "model_type": "mellum", "moe_intermediate_size": 896,
    "norm_topk_prob": True, "num_attention_heads": 32, "num_experts": 64,
    "num_experts_per_tok": 8, "num_hidden_layers": 28, "num_key_value_heads": 4,
    "rms_norm_eps": 1e-06,
    "rope_parameters": {
        "full_attention": {
            "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
            "original_max_position_embeddings": 8192, "beta_fast": 32,
            "beta_slow": 1, "attention_factor": 1.2772588722239782,
        },
        "sliding_attention": {"rope_type": "default", "rope_theta": 500000},
    },
    "sliding_window": 1024, "tie_word_embeddings": False, "vocab_size": 98304,
    "use_sliding_window": True,
}
# the rule at a size where the ramp ends inside a 16-wide head's 8 pairs: at
# theta 100 from 16 positions the pair that turns once is 16 ln(16 / 2 pi) /
# (2 ln 100) = 1.62, so high is 2 and low is clipped to 0; every pair turns
# visibly over 64 positions, four times the original 16
TINY_ROPE = {
    FULL: {
        **CATALOG["rope_parameters"][FULL], "rope_theta": 100,
        "original_max_position_embeddings": 16,
    },
    SLIDING: {"rope_type": "default", "rope_theta": 100},
}
FIELDS = dict(
    vocab_size=64, embed_dim=32, num_heads=4, num_kv_heads=2, head_dim=16,
    num_layers=8, layer_pattern="wEwEwE*E", norm="rmsnorm", norm_eps=1e-6,
    use_bias=False, positions="rope", rope_parameters=TINY_ROPE,
    sliding_window=WINDOW, qk_norm_per_head=True, mlp="swiglu",
    num_experts=EXPERTS, experts_per_token=2, expert_width=16, norm_topk_prob=True,
    router_scoring="softmax", expert_kind="swiglu", experts_held=HELD,
    first_expert=0, router_aux_weight=0.0, router_z_weight=0.0,
    router_trains=False,
)
# what the parameter tree does not carry, at this size
CONSTANTS = {
    "EXPERTS_PER_TOKEN": 2, "SLIDING_WINDOW": WINDOW, "ROPE_PARAMETERS": TINY_ROPE,
}


def shipped_reference():
    module = manifest_lib.Cell(repo_manifest(), CELL).module("references", "mellum")
    for name, value in CONSTANTS.items():
        setattr(module, name, value)
    return module


def tiny_mellum(dtype: str, **fields):
    """The zoo model and seeded parameters nudged off their init (norm scales
    too).  The sequence is longer than two windows and four times the
    positions the tiny YaRN rule extends from."""
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
    assert set(state["router_stats"]) == {"block_1", "block_3", "block_5", "block_7"}
    # the three window parts; block_6 is the full layer
    assert set(state["block_plan"]) == {"block_0", "block_2", "block_4"}

    def system(p):
        outputs, _ = model.apply(
            {"params": p, **state}, features, training=True, mutable=list(state)
        )
        return zoo.loss(labels, outputs).astype(jnp.float32)

    return system, params, features, labels


@pytest.fixture(scope="module")
def float32_system():
    system, params, features, labels = tiny_mellum(
        "float32", remat_layers=True  # as the configuration runs it
    )
    loss, grads = jax.jit(jax.value_and_grad(system))(params)
    return loss, grads, params, features, labels


def reference_errors(module, loss_sys, grads_sys, params, features, labels):
    # a fresh lambda keeps a jit cache from remembering older constants
    loss_ref, grads_ref = jax.jit(
        lambda p, f, l: module.loss_and_grads(p, f, l)
    )(params, features, labels)
    assert jax.tree_util.tree_structure(grads_ref) == jax.tree_util.tree_structure(params)
    return jax.device_get(reference.errors(loss_sys, grads_sys, loss_ref, grads_ref))


# float32 against float32: the order of the sums (the flash kernels' blocks,
# the experts' sort) and float32 angles made as positions * (a blend of two
# float32 frequencies) on both sides.  bfloat16 activations against float32:
# 0.4% a rounding through four attention parts and four expert parts.  A
# wrong term moves the loss or the gradient past the float32 limits by orders
# (below)
TOLERANCE = {"float32": (1e-5, 3e-5), "bfloat16": (5e-3, 0.15)}


@pytest.mark.compiles_a_model
def test_reference_agrees_with_the_zoo_model_in_float32(float32_system):
    loss, grads, *rest = float32_system
    got = reference_errors(shipped_reference(), loss, grads, *rest)
    loss_limit, grad_limit = TOLERANCE["float32"]
    assert got["loss_err"] <= loss_limit and got["grad_err"] <= grad_limit, got
    assert set(got["by_block"]) == {
        "tok_embed", "RMSNorm_0", "lm_head", *(f"block_{i}" for i in range(8)),
    }
    assert max(got["by_block"].values()) <= 1e-4, got


@pytest.mark.compiles_a_model
def test_reference_agrees_with_the_zoo_model_in_bfloat16():
    system, params, features, labels = tiny_mellum("bfloat16")
    loss, grads = jax.jit(jax.value_and_grad(system))(params)
    got = reference_errors(shipped_reference(), loss, grads, params, features, labels)
    loss_limit, grad_limit = TOLERANCE["bfloat16"]
    assert got["loss_err"] <= loss_limit and got["grad_err"] <= grad_limit, got


def without(key, value):
    """``ROPE_PARAMETERS`` with one number of the full layers' rule changed."""
    return {"ROPE_PARAMETERS": {**TINY_ROPE, FULL: {**TINY_ROPE[FULL], key: value}}}


def renormalised_over_the_held_alone(module):
    """Renormalised over the held experts among the chosen instead of over
    all the chosen: what a chip that forgot the absent experts would do."""
    def route(tokens, m):
        weight = original(tokens, m)
        held = m["w_up"].shape[0]
        mine = weight.at[:, held:].set(0.0)
        return mine / jnp.maximum(jnp.sum(mine, axis=-1, keepdims=True), 1e-9)
    original = module.route
    return route


FAULTS = {
    # the four ISSUE 61 names
    "ramp_left_out": lambda m: {
        # every pair interpolated: position interpolation, not YaRN
        "yarn_ramp": lambda rope, d: jnp.ones((d // 2,), jnp.float32)
    },
    "attention_factor_left_out": lambda m: without("attention_factor", 1.0),
    "window_a_key_short": lambda m: {"SLIDING_WINDOW": WINDOW - 1},
    "renormalisation_left_out": lambda m: {"NORM_TOPK_PROB": False},
    # and their neighbours
    "window_a_key_long": lambda m: {"SLIDING_WINDOW": WINDOW + 1},
    "every_pair_extrapolated": lambda m: {
        "yarn_ramp": lambda rope, d: jnp.zeros((d // 2,), jnp.float32)
    },
    "yarn_in_the_window_layers_too": lambda m: {
        "ROPE_PARAMETERS": {FULL: TINY_ROPE[FULL], SLIDING: TINY_ROPE[FULL]}
    },
    "the_base_in_the_full_layer": lambda m: {
        "ROPE_PARAMETERS": {FULL: TINY_ROPE[SLIDING], SLIDING: TINY_ROPE[SLIDING]}
    },
    "factor_8": lambda m: without("factor", 8),
    "ramp_from_another_length": lambda m: without(
        "original_max_position_embeddings", 64  # high 5 where it was 2
    ),
    "no_rope_at_all": lambda m: {"rotary": lambda x, layer_type: x},
    "every_layer_a_window_layer": lambda m: {"LAYER_TYPES": (SLIDING,) * 4},
    "the_full_layer_first": lambda m: {
        "LAYER_TYPES": (FULL, SLIDING, SLIDING, SLIDING)
    },
    "renormalised_over_the_held": lambda m: {
        "route": renormalised_over_the_held_alone(m)
    },
    "other_experts_held": lambda m: {"FIRST_EXPERT": 4},
    # the partial gradient of the logits taken as the whole one
    "the_routing_differentiated": lambda m: {"ROUTER_TRAINS": True},
    "top_3": lambda m: {"EXPERTS_PER_TOKEN": 3},
}


@pytest.mark.parametrize("fault", FAULTS)
@pytest.mark.compiles_a_model
def test_comparison_fails_on_wrong_mathematics(monkeypatch, float32_system, fault):
    """Each wrong term, in float32 where nothing else differs, is far outside
    the float32 agreement (a hundred times its limits at least)."""
    loss, grads, params, features, labels = float32_system
    module = shipped_reference()
    for name, value in FAULTS[fault](module).items():
        monkeypatch.setattr(module, name, value)
    got = reference_errors(module, loss, grads, params, features, labels)
    loss_limit, grad_limit = TOLERANCE["float32"]
    assert not (got["loss_err"] <= 100 * loss_limit and got["grad_err"] <= 100 * grad_limit), got


@pytest.mark.compiles_a_model
def test_control_in_fp8_fails(float32_system):
    """The reference in the program's place with its weights rounded through
    float8 (e4m3), the nearest precision below the bfloat16 the configuration
    states: not correct under the bf16 tolerance."""
    _, _, params, features, labels = float32_system
    module = shipped_reference()
    loss_sys, grads_sys = jax.jit(
        lambda p, f, l: module.loss_and_grads(p, f, l)
    )(reference.float8_weights(params), features, labels)
    got = reference_errors(module, loss_sys, grads_sys, params, features, labels)
    assert got["grad_err"] > 1.5 * TOLERANCE["bfloat16"][1], got


def test_the_references_yarn_is_the_formulas_numbers_for_this_row():
    """The reference's own arithmetic at the published numbers, against the
    numbers ``tests/test_rotary.py`` writes out by hand for the program's:
    two implementations, one table."""
    module = manifest_lib.Cell(repo_manifest(), CELL).module("references", "mellum")
    frequencies, factor = module.inv_freq(module.ROPE_PARAMETERS[FULL], 128)
    assert factor == 1.2772588722239782
    got = np.asarray(frequencies, np.float64)
    by_hand = {
        0: 1.0, 18: 2.495541e-2, 19: 1.920802e-2, 34: 1.104087e-4,
        35: 4.778106e-5, 63: 1.534463e-7,
    }
    for i, rate in by_hand.items():
        assert got[i] == pytest.approx(rate, rel=2e-6), i
    ramp = np.asarray(module.yarn_ramp(module.ROPE_PARAMETERS[FULL], 128))
    assert (ramp[:19] == 0).all() and (ramp[35:] == 1).all()
    assert ramp[19] == pytest.approx(1 / 17) and ramp[34] == pytest.approx(16 / 17)
    plain, one = module.inv_freq(module.ROPE_PARAMETERS[SLIDING], 128)
    assert one == 1.0
    np.testing.assert_allclose(
        np.asarray(plain), 500000.0 ** (-np.arange(64) / 64), rtol=2e-6
    )


# ---- the chip's share tied to the model ------------------------------------------


@pytest.mark.compiles_a_model
def test_four_shares_of_sixteen_experts_add_up_to_the_whole_layer():
    """4 chips, 16 of 64 experts each (``experts_held`` / ``first_expert``),
    no shared expert: the parts add up to what the uncut reference gives for
    the whole expert layer, every token's eight weights summing to one over
    the chips; and each share's pair counts add up to every pair, none
    dropped, none counted twice."""
    from elasticdl_tpu.layers.moe import MoEMLP
    from elasticdl_tpu.telemetry import router_load

    experts, held, per_token, width = 64, 16, 8, 16
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(2, 40, 32), jnp.float32)

    def matrix(*shape):
        return jnp.asarray(rng.randn(*shape) * 0.2, jnp.float32)

    whole = {
        "router": {"kernel": jnp.asarray(rng.randn(32, experts) * 0.5, jnp.float32)},
        "w_gate": matrix(experts, 32, width), "w_up": matrix(experts, 32, width),
        "w_down": matrix(experts, width, 32),
    }
    module = shipped_reference()
    module.EXPERTS_PER_TOKEN = per_token
    want = module.experts(x, whole)
    np.testing.assert_allclose(
        np.asarray(jnp.sum(module.route(x.reshape(-1, 32), whole), axis=-1)), 1.0,
        rtol=1e-5,
    )

    total, pairs_held, pairs = jnp.zeros_like(x), 0, None
    for chip in range(experts // held):
        first = chip * held
        layer = MoEMLP(
            num_experts=experts, experts_per_token=per_token, expert_width=width,
            norm_topk_prob=True, scoring="softmax", expert_kind="swiglu",
            experts_held=held, first_expert=first, aux_loss_weight=0.0,
            z_loss_weight=0.0,
        )
        params = {
            **whole,
            **{k: whole[k][first:first + held] for k in ("w_gate", "w_up", "w_down")},
        }
        y, sown = layer.apply(
            {"params": params}, x, mutable=["losses", router_load.ROUTER_STATS],
        )
        # the reference given the same share
        module.FIRST_EXPERT = first
        np.testing.assert_allclose(
            np.asarray(y), np.asarray(module.experts(x, params)), rtol=2e-5, atol=2e-6
        )
        total = total + y
        load = router_load.read(sown)
        assert load["dropped_pairs"] == 0
        assert load["held_pairs"] + load["absent_pairs"] == load["pairs"]
        pairs_held += load["held_pairs"]
        pairs = load["pairs"]
    assert pairs_held == pairs == 2 * 40 * per_token
    np.testing.assert_allclose(total, want, rtol=2e-5, atol=2e-6)


# ---- arithmetic -----------------------------------------------------------------


def test_flops_are_the_tiny_models_own_matrices():
    """Every matmul parameter of the tiny model, times the rows it meets: the
    count by shapes is the count by the parameter tree (the held experts at
    the balanced share)."""
    from elasticdl_tpu.models import long_seq_transformer as zoo

    manifest = manifest_with_tiny_mellum()
    cell = manifest_lib.Cell(manifest, TINY_CELL)
    fields = cell.config["run"]["model_params"]
    model = zoo.custom_model(**fields)
    shapes = jax.eval_shape(
        lambda: model.init(
            jax.random.PRNGKey(0), {"tokens": jnp.zeros((1, 8), jnp.int32)}
        )
    )["params"]
    size = lambda tree: sum(  # noqa: E731
        int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(tree)
    )
    attention = moe = router = 0
    for name, block in shapes.items():
        if "attn" in block:
            attention += sum(
                size(block["attn"][k]) for k in ("query", "key", "value", "out")
            )
        elif "moe" in block:
            router += size(block["moe"]["router"])
            stacks = sum(size(block["moe"][k]) for k in ("w_gate", "w_up", "w_down"))
            # held of all, per_token of them a token: k / all of the held stacks
            moe += stacks * fields["experts_per_token"] / fields["num_experts"]
    seq = cell.traffic["records"]["seq_len"]
    got = {k: v / seq for k, v in cell.flops_per_record().items()}
    assert got["attention_projections"] == 6 * attention
    assert got["router"] == 6 * router
    assert got["experts"] == pytest.approx(6 * moe)
    assert got["head"] == 6 * size(shapes["lm_head"])
    pair = 6 * fields["num_heads"] * 2 * fields["head_dim"]
    window = min(fields["sliding_window"], seq)
    inside = sum(min(t + 1, window) for t in range(seq))
    assert seq * got["window_attention"] == pair * inside  # one window layer
    assert seq * got["causal_attention"] == pair * seq * (seq + 1) // 2
    assert got["train"] == pytest.approx(sum(v for k, v in got.items() if k != "train"))


def test_flops_come_from_the_published_shapes_counted_by_hand():
    cell = manifest_lib.Cell(repo_manifest(), CELL)
    seq = 16384
    per_token = {k: v / seq for k, v in cell.flops_per_record().items()}
    d, heads = 2304, 32
    # q and output 2,304 x 4,096 each, k and v 2,304 x 512 each
    projections = 2 * d * 4096 + 2 * d * 512
    assert projections == 21_233_664
    assert per_token["attention_projections"] == 6 * 4 * projections
    window_pairs = 1024 * 1025 // 2 + (seq - 1024) * 1024
    causal_pairs = seq * (seq + 1) // 2
    assert (window_pairs, causal_pairs) == (16_253_440, 134_225_920)
    a_pair = 6 * heads * 2 * 128  # scores and values, forward and backward
    assert seq * per_token["window_attention"] == 3 * window_pairs * a_pair
    assert seq * per_token["causal_attention"] == 1 * causal_pairs * a_pair
    # 8 x 16 / 64 = 2 held experts a token of 3 x 2,304 x 896
    assert per_token["experts"] == 6 * 4 * 2 * 6_193_152
    assert per_token["router"] == 6 * 4 * d * 64
    assert per_token["head"] == 6 * d * 24576
    assert per_token["train"] == pytest.approx(
        sum(v for k, v in per_token.items() if k != "train")
    )
    # ISSUE 61: 283 M multiply-accumulates a token forward, 27.8 TFLOP a
    # step; the attention kernels 32% of it, all of attention 62%, experts
    # 17%, head 20%; a window layer reads 12% of the full layer's pairs
    step = seq * per_token["train"]
    assert per_token["train"] / 6 == pytest.approx(283.2e6, rel=1e-3)
    assert step == pytest.approx(27.84e12, rel=1e-3)
    kernels = per_token["window_attention"] + per_token["causal_attention"]
    assert kernels / per_token["train"] == pytest.approx(0.323, abs=2e-3)
    assert (kernels + per_token["attention_projections"]) / per_token[
        "train"
    ] == pytest.approx(0.623, abs=2e-3)
    assert per_token["experts"] / per_token["train"] == pytest.approx(0.175, abs=2e-3)
    assert per_token["head"] / per_token["train"] == pytest.approx(0.200, abs=2e-3)
    assert window_pairs / causal_pairs == pytest.approx(0.1211, abs=1e-4)


def test_parameters_of_the_cut_are_the_files_count():
    """The model ``run.model_params`` builds has the 595,154,176 parameters
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
    attn = params["block_0"]["attn"]
    assert attn["query"]["kernel"].shape == (2304, 32, 128)
    assert attn["key"]["kernel"].shape == attn["value"]["kernel"].shape == (2304, 4, 128)
    assert attn["q_norm"]["scale"].shape == attn["k_norm"]["scale"].shape == (128,)
    moe = params["block_1"]["moe"]
    assert moe["router"]["kernel"].shape == (2304, 64)
    assert moe["w_gate"].shape == moe["w_up"].shape == (16, 2304, 896)
    assert set(moe) == {"router", "w_gate", "w_up", "w_down"}  # no shared expert
    # a layer outside its routed experts, and whole
    assert count(params["block_0"]) + count(params["block_1"]) - 16 * 6_193_152 == 21_385_984
    for layer in range(4):
        assert (
            count(params[f"block_{2 * layer}"]) + count(params[f"block_{2 * layer + 1}"])
            == 120_476_416
        )
    assert (
        count(params["tok_embed"]) + count(params["lm_head"]) + count(params["RMSNorm_0"])
        == 113_248_512
    )
    assert count(params) == 595_154_176
    assert "595,154,176" in config["reduced_why"]
    assert set(shapes["router_stats"]) == {"block_1", "block_3", "block_5", "block_7"}
    # the three window parts; block_6 is the full layer
    assert set(shapes["block_plan"]) == {"block_0", "block_2", "block_4"}


def test_the_programs_counter_reads_the_plans_skipped_blocks():
    """``block_plan`` as the program would sow it at the cell's shape (shapes
    alone): of a window layer's 32 x 1,024 score blocks 32 x 931 are never
    visited, 90.9%; ``router_load.read_block_plan`` sums the three layers."""
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
    assert plan == (32 * 93, 32 * 62, 32 * 931)
    sown = {
        router_load.BLOCK_PLAN: {
            f"block_{i}": {"attn": dict(zip(("visited", "masked", "skipped"), plan))}
            for i in (0, 2, 4)
        }
    }
    read = router_load.read_block_plan(sown)
    assert read["layers"] == 3 and read["skipped"] == 3 * 32 * 931
    assert read["skipped_share"] == pytest.approx(931 / 1024)
    # against the full layer's 528: 17.6% of its blocks, 12.1% of its pairs
    full = attention_ops.flash_block_plan(16384, 16384, 512, 512, True)[0]
    assert plan[0] / (32 * full) == pytest.approx(0.176, abs=1e-3)


# ---- the readers the cell lists, on its own numbers ----------------------------


def synthetic_run():
    cell = manifest_lib.Cell(repo_manifest(), CELL)
    return {
        "cell": cell,
        "trace": {
            "busy_s": 4.0,
            "op_self_s": {
                "swa_fwd.1": 0.20, "swa_dq.2": 0.15, "swa_dkv.3": 0.15,
                "flash_fwd.4": 0.30, "flash_dq.5": 0.20, "flash_dkv.6": 0.25,
                "expert_gmm_fwd.7": 0.02, "expert_gmm_dx.8": 0.03,
                "expert_gmm_dw.9": 0.05, "fusion.10": 2.65,
            },
            "details": {},
        },
        "traced_steps": 8,
        "flops_per_step_chip": cell.flops_per_record(),
        "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
    }


@pytest.mark.parametrize("kernel", ["swa_fwd", "swa_dq", "swa_dkv"])
def test_the_window_readers_take_this_configurations_window(kernel):
    """The ``.swa`` readers are generic: the pairs inside a window of 1,024,
    three layers, from ``config["flops"]``; compute bounds all three."""
    from perf import window_rooflines

    cell = manifest_lib.Cell(repo_manifest(), CELL)
    run = synthetic_run()
    flops = 2 * 16_253_440 * 32 * 2 * 128
    seconds = run["trace"]["op_self_s"][
        next(k for k in run["trace"]["op_self_s"] if k.startswith(kernel + "."))
    ]
    want = 100.0 * 8 * 3 * flops / 197e12 / seconds
    assert cell.reader(f"{kernel}_roofline.swa")(run) == pytest.approx(want)
    assert 0 < want < 100
    least = window_rooflines.least_seconds(
        kernel, 16384, cell.config["flops"], run["peaks"]
    )
    assert least["compute_bound"] and least["compute_s"] == pytest.approx(flops / 197e12)
    assert cell.reader("window_attention_time_share.swa")(run) == pytest.approx(12.5)
    assert cell.reader("attention_kernels_time_share.swa")(run) == pytest.approx(31.25)
    # the dense kernels' readers divide the full layer's count alone
    assert cell.reader("flash_fwd_roofline.lm")(run) == pytest.approx(
        100.0 * 8 * (2 * 134_225_920 * 32 * 2 * 128) / 197e12 / 0.30
    )


def test_cell_reports_what_the_other_window_cell_reports():
    """ISSUE 61: no ``per_layer`` entry of its own; the cell is at the end of
    every list ``trinity_mini_seq16384`` is on, and of no other."""
    manifest = repo_manifest()
    cell = manifest_lib.Cell(manifest, CELL)
    other = manifest_lib.Cell(manifest, "trinity_mini_seq16384")
    names = [m["name"] for m in cell.metrics("per_layer")]
    assert names == [m["name"] for m in other.metrics("per_layer")]
    assert len(names) == 42 and sum(n.endswith(".swa") for n in names) == 8
    assert {
        "swa_fwd_roofline.swa", "swa_dq_roofline.swa", "swa_dkv_roofline.swa",
        "flash_fwd_roofline.lm", "flash_dq_roofline.lm", "flash_dkv_roofline.lm",
        "held_pair_share.swa", "step_mfu.lm", "attention_other_share.scope_lm",
    } <= set(names)
    # after the other window cell on every list, wherever later cells follow
    for metric in cell.metrics("per_layer"):
        listed = metric["workloads"]
        assert listed.index(CELL) > listed.index(other.name), metric["name"]
    assert {m["name"] for m in cell.metrics("end_to_end")} == {
        "tokens_per_s_chip", "peak_hbm_gb", "setup_s"
    }
    assert (cell.chips, cell.traffic_name) == (1, "seq16384")
    assert cell.traffic["records"]["seq_len"] == 16384
    assert cell.config["name"] == "mellum2_12b_a2p5b"


def test_configuration_keeps_every_published_width():
    """Every key of the catalog row under its own name, the five cuts listed
    and no other key changed, and the model's fields equal to the keys they
    come from."""
    config = manifest_lib.Cell(repo_manifest(), CELL).config
    reduced = [
        "num_hidden_layers", "layer_types", "mlp_layer_types", "num_experts",
        "vocab_size",
    ]
    assert config["reduced"] == reduced
    entry = next(c for c in repo_manifest()["configs"] if c["name"] == config["name"])
    assert entry["reduced"] == reduced and entry["source"] == config["source"]
    for key, value in CATALOG.items():
        if key in reduced:
            assert config["published"][key] == value, key
        else:
            assert config[key] == value, key
    assert set(config["published"]) == set(reduced)
    assert config["layer_types"] == CATALOG["layer_types"][:4]
    assert config["mlp_layer_types"] == ["sparse"] * 4
    assert (config["num_hidden_layers"], config["num_experts"]) == (4, 16)
    assert config["vocab_size"] == 98304 // 4
    params = config["run"]["model_params"]
    published = {
        "hidden_size": "embed_dim", "num_attention_heads": "num_heads",
        "num_key_value_heads": "num_kv_heads", "head_dim": "head_dim",
        "sliding_window": "sliding_window", "rope_parameters": "rope_parameters",
        "rms_norm_eps": "norm_eps", "num_experts_per_tok": "experts_per_token",
        "moe_intermediate_size": "expert_width", "norm_topk_prob": "norm_topk_prob",
        "num_experts": "experts_held", "vocab_size": "vocab_size",
        "attention_bias": "use_bias",
    }
    assert {k: config[k] for k in published} == {k: params[v] for k, v in published.items()}
    assert params["num_experts"] == config["published"]["num_experts"] == 64
    assert "rope_theta" not in params  # rope_parameters names both kinds' rules
    # a layer is two letters: w or * by its type, then E (every layer sparse)
    letters = {SLIDING: "w", FULL: "*"}
    assert params["layer_pattern"] == "".join(
        letters[kind] + "E" for kind in config["layer_types"]
    ) == "wEwEwE*E"
    assert params["num_layers"] == 2 * config["num_hidden_layers"]
    assert (params["expert_kind"], config["hidden_act"]) == ("swiglu", "silu")
    assert params["router_scoring"] == "softmax" and params["qk_norm_per_head"]
    assert "shared_expert_width" not in params and "full_attention_rope" not in params
    assert (params["router_aux_weight"], params["router_z_weight"]) == (0.0, 0.0)
    assert (params["dtype"], params["remat_layers"]) == ("bfloat16", True)
    flops = config["flops"]
    assert (flops["window_layers"], flops["full_layers"], flops["window"]) == (3, 1, 1024)
    assert (flops["heads"], flops["kv_heads"], flops["head_dim"]) == (32, 4, 128)
    assert (flops["d_model"], flops["expert_width"], flops["vocab"]) == (2304, 896, 24576)
    # the reference's constants are the file's
    module = manifest_lib.Cell(repo_manifest(), CELL).module("references", "mellum")
    assert list(module.LAYER_TYPES) == config["layer_types"]
    assert module.ROPE_PARAMETERS == config["rope_parameters"]
    assert (module.SLIDING_WINDOW, module.RMS_NORM_EPS) == (1024, 1e-6)
    assert (module.EXPERTS_PER_TOKEN, module.NORM_TOPK_PROB) == (8, True)
    assert "4 chips share each layer" in config["deployment"]
    assert len(config["assumed"]) >= 10
    # the optimizer's rate is the one the issue asked for first, with its public source
    assert config["run"]["train_args"] == ["--learning_rate", "0.0000073"]
    assert any(
        "7.3e-6" in line and "DeepSeek-V3" in line and "section 4.3" in line
        for line in config["assumed"]
    )
    # the cut's routing is a constant of the step, in the program and in the
    # reference alike, and the file says so among its departures
    assert params["router_trains"] is False and module.ROUTER_TRAINS is False
    assert any("router_trains" in line for line in config["departures"])
    assert any("MTP" in line for line in config["not_built"])
    for key in ("deployment", "assumed", "departures", "not_built", "reference",
                "reduced_why"):
        assert config[key], key
    assert set(config["reference"]["tolerance"]) == {"loss", "grad"}


# ---- the cell's control flow on the CPU ---------------------------------------


def manifest_with_tiny_mellum() -> dict:
    manifest = copy.deepcopy(manifest_with_tiny_cell())
    manifest["configs"].append({
        "name": "tiny_mellum",
        "source": "none: CPU rehearsal of the harness only",
        "file": "tests/perf/configs/tiny_mellum.json",
        "reduced": [],
        "why": "a window part under the base and a full part under YaRN, two expert layers at width 64: control flow only",
    })
    manifest["workloads"].append({
        "name": TINY_CELL, "config": "tiny_mellum", "traffic": "tiny",
        "chips": 1, "why": "2 x 64 tokens a step on the CPU backend through the window path and the YaRN tables",
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
    interpreted, the layers recomputed, the rule by kind of layer from the
    ``k=v`` string the executor hands the trainer, the block plan riding in
    the state."""
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(manifest_with_tiny_mellum()))
    env = {
        k: v for k, v in os.environ.items()
        if k not in ("XLA_FLAGS", "JAX_PLATFORMS", "BENCH_RUN")
    }
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cache")
    done = subprocess.run(
        [
            sys.executable, os.path.join(ROOT, "perf", "run.py"),
            "--workload", TINY_CELL, "--seed", str(2**31 + 61), "--seconds", "2",
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
