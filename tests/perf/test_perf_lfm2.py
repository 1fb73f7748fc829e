"""The ``lfm2_24b_a2b`` configuration's files: the plain reference against the
zoo model with the configuration's fields at sizes a CPU holds (a dense layer
under a convolution part, expert layers under an attention and a convolution
part, a non-zero selection bias, the tied head; the convolution through its
kernels, interpreted), wrong terms it must catch, the chip's share tied to
the whole layer, the tie, the FLOP figures and the parameter count against a
count by hand, the ``.conv`` readers on synthetic runs, and the cell's
control flow rehearsed on the CPU through a test-only configuration
(``configs/tiny_lfm2.json``)."""

import copy
import json
import math
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from perf_testlib import (
    CONV_READERS as OWN_READERS,
    ROOT,
    SWA_READERS,
    manifest_with_tiny_cell,
    repo_manifest,
    stand_together_after,
)

from perf import manifest as manifest_lib, reference

CELL = "lfm2_24b_a2b_seq4096x4"
TINY_CELL = "tiny_lfm2_tiny"
EXPERTS, HELD, SEQ, VOCAB = 16, 8, 64, 64

FIELDS = dict(
    vocab_size=VOCAB, embed_dim=128, num_heads=4, num_kv_heads=2, head_dim=32,
    num_layers=6, layer_pattern="c-*EcE", norm="rmsnorm", norm_eps=1e-5,
    use_bias=False, positions="rope", rope_theta=1e6, qk_norm_per_head=True,
    tie_embedding=True, mlp="swiglu", mlp_width=96,
    num_experts=EXPERTS, experts_per_token=2, expert_width=16,
    norm_topk_prob=True, router_scoring="sigmoid",
    selection_bias=True, routed_scaling=1.0, expert_kind="swiglu",
    shared_expert_width=0, experts_held=HELD, first_expert=0,
    router_aux_weight=0.0, router_z_weight=0.0,
)
# what the parameter tree does not carry, at this size
CONSTANTS = {"EXPERTS_PER_TOKEN": 2}


def shipped_reference():
    module = manifest_lib.Cell(repo_manifest(), CELL).module("references", "lfm2_moe")
    for name, value in CONSTANTS.items():
        setattr(module, name, value)
    return module


def tiny_lfm2(dtype: str, **fields):
    """The zoo model, seeded parameters nudged off their init (norm scales
    too), and a selection bias large enough to change which experts are
    chosen.  Three sequences a batch: the convolution's kernels (128
    channels, interpreted) meet the boundaries between them."""
    from elasticdl_tpu.models import long_seq_transformer as zoo

    model = zoo.custom_model(dtype=dtype, **{**FIELDS, **fields})
    tokens = np.random.default_rng(3).integers(VOCAB, size=(3, SEQ + 1)).astype(np.int32)
    features, labels = {"tokens": tokens[:, :-1]}, tokens[:, 1:]
    variables = model.init(jax.random.PRNGKey(1), features, training=False)
    leaves, tree = jax.tree_util.tree_flatten(variables["params"])
    keys = jax.random.split(jax.random.PRNGKey(7), len(leaves))
    params = tree.unflatten(
        [x + 0.05 * jax.random.normal(k, x.shape) for x, k in zip(leaves, keys)]
    )
    # N(0, 1) rows against a 128-wide state give logits of the order of 11
    # through the tie: a trained embedding's scale keeps the loss in range
    params["tok_embed"]["embedding"] = 0.3 * params["tok_embed"]["embedding"]
    state = {k: v for k, v in variables.items() if k != "params"}
    bias = 0.3 * jax.random.normal(jax.random.PRNGKey(5), (EXPERTS,))
    assert set(state) == {"router_stats"}
    assert set(state["router_stats"]) == {"block_3", "block_5"}
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
    system, params, buffers, features, labels, bias = tiny_lfm2(
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
# against float32: 0.4% a rounding, through the products of three streams in
# each convolution part.  A wrong term moves the loss or the gradient past
# the float32 limits by orders (below)
TOLERANCE = {"float32": (1e-5, 3e-5), "bfloat16": (5e-3, 0.15)}


@pytest.mark.compiles_a_model
def test_reference_agrees_with_the_zoo_model_in_float32(float32_system):
    loss, grads, *rest = float32_system
    got = reference_errors(shipped_reference(), loss, grads, *rest[:-1])
    loss_limit, grad_limit = TOLERANCE["float32"]
    assert got["loss_err"] <= loss_limit and got["grad_err"] <= grad_limit, got
    # the tie: no ``lm_head`` in the tree
    assert set(got["by_block"]) == {
        "tok_embed", "RMSNorm_0", *(f"block_{i}" for i in range(6)),
    }
    assert max(got["by_block"].values()) <= 1e-4, got


@pytest.mark.compiles_a_model
def test_reference_agrees_with_the_zoo_model_in_bfloat16():
    system, params, buffers, features, labels, _ = tiny_lfm2("bfloat16")
    loss, grads = jax.jit(jax.value_and_grad(system))(params)
    got = reference_errors(
        shipped_reference(), loss, grads, params, buffers, features, labels
    )
    loss_limit, grad_limit = TOLERANCE["bfloat16"]
    assert got["loss_err"] <= loss_limit and got["grad_err"] <= grad_limit, got


def conv_with(module, taps=None, gate_in=True, gate_out=True):
    def short_conv(u, p):
        b, c, x = jnp.split(u @ p["in_proj"]["kernel"], 3, axis=-1)
        z = b * x if gate_in else x
        k = p["conv_kernel"] if taps is None else taps(p["conv_kernel"])
        conv = sum(k[2 - s] * module.steps_back(z, s) for s in range(3))
        return ((c * conv) if gate_out else conv) @ p["out_proj"]["kernel"]
    return short_conv


def bias_inside_the_weights(module):
    def route(tokens, m, bias):
        experts = m["router"]["kernel"].shape[1]
        scores = jax.nn.sigmoid(tokens @ m["router"]["kernel"]) + bias
        top, chosen = jax.lax.top_k(scores, module.EXPERTS_PER_TOKEN)
        top = top / (jnp.sum(top, axis=-1, keepdims=True) + module.NORM_TOPK_EPS)
        one_hot = jax.nn.one_hot(chosen, experts, dtype=tokens.dtype)
        return jnp.einsum("tk,tke->te", top, one_hot)
    return route


def head_outside_the_embeddings_gradient(module):
    shipped = module.token_losses

    def token_losses(x, embedding, labels):
        return shipped(x, jax.lax.stop_gradient(embedding), labels)
    return token_losses


def attention_without_the_norm_a_head(module):
    def attention(x, a):
        q, k, v = (
            jnp.einsum("bse,ehd->bshd", x, a[name]["kernel"])
            for name in ("query", "key", "value")
        )
        u = module.causal_attention(module.rotary(q), module.rotary(k), v)
        return jnp.einsum("bshd,hde->bse", u, a["out"]["kernel"])
    return attention


FAULTS = {
    "taps_in_the_other_order": lambda m, bias: {
        "short_conv": conv_with(m, taps=lambda k: k[::-1])
    },
    "no_gate_before_the_taps": lambda m, bias: {"short_conv": conv_with(m, gate_in=False)},
    "no_gate_after_the_taps": lambda m, bias: {"short_conv": conv_with(m, gate_out=False)},
    "a_sequence_sees_the_one_before_it": lambda m, bias: {
        "steps_back": lambda z, s: jnp.roll(
            z.reshape(1, -1, z.shape[-1]), s, axis=1
        ).reshape(z.shape)
    },
    "no_rope": lambda m, bias: {"rotary": lambda x: x},
    "rope_at_another_base": lambda m, bias: {"ROPE_THETA": 1e4},
    "no_norm_a_head": lambda m, bias: {
        "attention": attention_without_the_norm_a_head(m)
    },
    "bias_inside_the_weights": lambda m, bias: {"route": bias_inside_the_weights(m)},
    "weights_not_divided_by_their_sum": lambda m, bias: {"NORM_TOPK_PROB": False},
    "a_routed_scale": lambda m, bias: {"ROUTED_SCALING": 2.5},
    "bias_left_out": lambda m, bias: {
        "selection_bias": lambda buffers, name, moe: jnp.zeros_like(bias)
    },
    "other_experts_held": lambda m, bias: {"FIRST_EXPERT": 4},
    "the_heads_use_of_the_embedding_left_out_of_its_gradient": lambda m, bias: {
        "token_losses": head_outside_the_embeddings_gradient(m)
    },
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
def test_the_divisors_epsilon_is_the_references_alone(monkeypatch):
    """HF adds 1e-6 to the sum the chosen scores are divided by.  The
    reference keeps it, as published; the program divides by the sum alone
    (``departures`` in the configuration's file says so): on a sum of four
    sigmoids that is under a float32 rounding or two of the weights, a
    thousandth of the comparison's tighter limit."""
    module = shipped_reference()
    assert module.NORM_TOPK_EPS == 1e-6
    config = manifest_lib.Cell(repo_manifest(), CELL).config
    assert "norm_topk_eps" not in config["run"]["model_params"]
    assert any("1e-6" in line for line in config["departures"])
    rng = np.random.RandomState(0)
    layer = {
        "router": {"kernel": jnp.asarray(rng.randn(32, 8) * 0.5, jnp.float32)},
        "w_gate": jnp.asarray(rng.randn(8, 32, 16) * 0.2, jnp.float32),
        "w_up": jnp.asarray(rng.randn(8, 32, 16) * 0.2, jnp.float32),
        "w_down": jnp.asarray(rng.randn(8, 16, 32) * 0.2, jnp.float32),
    }
    x = jnp.asarray(rng.randn(2, 40, 32), jnp.float32)
    published = module.experts(x, layer, jnp.zeros((8,), jnp.float32))
    monkeypatch.setattr(module, "NORM_TOPK_EPS", 0.0)
    built = module.experts(x, layer, jnp.zeros((8,), jnp.float32))
    moved = float(jnp.linalg.norm(published - built) / jnp.linalg.norm(published))
    loss_limit = config["reference"]["tolerance"]["loss"]
    assert 0 < moved < 1e-3 * loss_limit, moved


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


# ---- the chip's share, and the tie, tied to the model ------------------------------


@pytest.mark.compiles_a_model
def test_four_shares_of_eight_experts_add_up_to_the_whole_layer():
    """4 chips, 2 of 8 experts each (``experts_held`` / ``first_expert``),
    sigmoid scores, a selection bias and no shared expert: nothing is
    computed alike on every chip, so the four parts add up, with nothing to
    count once, to what the uncut reference gives for the whole expert
    layer; and each share's pair counts add up to every pair, none dropped,
    none counted twice."""
    from elasticdl_tpu.layers.moe import MoEMLP
    from elasticdl_tpu.telemetry import router_load

    experts, held, per_token, width = 8, 2, 4, 16
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(2, 40, 32), jnp.float32)

    def matrix(*shape):
        return jnp.asarray(rng.randn(*shape) * 0.2, jnp.float32)

    whole = {
        "router": {"kernel": jnp.asarray(rng.randn(32, experts) * 0.5, jnp.float32)},
        "w_gate": matrix(experts, 32, width), "w_up": matrix(experts, 32, width),
        "w_down": matrix(experts, width, 32),
    }
    bias = jnp.asarray(rng.randn(experts) * 0.2, jnp.float32)
    module = shipped_reference()
    module.EXPERTS_PER_TOKEN = per_token
    want = module.experts(x, whole, bias)

    total, pairs_held, pairs = jnp.zeros_like(x), 0, None
    for chip in range(experts // held):
        first = chip * held
        layer = MoEMLP(
            num_experts=experts, experts_per_token=per_token, expert_width=width,
            norm_topk_prob=True, scoring="sigmoid",
            selection_bias=True, routed_scaling=1.0, expert_kind="swiglu",
            shared_width=0, experts_held=held, first_expert=first,
            aux_loss_weight=0.0, z_loss_weight=0.0,
        )
        params = {
            **whole,
            **{k: whole[k][first:first + held] for k in ("w_gate", "w_up", "w_down")},
        }
        y, sown = layer.apply(
            {"params": params, router_load.ROUTER_STATS: {"selection_bias": bias}},
            x, mutable=["losses", router_load.ROUTER_STATS],
        )
        assert float(jnp.linalg.norm(y)) > 0  # every share gives a part
        total = total + y
        load = router_load.read(sown)
        assert load["dropped_pairs"] == 0
        assert load["held_pairs"] + load["absent_pairs"] == load["pairs"]
        pairs_held += load["held_pairs"]
        pairs = load["pairs"]
    assert pairs_held == pairs == 2 * 40 * per_token
    np.testing.assert_allclose(total, want, rtol=2e-5, atol=2e-6)


@pytest.mark.compiles_a_model
def test_the_tied_head_is_one_parameter_whose_gradient_is_the_sum_of_both_uses():
    from elasticdl_tpu.models import long_seq_transformer as zoo

    fields = {**FIELDS, "num_layers": 2, "layer_pattern": "c-"}
    tokens = np.random.default_rng(4).integers(VOCAB, size=(2, 17)).astype(np.int32)
    features, labels = {"tokens": tokens[:, :-1]}, tokens[:, 1:]

    def loss_of(model):
        def f(params):
            return zoo.loss(labels, model.apply({"params": params}, features))
        return f

    tied = zoo.custom_model(dtype="float32", **fields)
    params = tied.init(jax.random.PRNGKey(0), features)["params"]
    assert "lm_head" not in params and "tok_embed" in params
    assert sum(x.size for x in jax.tree_util.tree_leaves(params["tok_embed"])) == VOCAB * 128
    untied = zoo.custom_model(dtype="float32", **{**fields, "tie_embedding": False})
    embedding = params["tok_embed"]["embedding"]
    both = {**params, "lm_head": {"kernel": embedding.T}}
    assert jax.tree_util.tree_structure(
        untied.init(jax.random.PRNGKey(0), features)["params"]
    ) == jax.tree_util.tree_structure(both)
    loss_tied, grads_tied = jax.value_and_grad(loss_of(tied))(params)
    loss_untied, grads_untied = jax.value_and_grad(loss_of(untied))(both)
    np.testing.assert_allclose(loss_tied, loss_untied, rtol=1e-6)
    by_use = (
        grads_untied["tok_embed"]["embedding"], grads_untied["lm_head"]["kernel"].T
    )
    assert all(float(jnp.linalg.norm(g)) > 0 for g in by_use)
    np.testing.assert_allclose(
        grads_tied["tok_embed"]["embedding"], by_use[0] + by_use[1],
        rtol=1e-5, atol=1e-7,
    )
    # a tied head has no bias to carry
    with pytest.raises(ValueError, match="tied head"):
        zoo.custom_model(**{**fields, "use_bias": True}).init(
            jax.random.PRNGKey(0), features
        )


def test_the_model_refuses_to_decode_through_a_convolution_layer():
    from elasticdl_tpu.models import long_seq_transformer as zoo

    model = zoo.custom_model(**FIELDS)
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), {"tokens": jnp.zeros((1, 8), jnp.int32)})
    )["params"]
    with pytest.raises(NotImplementedError, match="short-convolution"):
        zoo.generate(
            jax.tree_util.tree_map(lambda s: jnp.zeros(s.shape, s.dtype), params),
            np.zeros((1, 2), np.int32), 2, model=model,
        )


# ---- arithmetic -----------------------------------------------------------------


def test_flops_come_from_the_published_shapes_counted_by_hand():
    cell = manifest_lib.Cell(repo_manifest(), CELL)
    seq = 4096
    per_token = {k: v / seq for k, v in cell.flops_per_record().items()}
    d = 2048
    # in 2,048 -> 6,144 and out 2,048 -> 2,048; four conv layers
    assert per_token["conv_projections"] == 6 * 4 * d * (6144 + 2048)
    assert per_token["conv_projections"] / 6 / 4 == 16_777_216
    # three taps and two gates a channel
    assert per_token["conv_taps"] == 6 * 4 * 5 * d
    # q and output 2,048 x 2,048 each, k and v 2,048 x 512 each
    assert per_token["attention_projections"] == 6 * (2 * d * 2048 + 2 * d * 512)
    causal_pairs = seq * (seq + 1) // 2
    assert causal_pairs == 8_390_656
    assert seq * per_token["causal_attention"] == 6 * 32 * 2 * 64 * causal_pairs
    assert per_token["dense_mlp"] == 6 * 3 * d * 11776 == 6 * 72_351_744
    assert per_token["experts"] == 6 * 4 * (4 * 8 / 64) * 3 * d * 1536
    assert per_token["router"] == 6 * 4 * d * 64
    assert per_token["head"] == 6 * d * 8192  # the tie: one product
    assert per_token["train"] == pytest.approx(
        sum(v for k, v in per_token.items() if k != "train")
    )
    # ISSUE 48: ~389M a token forward, 19.1 T a step of 4 x 4,096 tokens
    # forward and backward; dense MLP 37%, the conv projections 35%,
    # attention 10% (its kernels 4%), held experts 10%, head 9%
    assert per_token["train"] / 3 == pytest.approx(389e6, rel=5e-3)
    assert 4 * seq * per_token["train"] == pytest.approx(19.1e12, rel=5e-3)
    share = {k: v / per_token["train"] for k, v in per_token.items()}
    assert share["dense_mlp"] == pytest.approx(0.372, abs=2e-3)
    assert share["conv_projections"] == pytest.approx(0.345, abs=2e-3)
    assert share["causal_attention"] == pytest.approx(0.043, abs=2e-3)
    assert share["causal_attention"] + share["attention_projections"] == pytest.approx(
        0.097, abs=2e-3
    )
    assert share["experts"] == pytest.approx(0.097, abs=2e-3)
    assert share["head"] == pytest.approx(0.086, abs=2e-3)


def test_parameters_of_the_cut_are_the_files_count():
    """The model ``run.model_params`` builds has the 469,284,992 parameters
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
    conv = params["block_0"]["conv"]
    assert count(conv) == 16_783_360
    assert conv["in_proj"]["kernel"].shape == (2048, 6144)
    assert conv["conv_kernel"].shape == (3, 2048)
    assert conv["out_proj"]["kernel"].shape == (2048, 2048)
    assert count(params["block_2"]["attn"]) == 10_485_888
    assert params["block_2"]["attn"]["q_norm"]["scale"].shape == (64,)
    # layer 0 (conv + dense MLP), the attention expert layer, a conv expert layer
    assert count(params["block_0"]) + count(params["block_1"]) == 89_139_200
    assert count(params["block_1"]) == 72_351_744 + 2048
    assert count(params["block_2"]) + count(params["block_3"]) == 86_118_528
    for conv_block in (4, 6, 8):
        assert count(params[f"block_{conv_block}"]) + count(
            params[f"block_{conv_block + 1}"]
        ) == 92_416_000
    assert count(params["block_3"]["moe"]) == 8 * 9_437_184 + 131_072
    assert set(params["block_3"]["moe"]) == {"router", "w_gate", "w_up", "w_down"}
    assert count(params["tok_embed"]) == 16_777_216 and "lm_head" not in params
    assert count(params["RMSNorm_0"]) == 2048
    assert count(params) == 469_284_992
    assert "469,284,992" in config["reduced_why"]
    assert set(shapes["router_stats"]) == {"block_3", "block_5", "block_7", "block_9"}


# ---- the readers ----------------------------------------------------------------


def synthetic_run():
    cell = manifest_lib.Cell(repo_manifest(), CELL)
    return {
        "cell": cell,
        "trace": {
            "busy_s": 4.0,
            "op_self_s": {
                "short_conv_fwd.1": 0.016, "short_conv_fwd.2": 0.010,
                "short_conv_bwd.3": 0.024, "mamba_conv_fwd.4": 0.5,
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


def test_time_share_readers_on_a_synthetic_run():
    cell = manifest_lib.Cell(repo_manifest(), CELL)
    run = synthetic_run()
    # the two kernels alone: the Mamba-2 convolution is another's
    assert cell.reader("short_conv_time_share.conv")(run) == pytest.approx(1.25)
    assert cell.reader("expert_gmm_time_share.conv")(run) == pytest.approx(2.5)
    assert cell.reader("flash_time_share.lm")(run) == pytest.approx(18.75)
    # a program with no such kernel (the parent), or no trace
    bare = synthetic_run()
    bare["trace"]["op_self_s"] = {"flash_fwd.4": 0.3, "fusion.10": 1.2}
    for name in OWN_READERS[:3]:
        assert cell.reader(name)(bare) is None, name
        assert cell.reader(name)({**run, "trace": None}) is None, name
    assert cell.reader("conv_operator_share.scope_conv")({**run, "trace": None}) is None


@pytest.mark.parametrize("kernel,streams,calls", [("fwd", 4, 8), ("bwd", 7, 4)])
def test_roofline_readers_on_a_synthetic_run(kernel, streams, calls):
    """A kernel's share: its streams of 4 x 4,096 x 2,048 bfloat16 and the
    taps in float32, a call a convolution layer (the forward's twice, the
    layers being recomputed), eight steps, over its time and the HBM peak."""
    from perf import conv_rooflines

    cell = manifest_lib.Cell(repo_manifest(), CELL)
    run = synthetic_run()
    taps = 3 * 2048 * 4 * (2 if kernel == "bwd" else 1)
    moved = streams * 4 * 4096 * 2048 * 2 + taps
    assert conv_rooflines.kernel_bytes(
        f"short_conv_{kernel}", 4 * 4096, cell.config["flops"]
    ) == moved
    assert conv_rooflines.calls_per_step(f"short_conv_{kernel}", cell.config) == calls
    seconds = sum(
        s for name, s in run["trace"]["op_self_s"].items()
        if name.startswith(f"short_conv_{kernel}.")
    )
    want = 100.0 * 8 * calls * moved / 819e9 / seconds
    assert cell.reader(f"short_conv_{kernel}_roofline.conv")(run) == pytest.approx(want)
    assert 0 < want < 100


def test_the_operators_share_is_everything_under_its_part(monkeypatch):
    from perf import scope_shares

    cell = manifest_lib.Cell(repo_manifest(), CELL)
    run = synthetic_run()
    run[scope_shares._KEY] = {
        "scopes": {
            ("block/conv/in_proj", "forward", "matmul"): 0.5,
            ("block/conv/pass/short_conv_fwd", "recompute", "kernel"): 0.1,
            ("block/conv/out_proj", "backward", "matmul"): 0.4,
            ("block/mamba/mamba_conv", "forward", "kernel"): 0.7,
            ("block/attn/query", "forward", "matmul"): 0.3,
            ("optimizer", "optimizer", "other"): 0.2,
        },
        "unattributed": 0.0, "fused_across": 0.0,
    }
    assert cell.reader("conv_operator_share.scope_conv")(run) == pytest.approx(25.0)


@pytest.mark.parametrize(
    "metric,value",
    [("held_pair_share.conv", 12.5), ("router_load_max_over_mean.conv", 3.5)],
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


def test_cell_reports_the_lm_metrics_it_can():
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
        "flash_time_share.lm", "flash_roofline.lm", "flash_fwd_roofline.lm",
        "flash_dq_roofline.lm", "flash_dkv_roofline.lm",
        "forward_share.scope_lm", "backward_share.scope_lm",
        "optimizer_share.scope_lm", "recompute_share.scope_lm",
        "head_loss_share.scope_lm", "attention_other_share.scope_lm",
        "experts_other_share.scope_lm", "block_other_share.scope_lm",
        "fused_across_share.scope_lm", "unattributed_share.scope_lm",
    } <= names
    assert "collective_exposed_share.lm" not in names  # dp4's alone
    # no share of a roofline on a balanced expert count (ISSUE 34)
    assert not [n for n in names if "expert" in n and "roofline" in n]
    assert {m["name"] for m in cell.metrics("end_to_end")} == {
        "tokens_per_s_chip", "peak_hbm_gb", "setup_s"
    }
    assert (cell.chips, cell.traffic_name) == (1, "seq4096x4")
    assert cell.traffic["records"]["seq_len"] == 4096
    assert cell.traffic["batch_per_chip"] == 4
    assert cell.reference().__name__.endswith("lfm2_moe")


def test_the_cells_own_entries_stand_in_the_manifest():
    """The seven readers of what this configuration adds, each this cell's
    first and each moving its rate.  They waited as data beside their readers
    (``conv_entries.json``) while tests/perf/test_perf_trinity.py held the
    eight ``.swa`` entries to the end of ``per_layer``; PR 59 dropped that pin
    and listed them, in the file's order, after the ``.swa`` entries.  Held
    here: they keep the manifest's rules and the cell reports them through
    the files that are there; a later PR may put further cells on their
    lists and entries after them."""
    manifest = repo_manifest()
    listed = [m["name"] for m in manifest["per_layer"]]
    assert len(set(listed)) == len(listed)
    own = [m for m in manifest["per_layer"] if m["name"] in OWN_READERS]
    assert tuple(m["name"] for m in own) == OWN_READERS
    assert stand_together_after(listed, OWN_READERS, SWA_READERS)
    keys = ["name", "unit", "better", "source", "layer", "moves", "workloads"]
    assert all(list(m) == keys for m in own)
    assert all(m["workloads"][:1] == [CELL] for m in own)
    assert all(m["moves"] == "tokens_per_s_chip" for m in own)
    assert all(m["better"] in ("lower", "higher") for m in own)
    assert {m["source"] for m in own} == {"device_trace", "program_counter"}
    assert {m["name"]: m["unit"] for m in own if m["unit"] != "%"} == {
        "router_load_max_over_mean.conv": "x"
    }
    # the experts' layer is spelled as the entries before these spell it
    before = {m["layer"] for m in manifest["per_layer"][: listed.index(OWN_READERS[0])]}
    assert {m["layer"] for m in own} - before == {"kernels (ops/short_conv.py)"}
    assert {m["layer"] for m in own} == {
        "kernels (ops/short_conv.py)", "experts (layers/moe.py, ops/grouped_matmul.py)"
    }
    cell = manifest_lib.Cell(manifest, CELL)
    assert set(OWN_READERS) <= {m["name"] for m in cell.metrics("per_layer")}
    for name in OWN_READERS:
        assert callable(cell.reader(name)), name


def test_configuration_keeps_every_published_width():
    """Every number of the catalog row under its own key, the five cuts
    listed, and the model's fields equal to the keys they come from."""
    config = manifest_lib.Cell(repo_manifest(), CELL).config
    assert config["reduced"] == [
        "num_hidden_layers", "num_dense_layers", "layer_types", "num_experts",
        "vocab_size",
    ]
    conv, full = "conv", "full_attention"
    types = [conv, conv] + [full, conv, conv, conv] * 9 + [full, conv]
    assert config["published"] == {
        "num_hidden_layers": 40, "num_dense_layers": 2, "layer_types": types,
        "num_experts": 64, "vocab_size": 65536,
    }
    # published layer 0, then layers 2..5: one whole period, 1 : 3
    assert config["layer_types"] == types[:1] + types[2:6]
    assert (config["num_hidden_layers"], config["num_dense_layers"]) == (5, 1)
    assert (config["num_experts"], config["vocab_size"]) == (8, 65536 // 8)
    params = config["run"]["model_params"]
    published = {
        "hidden_size": "embed_dim", "num_attention_heads": "num_heads",
        "num_key_value_heads": "num_kv_heads",
        "norm_eps": "norm_eps", "intermediate_size": "mlp_width",
        "num_experts_per_tok": "experts_per_token",
        "moe_intermediate_size": "expert_width", "norm_topk_prob": "norm_topk_prob",
        "routed_scaling_factor": "routed_scaling", "num_experts": "experts_held",
        "use_expert_bias": "selection_bias", "conv_bias": "use_bias",
        "vocab_size": "vocab_size",
    }
    assert {k: config[k] for k in published} == {k: params[v] for k, v in published.items()}
    # the taps are the part's own default: one value in use, no model field
    from elasticdl_tpu.layers.short_conv import ShortConv

    assert (config["hidden_size"], config["conv_L_cache"]) == (2048, ShortConv.taps) == (2048, 3)
    assert "short_conv_taps" not in params
    assert params["head_dim"] == config["hidden_size"] // config["num_attention_heads"] == 64
    assert params["rope_theta"] == config["rope_parameters"]["rope_theta"] == 1_000_000
    assert params["num_experts"] == config["published"]["num_experts"] == 64
    assert (params["shared_expert_width"], params["router_scoring"]) == (0, "sigmoid")
    # a layer is two letters: c or * by its type, then - (dense) or E
    letters = {conv: "c", full: "*"}
    assert params["layer_pattern"] == "".join(
        letters[kind] + ("-" if i < config["num_dense_layers"] else "E")
        for i, kind in enumerate(config["layer_types"])
    ) == "c-*EcEcEcE"
    assert params["num_layers"] == 2 * config["num_hidden_layers"]
    assert params["tie_embedding"] is True and params["qk_norm_per_head"] is True
    assert (params["router_aux_weight"], params["router_z_weight"]) == (0.0, 0.0)
    flops = config["flops"]
    assert (flops["conv_layers"], flops["attention_layers"]) == (4, 1)
    assert (flops["dense_layers"], flops["expert_layers"], flops["conv_taps"]) == (1, 4, 3)
    # the reference's constants are the file's
    module = manifest_lib.Cell(repo_manifest(), CELL).module("references", "lfm2_moe")
    assert (module.ROPE_THETA, module.RMS_NORM_EPS) == (1e6, 1e-5)
    assert (module.EXPERTS_PER_TOKEN, module.ROUTED_SCALING) == (4, 1.0)
    assert module.NORM_TOPK_PROB is True and module.FIRST_EXPERT == 0
    assert "8 chips share each layer" in config["deployment"]
    assert "1,024" in config["deployment"] and "8,192" in config["deployment"]
    assert len(config["assumed"]) >= 7
    for key in ("deployment", "assumed", "departures", "not_built", "reference",
                "reduced_why"):
        assert config[key], key
    assert "TODO" not in json.dumps(config)


def test_the_window_ends_while_the_routers_are_balanced():
    """The cell reads alike at every seed only while the cut's routers hold
    the balanced load (the configuration's ``window`` group has the
    readings): the step at which the benchmark's window ends, from
    ``run_seconds``, the traffic file's warm-up, fill and task size as
    ``perf/executor.py::Probe`` counts them and the measured step time, is
    the one written down and no later than the last step read balanced."""
    manifest = repo_manifest()
    cell = manifest_lib.Cell(manifest, CELL)
    traffic, window = cell.traffic, cell.config["window"]
    per_interval = traffic["steps_per_task"] * traffic["tasks_per_interval"]
    before = (
        traffic["warmup_tasks"] * traffic["steps_per_task"]
        + max(3, traffic["fill_intervals"]) * per_interval
    )
    intervals = math.ceil(manifest["run_seconds"] / (per_interval * window["step_s"]))
    ends = before + intervals * per_interval
    assert (before, ends) == (12, window["ends_at_step"]) == (12, 51)
    assert ends <= window["balanced_through_step"] < window["first_climb_at_step"]
    # the three places that describe the window say the same
    why = next(w["why"] for w in manifest["workloads"] if w["name"] == CELL)
    for text in (traffic["notes"], why, window["why"]):
        assert f"step {ends}" in text or f"..{ends}" in text, text
        assert str(window["balanced_through_step"]) in text, text


# ---- the cell's control flow on the CPU ---------------------------------------


def manifest_with_tiny_lfm2() -> dict:
    manifest = copy.deepcopy(manifest_with_tiny_cell())
    manifest["configs"].append({
        "name": "tiny_lfm2",
        "source": "none: CPU rehearsal of the harness only",
        "file": "tests/perf/configs/tiny_lfm2.json",
        "reduced": [],
        "why": "two convolution parts and an attention part, a dense and two expert layers at width 128, a tied head: control flow only",
    })
    manifest["workloads"].append({
        "name": TINY_CELL, "config": "tiny_lfm2", "traffic": "tiny",
        "chips": 1, "why": "2 x 64 tokens a step on the CPU backend through the convolution path",
    })
    for metric in manifest["end_to_end"] + manifest["per_layer"]:
        if CELL in metric.get("workloads", []):
            metric["workloads"].append(TINY_CELL)
    return manifest


@pytest.mark.compiles_a_model
def test_cell_rehearsal_on_cpu(tmp_path, trace=1):
    """Six tiny parts through ``perf/run.py --rehearse-cpu`` (the traced run,
    which measures untraced first): the path driver, the stacked dispatch,
    the convolution's kernels, the flash kernels and the expert kernels
    interpreted, the layers recomputed, the selection bias riding in the
    state, the head tied."""
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(manifest_with_tiny_lfm2()))
    env = {
        k: v for k, v in os.environ.items()
        if k not in ("XLA_FLAGS", "JAX_PLATFORMS", "BENCH_RUN")
    }
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cache")
    done = subprocess.run(
        [
            sys.executable, os.path.join(ROOT, "perf", "run.py"),
            "--workload", TINY_CELL, "--seed", str(2**31 + 48), "--seconds", "2",
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
