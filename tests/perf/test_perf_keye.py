"""The ``keye_vl2_30b_a3b`` configuration's files: the plain reference
against the zoo model with the configuration's fields at sizes a CPU holds
(two sparse-attention expert layers), wrong terms it must catch, a built tie
at the last place of the selection, positions of three distinct components,
the two gradient paths kept apart, the chip's share tied to the whole layer,
the FLOP figures against a count by hand, the ``.dsa`` readers on synthetic
runs, and the cell's control flow rehearsed on the CPU through a test-only
configuration (``configs/tiny_keye.json``)."""

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

CELL = "keye_vl2_seq16384"
TINY_CELL = "tiny_keye_tiny"
EXPERTS, HELD, TOPK, SEQ = 16, 8, 16, 64

FIELDS = dict(
    vocab_size=64, embed_dim=32, num_heads=4, num_kv_heads=2, head_dim=16,
    num_layers=2, norm="rmsnorm", norm_eps=1e-6, use_bias=False, positions="rope",
    rope_theta=1e7, mrope_section=(2, 4, 2), qk_norm_per_head=True, index_topk=TOPK,
    index_heads=4, index_head_dim=8, index_kl_weight=1.0, mlp="swiglu",
    num_experts=EXPERTS, experts_per_token=2, expert_width=16, norm_topk_prob=True,
    experts_held=HELD, first_expert=0, router_aux_weight=0.001, router_z_weight=0.0,
)
# what the parameter tree does not carry, at this size
CONSTANTS = {"EXPERTS_PER_TOKEN": 2, "TOPK": TOPK, "MROPE_SECTION": (2, 4, 2)}


def shipped_reference(**constants):
    module = manifest_lib.Cell(repo_manifest(), CELL).module("references", "keye_vl2")
    for name, value in {**CONSTANTS, **constants}.items():
        setattr(module, name, value)
    return module


def image_positions(batch=2, seq=SEQ):
    """Three DISTINCT components: a frame index, and a row and a column of an
    8-wide grid."""
    index = np.arange(seq)
    parts = np.stack([index // 16, (index // 8) % 8, index % 8])
    return np.broadcast_to(parts[None], (batch, 3, seq)).astype(np.int32)


def tiny_keye(dtype: str, positions=None, **fields):
    """The zoo model and seeded parameters nudged off their init (norm scales
    and the indexer's too); ``system(params, kl=1.0, lm=1.0)`` is the training
    loss with its indexer part and its language-model part weighed."""
    from elasticdl_tpu.models import long_seq_transformer as zoo

    model = zoo.custom_model(dtype=dtype, **{**FIELDS, **fields})
    tokens = np.random.default_rng(3).integers(64, size=(2, SEQ + 1)).astype(np.int32)
    features, labels = {"tokens": tokens[:, :-1]}, tokens[:, 1:]
    if positions is not None:
        features["positions"] = positions
    variables = model.init(jax.random.PRNGKey(1), features, training=False)
    leaves, tree = jax.tree_util.tree_flatten(variables["params"])
    keys = jax.random.split(jax.random.PRNGKey(7), len(leaves))
    params = tree.unflatten(
        [x + 0.05 * jax.random.normal(k, x.shape) for x, k in zip(leaves, keys)]
    )
    state = {k: v for k, v in variables.items() if k != "params"}

    def system(p, kl=1.0, lm=1.0):
        outputs, new = model.apply(
            {"params": p, **state}, features, training=True, mutable=list(state)
        )
        sown = {
            jax.tree_util.keystr(path): leaf
            for path, leaf in jax.tree_util.tree_leaves_with_path(new["losses"])
        }
        indexer = sum(v for k, v in sown.items() if "indexer_kl" in k)
        rest = sum(v for k, v in sown.items() if "indexer_kl" not in k)
        main = zoo.loss(labels, outputs).astype(jnp.float32)
        return lm * (main + rest) + kl * indexer

    return system, params, features, labels


@pytest.fixture(scope="module")
def float32_system():
    system, params, features, labels = tiny_keye(
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


# float32 against float32: the order of the sums.  bfloat16 activations against
# float32: 0.4% a rounding through two attention parts, two expert parts and the
# head (0.042 with every key kept), and a few keys chosen otherwise where two
# index scores lie closer than a rounding: a key is 1/32 of a query's set here
# (0.075; 0.20 at 16 keys a query, which is why the bfloat16 comparison keeps
# 32) and 1/2,048 of it in the cell.  A wrong term moves the loss or the
# gradient past 100 times the float32 limits (below)
TOLERANCE = {"float32": (1e-5, 2e-5), "bfloat16": (5e-3, 0.12)}


@pytest.mark.compiles_a_model
def test_reference_agrees_with_the_zoo_model_in_float32(float32_system):
    loss, grads, *rest = float32_system
    got = reference_errors(shipped_reference(), loss, grads, *rest)
    loss_limit, grad_limit = TOLERANCE["float32"]
    assert got["loss_err"] <= loss_limit and got["grad_err"] <= grad_limit, got
    assert set(got["by_block"]) == {
        "tok_embed", "RMSNorm_0", "lm_head", "block_0", "block_1"
    }
    assert max(got["by_block"].values()) <= 1e-4, got


@pytest.mark.compiles_a_model
def test_reference_agrees_with_the_zoo_model_in_bfloat16():
    system, params, features, labels = tiny_keye("bfloat16", index_topk=2 * TOPK)
    loss, grads = jax.jit(jax.value_and_grad(system))(params)
    module = shipped_reference(TOPK=2 * TOPK)
    got = reference_errors(module, loss, grads, params, features, labels)
    loss_limit, grad_limit = TOLERANCE["bfloat16"]
    assert got["loss_err"] <= loss_limit and got["grad_err"] <= grad_limit, got


@pytest.mark.compiles_a_model
def test_positions_of_three_distinct_components_agree_with_the_reference():
    """The only place mRoPE differs from RoPE: the zoo model reads the
    records' ``positions`` and so does the reference; and they matter (the
    loss with them is not the text loss)."""
    system, params, features, labels = tiny_keye(
        "float32", positions=image_positions()
    )
    loss, grads = jax.jit(jax.value_and_grad(system))(params)
    got = reference_errors(shipped_reference(), loss, grads, params, features, labels)
    loss_limit, grad_limit = TOLERANCE["float32"]
    assert got["loss_err"] <= loss_limit and got["grad_err"] <= grad_limit, got
    text, _, _, _ = tiny_keye("float32")
    assert abs(float(jax.jit(text)(params)) - float(loss)) > 1e-3


def one_key_short(module):
    def select(scores, seen, topk):
        return original(scores, seen, topk - 1)
    original = module.select
    return select


def no_relu(module):
    def index_scores(qi, ki, w):
        return jnp.einsum("bjqk,bqj->bqk", jnp.einsum("bqjd,bkd->bjqk", qi, ki), w)
    return index_scores


def target_not_detached(module):
    def indexer_kl(probs, scores, chosen):
        target = jnp.mean(probs, axis=1)
        log_index = jax.nn.log_softmax(jnp.where(chosen, scores, -jnp.inf), axis=-1)
        live = chosen & (target > 0)
        log_target = jnp.log(jnp.where(live, target, 1.0))
        return jnp.sum(
            jnp.where(live, target * (log_target - jnp.where(live, log_index, 0.0)), 0.0)
        )
    return indexer_kl


def no_head_norm(module):
    def rms_norm(x, p):  # the heads' norms (16 wide here) left out
        return x if p["scale"].shape[-1] == 16 else original(x, p)
    original = module.rms_norm
    return rms_norm


FAULTS = {
    "a_selection_one_key_short": lambda m: {"select": one_key_short(m)},
    "the_relu_left_out": lambda m: {"index_scores": no_relu(m)},
    "the_kl_target_not_detached": lambda m: {"indexer_kl": target_not_detached(m)},
    "mrope_sections_swapped": lambda m: {"MROPE_SECTION": (4, 2, 2)},
    "no_qk_norm": lambda m: {"rms_norm": no_head_norm(m)},
    "weights_not_divided_by_their_sum": lambda m: {"NORM_TOPK_PROB": False},
    "every_expert_held": lambda m: {"FIRST_EXPERT": 4},
    "kl_at_half_weight": lambda m: {"INDEXER_KL_WEIGHT": 0.5},
}


@pytest.mark.parametrize("fault", FAULTS)
@pytest.mark.compiles_a_model
def test_comparison_fails_on_wrong_mathematics(monkeypatch, fault):
    """Each wrong term, in float32 where nothing else differs, is far outside
    the float32 agreement.  ``mrope_sections_swapped`` is read on positions of
    three distinct components (on text the sections cannot matter)."""
    positions = image_positions() if "mrope" in fault else None
    system, params, features, labels = tiny_keye("float32", positions=positions)
    loss, grads = jax.jit(jax.value_and_grad(system))(params)
    module = shipped_reference()
    for name, value in FAULTS[fault](module).items():
        monkeypatch.setattr(module, name, value)
    got = reference_errors(module, loss, grads, params, features, labels)
    loss_limit, grad_limit = TOLERANCE["float32"]
    assert not (
        got["loss_err"] <= 100 * loss_limit and got["grad_err"] <= 100 * grad_limit
    ), got


@pytest.mark.compiles_a_model
def test_comparison_fails_when_the_indexers_input_is_not_detached(monkeypatch):
    """The reference with ``stop_gradient`` taken off the indexer's input: the
    main model's parameters would then receive the KL's gradient too."""
    system, params, features, labels = tiny_keye("float32")
    loss, grads = jax.jit(jax.value_and_grad(system))(params)
    module = shipped_reference()
    real = jax.lax.stop_gradient

    def attention(x, a, positions, with_selection=False):
        # the first detachment of ``attention`` is the indexer's input
        calls = iter([lambda y: y])
        monkeypatch.setattr(
            jax.lax, "stop_gradient", lambda y: next(calls, real)(y)
        )
        try:
            return original(x, a, positions, with_selection)
        finally:
            monkeypatch.setattr(jax.lax, "stop_gradient", real)

    original = module.attention
    monkeypatch.setattr(module, "attention", attention)
    got = reference_errors(module, loss, grads, params, features, labels)
    assert got["loss_err"] <= TOLERANCE["float32"][0]  # the value is the same
    assert got["grad_err"] > 100 * TOLERANCE["float32"][1], got


@pytest.mark.compiles_a_model
def test_control_in_fp8_fails(float32_system):
    """The reference in the program's place with its weights rounded through
    float8 (e4m3), the nearest precision below the bfloat16 the configuration
    states: not correct under the bf16 tolerance."""
    _, _, params, features, labels = float32_system
    module = shipped_reference()
    rounded = jax.tree_util.tree_map(
        lambda x: x.astype(jnp.float8_e4m3fn).astype(jnp.float32), params
    )
    loss_sys, grads_sys = jax.jit(
        lambda p, f, l: module.loss_and_grads(p, f, l)
    )(rounded, features, labels)
    got = reference_errors(module, loss_sys, grads_sys, params, features, labels)
    assert got["grad_err"] > 1.5 * TOLERANCE["bfloat16"][1], got


# ---- the selection -------------------------------------------------------------


@pytest.mark.compiles_a_model
def test_a_built_tie_at_the_last_place_goes_to_the_lower_index_in_both():
    """Index scores with exact ties across the ``topk``-th place (keys that
    are copies of one another score alike for every query): the kernel's
    radix select and the reference's ``lax.top_k`` keep the same set, the
    tied keys from the lowest index up, and the counter says a tie was
    broken."""
    from elasticdl_tpu.ops import sparse_attention as sparse_ops

    seq, heads, width, topk = 128, 2, 8, 8
    rng = np.random.default_rng(0)
    qi = jnp.asarray(rng.normal(size=(1, seq, heads, width)), jnp.float32)
    # every key one of three vectors: whole runs of exact ties
    three = rng.normal(size=(3, width))
    ki = jnp.asarray(three[rng.integers(3, size=seq)][None], jnp.float32)
    w = jnp.asarray(np.abs(rng.normal(size=(1, seq, heads))), jnp.float32)
    mask, lse, kept, ties = sparse_ops.index_select(qi, ki, w, topk, 128, 128)
    module = shipped_reference()
    scores = module.index_scores(qi, ki, w)
    seen = jnp.tril(jnp.ones((seq, seq), bool))[None]
    want = module.select(scores, seen, topk)
    np.testing.assert_array_equal(sparse_ops.dense_mask(mask), want)
    np.testing.assert_array_equal(kept[0], np.minimum(np.arange(seq) + 1, topk))
    assert float(ties.sum()) > seq // 2  # nearly every query had to break one
    # by hand, for the last query: the best vector's keys first, the lowest
    # indices of the next one after them
    last = np.asarray(scores[0, -1])
    order = sorted(range(seq), key=lambda s: (-last[s], s))[:topk]
    assert sorted(order) == list(np.flatnonzero(np.asarray(want[0, -1])))
    np.testing.assert_allclose(
        lse[0], jax.nn.logsumexp(jnp.where(want, scores, -jnp.inf), axis=-1)[0],
        rtol=1e-5, atol=1e-5,
    )


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.compiles_a_model
def test_the_programs_selection_is_the_references_on_the_programs_own_inputs(dtype):
    """What the chip comparison reads layer by layer: the program's mask (its
    ``selection``, kept by an apply that asks for ``intermediates``, through
    ``remat_layers`` too) against ``selections()`` of the reference, which
    carries its own activations forward, and against ``selections(inputs=)``
    on the input the program's indexer read (its ``indexer_input``).  In
    float32 all three are one set.  In bfloat16 the second layer's free-running
    sets part (its input already differs) while on the program's own input
    both layers agree alike: what is left is the rounding of the indexer's
    operands, no fault of the selection."""
    from elasticdl_tpu.models import long_seq_transformer as zoo
    from elasticdl_tpu.ops import sparse_attention as sparse_ops

    _, params, features, _ = tiny_keye(dtype)
    model = zoo.custom_model(dtype=dtype, **{**FIELDS, "remat_layers": True})
    variables = model.init(jax.random.PRNGKey(1), features, training=False)
    state = {k: v for k, v in variables.items() if k != "params"}
    _, new = model.apply(
        {"params": params, **state}, features, training=True,
        mutable=list(state) + ["intermediates"],
    )
    kept = [new["intermediates"][f"block_{i}"]["attn"] for i in range(2)]
    ours = [sparse_ops.dense_mask(layer["selection"][0]) for layer in kept]
    inputs = [layer["indexer_input"][0] for layer in kept]
    assert all(x.dtype == jnp.dtype(dtype) for x in inputs)
    module = shipped_reference()

    def agreement(theirs):
        return [float(jnp.sum(a & b) / jnp.sum(b)) for a, b in zip(ours, theirs)]

    free = agreement(module.selections(params, features))
    own = agreement(module.selections(params, features, inputs))
    if dtype == "float32":
        assert free == own == [1.0, 1.0]
    else:
        assert min(own) >= 0.995 and own[1] > free[1] and free[1] < 0.99, (free, own)


@pytest.mark.compiles_a_model
def test_the_programs_counter_reads_the_keys_a_query_keeps():
    """``selection_stats``: ``sum_t min(t + 1, topk) / T`` keys a query in
    every layer (1,920.06 at 16,384 and 2,048), read on demand."""
    from elasticdl_tpu.models import long_seq_transformer as zoo
    from elasticdl_tpu.telemetry import router_load

    model = zoo.custom_model(dtype="float32", **FIELDS)
    tokens = np.random.default_rng(3).integers(64, size=(2, SEQ)).astype(np.int32)
    variables = model.init(jax.random.PRNGKey(1), {"tokens": tokens}, training=False)
    state = {k: v for k, v in variables.items() if k != "params"}
    assert router_load.read_selection({}) is None
    _, new = model.apply(variables, {"tokens": tokens}, training=True, mutable=list(state))
    got = router_load.read_selection(new)
    want = sum(min(t + 1, TOPK) for t in range(SEQ)) / SEQ
    assert got["kept_keys"] == [want, want] and len(got["ties_broken"]) == 2
    flops = manifest_lib.Cell(repo_manifest(), CELL).module("flop_functions", "keye_vl2")
    assert flops.selected_pairs(SEQ, TOPK) == want * SEQ
    assert flops.selected_pairs(16384, 2048) / 16384 == pytest.approx(1920.0625)
    assert set(new[router_load.LOSS_PARTS]) == {"main", "indexer_kl", "moe_load_balance"}


# ---- the two gradient paths ------------------------------------------------------


@pytest.mark.compiles_a_model
def test_the_two_gradient_paths_stay_apart():
    """The main model's parameters receive the gradient of the language-model
    loss and the balance loss alone (unchanged by the KL's weight), the
    indexers' parameters that of their own KL alone (unchanged by the
    language-model loss), and both are non-zero."""
    system, params, _, _ = tiny_keye("float32")
    grad = jax.jit(jax.grad(system), static_argnums=(1, 2))
    both, no_kl, no_lm = grad(params, 1.0, 1.0), grad(params, 0.0, 1.0), grad(params, 1.0, 0.0)

    def split(tree):
        flat = {
            jax.tree_util.keystr(path): leaf
            for path, leaf in jax.tree_util.tree_leaves_with_path(tree)
        }
        indexer = {k: v for k, v in flat.items() if "index_" in k}
        return indexer, {k: v for k, v in flat.items() if "index_" not in k}

    (ix_both, main_both), (ix_no_kl, main_no_kl), (ix_no_lm, main_no_lm) = map(
        split, (both, no_kl, no_lm)
    )
    assert len(ix_both) == 2 * 5  # query, key, its norm's scale and bias, weights
    for name, value in main_both.items():
        np.testing.assert_array_equal(value, main_no_kl[name], err_msg=name)
        assert not np.any(main_no_lm[name]), name
    for name, value in ix_both.items():
        np.testing.assert_array_equal(value, ix_no_lm[name], err_msg=name)
        assert not np.any(ix_no_kl[name]), name
        assert np.any(value), name
    assert all(np.any(v) for k, v in main_both.items() if "router" not in k)


# ---- the chip's share tied to the model ------------------------------------------


@pytest.mark.compiles_a_model
def test_eight_shares_of_sixteen_experts_add_up_to_the_whole_layer():
    """8 chips, 16 of 128 experts each (``experts_held`` / ``first_expert``):
    the parts add up to what the uncut reference gives for the whole expert
    layer; each share's pair counts add up to every pair, none dropped."""
    from elasticdl_tpu.layers.moe import MoEMLP
    from elasticdl_tpu.telemetry import router_load

    experts, held, per_token, width = 128, 16, 8, 16
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(2, 40, 32), jnp.float32)

    def matrix(*shape):
        return jnp.asarray(rng.randn(*shape) * 0.2, jnp.float32)

    whole = {
        "router": {"kernel": jnp.asarray(rng.randn(32, experts) * 0.5, jnp.float32)},
        "w_gate": matrix(experts, 32, width), "w_up": matrix(experts, 32, width),
        "w_down": matrix(experts, width, 32),
    }
    module = shipped_reference(EXPERTS_PER_TOKEN=per_token)
    want, _ = module.experts(x, whole)
    total, pairs_held, pairs = jnp.zeros_like(x), 0, None
    for chip in range(experts // held):
        first = chip * held
        layer = MoEMLP(
            num_experts=experts, experts_per_token=per_token, expert_width=width,
            norm_topk_prob=True, experts_held=held, first_expert=first,
            aux_loss_weight=0.001, z_loss_weight=0.0,
        )
        params = {
            **whole,
            **{k: whole[k][first:first + held] for k in ("w_gate", "w_up", "w_down")},
        }
        y, sown = layer.apply(
            {"params": params}, x, mutable=["losses", router_load.ROUTER_STATS]
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


def test_flops_come_from_the_published_shapes_counted_by_hand():
    cell = manifest_lib.Cell(repo_manifest(), CELL)
    per_step = cell.flops_per_record()
    d, heads, seq, layers = 2048, 32, 16384, 4
    selected = 2048 * 2049 // 2 + (seq - 2048) * 2048
    causal = seq * (seq + 1) // 2
    assert (selected, causal) == (31_458_304, 134_225_920)
    projections = 2 * d * heads * 128 + 2 * d * 4 * 128
    assert projections == 18_874_368
    assert per_step["attention_projections"] == 6 * seq * layers * projections
    indexer = d * 16 * 64 + d * 64 + d * 16
    assert indexer == 2_260_992
    assert per_step["indexer_projections"] == 6 * seq * layers * indexer
    assert per_step["experts"] == 6 * seq * layers * (8 * 16 / 128) * 3 * d * 768
    assert per_step["router"] == 6 * seq * layers * d * 128
    assert per_step["head"] == 6 * seq * d * 18992
    # scores and values over the selected pairs, 3 x forward
    assert per_step["selected_attention"] == 3 * layers * selected * heads * 256 * 2
    # ISSUE 39: 0.52 TFLOP a layer forward over the selected set, 2.20 dense
    assert per_step["selected_attention"] / 3 / layers == pytest.approx(0.515e12, rel=2e-3)
    assert causal * heads * 256 * 2 == pytest.approx(2.20e12, rel=2e-3)
    # index scores: every causal pair forward (0.27 TFLOP a layer), the
    # selected pairs' two products backward
    assert per_step["index_scores"] == layers * 16 * 64 * 2 * (causal + 2 * selected)
    assert causal * 16 * 64 * 2 == pytest.approx(0.275e12, rel=2e-3)
    assert per_step["train"] == pytest.approx(
        sum(v for k, v in per_step.items() if k != "train")
    )
    assert per_step["train"] == pytest.approx(21.89e12, rel=1e-3)
    sparse = sum(
        per_step[k] for k in ("selected_attention", "index_scores", "indexer_projections")
    )
    assert sparse / per_step["train"] == pytest.approx(0.397, abs=2e-3)
    outside = per_step["train"] - per_step["selected_attention"] - per_step["index_scores"]
    assert per_step["head"] / outside == pytest.approx(0.271, abs=2e-3)  # deployment


def test_parameters_of_the_cut_are_the_files_count():
    """The model ``run.model_params`` builds has the 465,391,104 parameters
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
    attention = params["block_0"]["attn"]
    indexer = {k: v for k, v in attention.items() if k.startswith("index_")}
    assert count(indexer) == 2_261_120
    assert count(attention) - count(indexer) == 18_874_624
    assert count(params["block_0"]["moe"]) == 262_144 + 16 * 4_718_592
    assert count(params["block_0"]) == 96_899_456
    assert (
        count(params["tok_embed"]) + count(params["lm_head"]) + count(params["RMSNorm_0"])
        == 77_793_280
    )
    assert count(params) == 465_391_104
    for figure in ("465,391,104", "21,401,984", "96,899,456", "77,793,280"):
        assert figure in config["reduced_why"], figure
    assert set(shapes["router_stats"]) == set(shapes["selection_stats"]) == {
        f"block_{i}" for i in range(4)
    }
    assert set(shapes["loss_parts"]) == {"main", "indexer_kl", "moe_load_balance"}


# ---- the readers ----------------------------------------------------------------


def synthetic_run(cell):
    """A traced window of 10 steps whose five kernels each took 10 times
    their least time."""
    from perf import dsa_rooflines

    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    spec = cell.config["flops"]
    ops = {"fusion.6": 1.0}
    for n, kernel in enumerate(dsa_rooflines.KERNELS):
        least = dsa_rooflines.least_seconds(kernel, 16384, spec, peaks)
        assert least["compute_bound"], kernel
        ops[f"{kernel}.{n}"] = 10 * 4 * 10 * least["least_s"]
    return {
        "cell": cell, "traced_steps": 10, "peaks": peaks,
        "trace": {"busy_s": 12.0, "op_self_s": ops, "details": {}},
    }


@pytest.mark.parametrize("kernel", ["index", "fwd", "dq", "dkv", "kl"])
def test_roofline_readers_on_a_synthetic_run(kernel):
    cell = manifest_lib.Cell(repo_manifest(), CELL)
    read = cell.reader(f"dsa_{kernel}_roofline.dsa")
    assert read(synthetic_run(cell)) == pytest.approx(10.0)
    assert read({**synthetic_run(cell), "trace": None}) is None
    no_kernel = synthetic_run(cell)
    no_kernel["trace"]["op_self_s"] = {"flash_fwd.5": 0.3, "fusion.6": 1.2}
    assert read(no_kernel) is None  # a program without the kernels: nothing
    # a configuration without an indexer (these files laid over another cell)
    other = manifest_lib.Cell(repo_manifest(), "olmoe_1b7b_seq4096")
    assert read({**synthetic_run(cell), "cell": other}) is None


def test_kernel_operations_and_bytes_by_hand():
    from perf import dsa_rooflines

    spec = manifest_lib.Cell(repo_manifest(), CELL).config["flops"]
    seq, selected, causal = 16384, 31_458_304, 134_225_920
    assert dsa_rooflines.kernel_flops("dsa_fwd", seq, spec) == selected * 32 * 256 * 2
    assert dsa_rooflines.kernel_flops("dsa_dq", seq, spec) == selected * 32 * 256 * 2
    assert dsa_rooflines.kernel_flops("dsa_index", seq, spec) == causal * 16 * 64 * 2
    assert dsa_rooflines.kernel_flops("dsa_kl", seq, spec) == 2 * selected * 16 * 64 * 2
    q, kv, mask = seq * 32 * 128 * 2, seq * 4 * 128 * 2, seq * seq
    assert dsa_rooflines.kernel_bytes("dsa_fwd", seq, spec) == (
        2 * q + 2 * kv + mask + seq * 32 * 4
    )
    # the three attention kernels and the flop function count the same pairs
    per_step = manifest_lib.Cell(repo_manifest(), CELL).flops_per_record()
    assert per_step["selected_attention"] == 4 * sum(
        dsa_rooflines.kernel_flops(k, seq, spec) for k in ("dsa_fwd", "dsa_dq", "dsa_dkv")
    )


def test_time_share_readers_read_the_models_scopes(monkeypatch):
    from perf import scope_shares

    cell = manifest_lib.Cell(repo_manifest(), CELL)
    scopes = {
        ("block/attn/indexer", "forward", "matmul"): 0.2,
        ("block/attn/indexer_kl/dsa_kl", "recompute", "kernel"): 1.0,
        ("block/attn/index_select/dsa_index", "forward", "kernel"): 0.6,
        ("block/attn/index_select", "recompute", "other"): 0.2,
        ("block/attn/dsa_fwd", "forward", "kernel"): 2.0,
        ("block/attn/dsa_dkv", "backward", "kernel"): 2.0,
        ("block/attn/rope", "forward", "other"): 0.5,
        ("block/moe/experts", "forward", "kernel"): 1.5,
    }
    found = {"scopes": scopes, "unattributed": 0.0, "fused_across": 0.0}
    monkeypatch.setattr(scope_shares, "attributed", lambda run: found)
    run = {"trace": {"busy_s": 10.0}}
    assert cell.reader("indexer_time_share.dsa")(run) == pytest.approx(12.0)
    assert cell.reader("selection_time_share.dsa")(run) == pytest.approx(8.0)
    assert cell.reader("sparse_attention_time_share.dsa")(run) == pytest.approx(60.0)
    # a program without an indexer, or without the scope map: nothing
    dense = {k: v for k, v in scopes.items() if "ind" not in k[0]}
    monkeypatch.setattr(
        scope_shares, "attributed", lambda run: {**found, "scopes": dense}
    )
    assert cell.reader("sparse_attention_time_share.dsa")(run) is None
    monkeypatch.setattr(scope_shares, "attributed", lambda run: None)
    assert cell.reader("selection_time_share.dsa")(run) is None


@pytest.mark.parametrize(
    "metric,value",
    [("held_pair_share.dsa", 12.5), ("router_load_max_over_mean.dsa", 3.5)],
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


OWN_READERS = (
    "sparse_attention_time_share.dsa", "indexer_time_share.dsa",
    "selection_time_share.dsa", "dsa_index_roofline.dsa", "dsa_fwd_roofline.dsa",
    "dsa_dq_roofline.dsa", "dsa_dkv_roofline.dsa", "dsa_kl_roofline.dsa",
    "held_pair_share.dsa", "router_load_max_over_mean.dsa",
    "expert_gmm_time_share.dsa", "recompute_share.scope_dsa", "optimizer_share.scope_dsa",
)


def test_cell_reports_the_lm_metrics_it_can_and_its_own():
    """What the cell reports at least: a later PR may put it on further lists
    (the ``.scope_lm`` ones are open at their ends) and add cells and
    configurations beside it."""
    manifest = repo_manifest()
    cell = manifest_lib.Cell(manifest, CELL)
    names = {m["name"] for m in cell.metrics("per_layer")}
    lm = {n for n in names if n.endswith(".lm")}
    # the dense flash kernels' five metrics are not this cell's: its step runs
    # the selected-set kernels under their own names
    assert len(lm) >= 11 and not [n for n in lm if n.startswith("flash_")]
    assert {
        "input_wait_share.lm", "dispatch_ms.lm", "step_device_ms.lm", "step_mfu.lm",
        "bookkeeping_ms.lm", "assemble_ms.lm", "place_ms.lm", "enqueue_ms.lm",
        "fetch_wait_ms.lm", "producer_batch_ms.lm", "producer_busy_share.lm",
    } <= lm
    assert {"setup_trace_s", "setup_lower_s", "setup_compile_s"} <= names
    assert set(OWN_READERS) <= names and len(names) >= 27
    assert {m["name"] for m in cell.metrics("end_to_end")} == {
        "tokens_per_s_chip", "peak_hbm_gb", "setup_s"
    }
    assert (cell.chips, cell.traffic_name) == (1, "seq16384")
    assert cell.traffic["records"]["seq_len"] == 16384


# the listed entry of the same stem, whose unit, better, source and layer an
# own entry repeats
SIBLINGS = {
    "dsa_index_roofline.dsa": "flash_fwd_roofline.lm", "dsa_fwd_roofline.dsa": "flash_fwd_roofline.lm",
    "dsa_dq_roofline.dsa": "flash_dq_roofline.lm", "dsa_dkv_roofline.dsa": "flash_dkv_roofline.lm",
    "dsa_kl_roofline.dsa": "flash_fwd_roofline.lm", "held_pair_share.dsa": "held_pair_share.mla",
    "router_load_max_over_mean.dsa": "router_load_max_over_mean.lm",
    "expert_gmm_time_share.dsa": "expert_gmm_time_share.mla",
    "recompute_share.scope_dsa": "recompute_share.scope_lm",
    "optimizer_share.scope_dsa": "optimizer_share.scope_lm",
    "sparse_attention_time_share.dsa": "flash_time_share.lm",
    "indexer_time_share.dsa": "flash_time_share.lm",
    "selection_time_share.dsa": "flash_time_share.lm",
}


@pytest.mark.parametrize("name", OWN_READERS)
def test_the_manifest_names_the_cells_thirteen_readers(name):
    """The case PR 39 had to withdraw: each of the cell's own readers is an
    entry of ``per_layer``, for this cell, moving its rate, spelled as the
    listed sibling of the same stem is."""
    by_name = {m["name"]: m for m in repo_manifest()["per_layer"]}
    entry = by_name[name]
    assert entry["workloads"][:1] == [CELL]
    sibling = by_name[SIBLINGS[name]]
    for key in ("unit", "better", "source", "layer", "moves"):
        assert entry[key] == sibling[key], key
    assert set(entry) == set(sibling)


@pytest.mark.parametrize("name", OWN_READERS)
def test_own_reader_is_found_by_name_and_reads_nothing_from_an_empty_run(name):
    """The cell's own readers are files the harness finds by name, as it
    finds any entry's; a run without a trace gives them nothing to read."""
    cell = manifest_lib.Cell(repo_manifest(), CELL)
    assert name in {m["name"] for m in cell.metrics("per_layer")}
    assert cell.reader(name)({"cell": cell, "trace": None}) is None


def test_configuration_keeps_every_published_width():
    """Every number of the catalog row under its own key, the four cuts
    listed, and the model's fields equal to the keys they come from."""
    config = manifest_lib.Cell(repo_manifest(), CELL).config
    assert config["reduced"] == [
        "num_hidden_layers", "num_experts", "num_local_experts", "vocab_size"
    ]
    assert config["published"] == {
        "num_hidden_layers": 48, "num_experts": 128, "num_local_experts": 128,
        "vocab_size": 151936,
    }
    assert (config["num_hidden_layers"], config["vocab_size"]) == (4, 151936 // 8)
    assert config["num_experts"] == config["num_local_experts"] == 16
    params = config["run"]["model_params"]
    published = {
        "hidden_size": "embed_dim", "num_attention_heads": "num_heads",
        "num_key_value_heads": "num_kv_heads", "head_dim": "head_dim",
        "rope_theta": "rope_theta", "rms_norm_eps": "norm_eps",
        "num_experts_per_tok": "experts_per_token",
        "moe_intermediate_size": "expert_width", "norm_topk_prob": "norm_topk_prob",
        "num_experts": "experts_held", "num_hidden_layers": "num_layers",
        "vocab_size": "vocab_size",
    }
    assert {k: config[k] for k in published} == {k: params[v] for k, v in published.items()}
    assert params["num_experts"] == config["published"]["num_experts"]
    sparse = config["sa_config"]
    assert sparse == {
        "indexer_head_dim": 64, "indexer_num_heads": 16, "indexer_num_kv_heads": 1,
        "kv_chunk_size": 512, "q_chunk_size": 512, "topk": 2048,
    }
    assert (
        params["index_topk"], params["index_heads"], params["index_head_dim"]
    ) == (sparse["topk"], sparse["indexer_num_heads"], sparse["indexer_head_dim"])
    assert params["mrope_section"] == config["rope_scaling"]["mrope_section"] == [16, 24, 24]
    assert (config["intermediate_size"], config["mlp_only_layers"]) == (6144, [])
    assert (params["mlp"], config["hidden_act"]) == ("swiglu", "silu")
    assert config["attention_bias"] is params["use_bias"] is False
    flops = config["flops"]
    assert (flops["layers"], flops["index_topk"], flops["kv_heads"]) == (4, 2048, 4)
    for key in ("deployment", "assumed", "departures", "not_built", "reference",
                "reduced_why"):
        assert config[key], key
    assert "8 chips share each layer" in config["deployment"]


# ---- the cell's control flow on the CPU ---------------------------------------


def manifest_with_tiny_keye() -> dict:
    manifest = copy.deepcopy(manifest_with_tiny_cell())
    manifest["configs"].append({
        "name": "tiny_keye",
        "source": "none: CPU rehearsal of the harness only",
        "file": "tests/perf/configs/tiny_keye.json",
        "reduced": [],
        "why": "two sparse-attention expert layers at width 64: control flow only",
    })
    manifest["workloads"].append({
        "name": TINY_CELL, "config": "tiny_keye", "traffic": "tiny",
        "chips": 1, "why": "2 x 64 tokens a step on the CPU backend through the sparse path",
    })
    for metric in manifest["end_to_end"] + manifest["per_layer"]:
        if CELL in metric.get("workloads", []):
            metric["workloads"].append(TINY_CELL)
    return manifest


@pytest.mark.compiles_a_model
def test_cell_rehearsal_on_cpu(tmp_path, trace=1):
    """Two tiny layers through ``perf/run.py --rehearse-cpu`` (the traced run,
    which measures untraced first): the path driver, the stacked dispatch, the
    five sparse-attention kernels and the expert kernels interpreted, the
    layers recomputed, the loss by its three parts riding in the state."""
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(manifest_with_tiny_keye()))
    env = {
        k: v for k, v in os.environ.items()
        if k not in ("XLA_FLAGS", "JAX_PLATFORMS", "BENCH_RUN")
    }
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cache")
    done = subprocess.run(
        [
            sys.executable, os.path.join(ROOT, "perf", "run.py"),
            "--workload", TINY_CELL, "--seed", str(2**31 + 39), "--seconds", "2",
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
