"""The ``qwen3_next_80b_a3b`` configuration's files: the plain reference (the
delta rule one step at a time) against the zoo model with the configuration's
fields at sizes a CPU holds (a Gated DeltaNet layer and a gated softmax
layer with rotary positions on a head's leading lanes, softmax-routed experts
renormalised over the chosen with half of them held, a gated shared expert),
wrong terms it must catch, the chip's share tied to the whole layer, the FLOP
and byte figures against counts by hand, the parameter count of the cut, the
configuration against the catalog's row, the cell's own readers on a synthetic
run and on a run without the kernels, and the cell's control flow rehearsed on
the CPU through a test-only configuration (``configs/tiny_qwen3_next.json``)."""

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

CELL = "qwen3_next_seq16384"
TINY_CELL = "tiny_qwen3_next_tiny"
EXPERTS, HELD, SEQ = 16, 8, 64

# the catalog's row (architectures.jsonl, Qwen3-Next-80B-A3B-Instruct): config
CATALOG = {
    "decoder_sparse_step": 1, "full_attention_interval": 4, "head_dim": 256,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 5120,
    "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128,
    "linear_num_key_heads": 16, "linear_num_value_heads": 32,
    "linear_value_head_dim": 128, "max_position_embeddings": 262144,
    "mlp_only_layers": [], "model_type": "qwen3_next",
    "moe_intermediate_size": 512, "norm_topk_prob": True,
    "num_attention_heads": 16, "num_experts": 512, "num_experts_per_tok": 10,
    "num_hidden_layers": 48, "num_key_value_heads": 2,
    "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-06, "rope_scaling": None,
    "rope_theta": 10000000, "shared_expert_intermediate_size": 512,
    "tie_word_embeddings": False, "use_sliding_window": False,
    "vocab_size": 151936,
}
# one delta-rule layer and one gated softmax layer, each with its experts: key
# heads of 32 beside value heads of 16 (the two widths differ, as the state's
# two sides may), four chunks of 16 steps, 4 rotating lanes of 16
FIELDS = dict(
    vocab_size=64, embed_dim=32, num_heads=4, num_kv_heads=2, head_dim=16,
    num_layers=4, layer_pattern="dE*E", norm="rmsnorm", norm_eps=1e-6,
    use_bias=False, positions="rope", rope_theta=100.0,
    partial_rotary_factor=0.25, qk_norm_per_head=True, output_gate=True,
    linear_key_heads=2, linear_value_heads=4, linear_key_dim=32,
    linear_value_dim=16, conv_kernel=4, delta_chunk=16, mlp="swiglu",
    num_experts=EXPERTS, experts_per_token=2, expert_width=16,
    norm_topk_prob=True, router_scoring="softmax", expert_kind="swiglu",
    shared_expert_width=16, shared_expert_gate=True, experts_held=HELD,
    first_expert=0, router_aux_weight=0.0, router_z_weight=0.0,
    router_trains=False,
)
# what the parameter tree does not carry, at this size
CONSTANTS = {
    "EXPERTS_PER_TOKEN": 2, "LINEAR_KEY_HEADS": 2, "LINEAR_KEY_DIM": 32,
    "ROTARY_DIM": 4, "ROPE_THETA": 100.0,
}


def shipped_reference():
    module = manifest_lib.Cell(repo_manifest(), CELL).module(
        "references", "qwen3_next"
    )
    for name, value in CONSTANTS.items():
        setattr(module, name, value)
    return module


def tiny_qwen3_next(dtype: str, **fields):
    """The zoo model and seeded parameters nudged off their init (norm scales
    too): four chunks of the delta rule a sequence."""
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
    assert set(state["router_stats"]) == {"block_1", "block_3"}
    assert set(state["delta_state"]) == {"block_0"}

    def system(p):
        outputs, _ = model.apply(
            {"params": p, **state}, features, training=True, mutable=list(state)
        )
        return zoo.loss(labels, outputs).astype(jnp.float32)

    return system, params, features, labels


@pytest.fixture(scope="module")
def float32_system():
    system, params, features, labels = tiny_qwen3_next(
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


# float32 against float32: the chunked form (T by a triangular solve) against
# the recurrence a step at a time, the order of the experts' sums.  A wrong
# term moves the loss or the gradient past these limits by orders (below).
# (The bfloat16 model is not compared here: a model this narrow with its
# weights nudged by 0.05 reads 0.35 over eight blocks where four attention
# layers in the delta rule's place read 0.16 and the float8 control 1.48, so
# the reading says little of the cell, whose sound runs read 0.028 on the
# chip: PERF.md section 7, From PR 65 (c).  It costs a second compile of the
# system, 36 s.)
TOLERANCE = {"float32": (1e-5, 5e-5)}


@pytest.mark.compiles_a_model
def test_reference_agrees_with_the_zoo_model_in_float32(float32_system):
    loss, grads, *rest = float32_system
    got = reference_errors(shipped_reference(), loss, grads, *rest)
    loss_limit, grad_limit = TOLERANCE["float32"]
    assert got["loss_err"] <= loss_limit and got["grad_err"] <= grad_limit, got
    assert set(got["by_block"]) == {
        "tok_embed", "RMSNorm_0", "lm_head", *(f"block_{i}" for i in range(4)),
    }
    assert max(got["by_block"].values()) <= 1e-4, got


def beta_on_the_value_alone(state, at):
    """``beta`` left out of ``K_b``: the write is ``k (beta v - S^T k)^T``."""
    q_t, k_t, v_t, g_t, beta_t = at
    state = jnp.exp(g_t)[..., None, None] * state
    missing = beta_t[..., None] * v_t - jnp.einsum("bhkv,bhk->bhv", state, k_t)
    state = state + jnp.einsum("bhk,bhv->bhkv", k_t, missing)
    return state, jnp.einsum("bhkv,bhk->bhv", state, q_t)


def error_before_the_decay(state, at):
    """The error read from the state before it decays."""
    q_t, k_t, v_t, g_t, beta_t = at
    missing = v_t - jnp.einsum("bhkv,bhk->bhv", state, k_t)
    state = jnp.exp(g_t)[..., None, None] * state + jnp.einsum(
        "bh,bhk,bhv->bhkv", beta_t, k_t, missing
    )
    return state, jnp.einsum("bhkv,bhk->bhv", state, q_t)


def rotary_on_the_last_lanes(module):
    def rotary(x):
        turned = original(jnp.flip(x, axis=-1))
        return jnp.flip(turned, axis=-1)
    original = module.rotary
    return rotary


def gate_then_norm(module):
    def gated_norm(o, z, scale):
        gated = o * jax.nn.silu(z)
        variance = jnp.mean(jnp.square(gated), axis=-1, keepdims=True)
        return gated * jax.lax.rsqrt(variance + module.RMS_NORM_EPS) * scale
    return gated_norm


def shared_expert_without_its_gate(module):
    def shared_expert(tokens, m):
        return module.swiglu(
            tokens, m["shared_gate"]["kernel"], m["shared_up"]["kernel"],
            m["shared_down"]["kernel"],
        )
    return shared_expert


FAULTS = {
    # the five ISSUE 65 names
    "l2_norm_left_out": lambda m: {"l2norm": lambda x: x},
    "beta_left_out_of_the_keys": lambda m: {"delta_step": beta_on_the_value_alone},
    "norm_and_gate_swapped": lambda m: {"gated_norm": gate_then_norm(m)},
    "rotary_on_the_last_lanes": lambda m: {"rotary": rotary_on_the_last_lanes(m)},
    "shared_gate_left_out": lambda m: {
        "shared_expert": shared_expert_without_its_gate(m)
    },
    # and their neighbours
    "error_read_before_the_decay": lambda m: {"delta_step": error_before_the_decay},
    "the_whole_head_rotating": lambda m: {"ROTARY_DIM": 16},
    "the_routing_differentiated": lambda m: {"ROUTER_TRAINS": True},
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
    states: not correct under the shipped configuration's gradient limit."""
    _, _, params, features, labels = float32_system
    module = shipped_reference()
    loss_sys, grads_sys = jax.jit(
        lambda p, f, l: module.loss_and_grads(p, f, l)
    )(reference.float8_weights(params), features, labels)
    got = reference_errors(module, loss_sys, grads_sys, params, features, labels)
    limit = manifest_lib.Cell(repo_manifest(), CELL).config["reference"]["tolerance"]
    assert got["grad_err"] > 1.5 * limit["grad"], got


# ---- the chip's share tied to the model ------------------------------------------


@pytest.mark.compiles_a_model
def test_sixteen_shares_add_up_to_the_whole_layer():
    """16 chips, 4 of 64 experts each (``experts_held`` / ``first_expert``),
    the gated shared expert added by the first alone: the parts add up to
    what the uncut reference gives for the whole expert layer, every token's
    weights summing to one over the chips; each share's pair counts add up to
    every pair, none dropped, none counted twice."""
    from elasticdl_tpu.layers.moe import MoEMLP
    from elasticdl_tpu.telemetry import router_load

    experts, held, per_token, width = 64, 4, 10, 16
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(1, 16, 32), jnp.float32)

    def matrix(*shape):
        return jnp.asarray(rng.randn(*shape) * 0.2, jnp.float32)

    shared = {
        "shared_gate": {"kernel": matrix(32, width)},
        "shared_up": {"kernel": matrix(32, width)},
        "shared_down": {"kernel": matrix(width, 32)},
        "shared_expert_gate": {"kernel": matrix(32, 1)},
    }
    whole = {
        "router": {"kernel": jnp.asarray(rng.randn(32, experts) * 0.5, jnp.float32)},
        "w_gate": matrix(experts, 32, width), "w_up": matrix(experts, 32, width),
        "w_down": matrix(experts, width, 32), **shared,
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
        adds_shared = chip == 0
        layer = MoEMLP(
            num_experts=experts, experts_per_token=per_token, expert_width=width,
            norm_topk_prob=True, scoring="softmax", expert_kind="swiglu",
            shared_width=width if adds_shared else 0, shared_gated=adds_shared,
            experts_held=held, first_expert=first, aux_loss_weight=0.0,
            z_loss_weight=0.0,
        )
        params = {
            "router": whole["router"], **(shared if adds_shared else {}),
            **{k: whole[k][first:first + held] for k in ("w_gate", "w_up", "w_down")},
        }
        y, sown = layer.apply(
            {"params": params}, x, mutable=["losses", router_load.ROUTER_STATS],
        )
        if chip in (0, 5):  # the reference given the same share
            module.FIRST_EXPERT = first
            module.SHARED_EXPERT_SHARE = float(adds_shared)
            np.testing.assert_allclose(
                np.asarray(y), np.asarray(module.experts(x, {**params, **shared})),
                rtol=2e-5, atol=2e-6,
            )
        total = total + y
        load = router_load.read(sown)
        assert load["dropped_pairs"] == 0
        assert load["held_pairs"] + load["absent_pairs"] == load["pairs"]
        pairs_held += load["held_pairs"]
        pairs = load["pairs"]
    assert pairs_held == pairs == 16 * per_token
    np.testing.assert_allclose(total, want, rtol=2e-5, atol=2e-6)


# ---- arithmetic -----------------------------------------------------------------


def test_flops_come_from_the_published_shapes_counted_by_hand():
    cell = manifest_lib.Cell(repo_manifest(), CELL)
    parts = cell.flops_per_record()
    seq = 16384
    by_hand = {
        # q k v z, b a, the taps, the output projection; three layers
        "delta_projections": 3 * (
            2048 * 12288 + 2048 * 64 + 4 * 8192 + 4096 * 2048
        ),
        "delta_rule": 3 * 3 * 32 * 128 * 128,
        "attention_projections": 3 * 2048 * 4096 + 2 * 2048 * 512,
        "experts": 4 * 10 * (32 / 512) * 3 * 2048 * 512,
        "shared_expert": 4 * (3 * 2048 * 512 + 2048),
        "router": 4 * 2048 * 512,
        "head": 2048 * 18992,
    }
    for name, macs in by_hand.items():
        assert parts[name] == pytest.approx(6.0 * seq * macs), name
    pairs = seq * (seq + 1) // 2
    assert parts["causal_attention"] == pytest.approx(6.0 * 16 * 2 * 256 * pairs)
    assert parts["train"] == pytest.approx(sum(
        v for k, v in parts.items() if k != "train"
    ))
    assert parts["train"] / 1e12 == pytest.approx(25.93, abs=0.01)
    share = {k: v / parts["train"] for k, v in parts.items()}
    assert share["delta_projections"] + share["delta_rule"] == pytest.approx(0.401, abs=2e-3)
    assert share["causal_attention"] == pytest.approx(0.254, abs=2e-3)
    assert share["head"] == pytest.approx(0.147, abs=2e-3)


def test_the_kernels_operations_and_bytes_counted_by_hand():
    """A chunk of 128 of the layer's 16 + 32 heads of 128: the forward's
    products and the backward's own, the solve as the twelve products of the
    two-level form, once each; the least bytes with the float32 chunk states;
    the compute side bounds both calls."""
    from perf import gdn_rooflines

    spec = manifest_lib.Cell(repo_manifest(), CELL).config["flops"]
    assert [gdn_rooflines.solve_products(c) for c in (16, 32, 64, 128)] == [6, 8, 10, 12]
    c, d = 128, 128
    forward = 16 * 2 * c * c * d + 32 * (
        12 * c**3 + 2 * c * c * d + 2 * c * d * d + c * c * d + c * d * d
    )
    backward = 32 * (10 * c * c * d + 6 * c * d * d + 2 * c**3)
    assert gdn_rooflines.chunk_macs("gdn_fwd", spec) == forward
    assert gdn_rooflines.chunk_macs("gdn_bwd", spec) == backward
    assert gdn_rooflines.kernel_flops("gdn_fwd", 16384, spec) == 2.0 * 128 * forward
    tokens = 16384
    states = 128 * 32 * d * d * 4
    assert gdn_rooflines.kernel_bytes("gdn_fwd", tokens, spec) == (
        (2 * 2048 + 2 * 4096) * tokens * 2 + 2 * tokens * 32 * 4 + states
    )
    assert gdn_rooflines.kernel_bytes("gdn_bwd", tokens, spec) == (
        (4 * 2048 + 4 * 4096) * tokens * 2 + 4 * tokens * 32 * 4 + states
    )
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    for kernel in gdn_rooflines.KERNELS:
        least = gdn_rooflines.least_seconds(kernel, tokens, spec, peaks)
        # at a chunk of 128 the solve's twelve products make the operations
        # the longer side (at 64 the float32 chunk states made the bytes)
        assert least["compute_bound"]
        assert least["least_s"] == least["compute_s"] > least["memory_s"]


def test_parameters_of_the_cut_are_the_files_count():
    """The model ``run.model_params`` builds has the 625,667,136 parameters
    ``reduced_why`` counts (shapes alone: nothing is allocated)."""
    from elasticdl_tpu.models import long_seq_transformer as zoo

    config = manifest_lib.Cell(repo_manifest(), CELL).config
    model = zoo.custom_model(**config["run"]["model_params"])
    shapes = jax.eval_shape(
        lambda: model.init(
            jax.random.PRNGKey(0), {"tokens": jnp.zeros((1, 128), jnp.int32)}
        )
    )
    count = lambda tree: sum(  # noqa: E731
        int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(tree)
    )
    params = shapes["params"]
    gdn = params["block_0"]["gdn"]
    assert gdn["in_proj_qkvz"]["kernel"].shape == (2048, 12288)
    assert gdn["in_proj_ba"]["kernel"].shape == (2048, 64)
    assert gdn["conv_kernel"].shape == (4, 8192)
    assert gdn["A_log"].shape == gdn["dt_bias"].shape == (32,)
    assert gdn["norm_scale"].shape == (128,)
    assert gdn["out_proj"]["kernel"].shape == (4096, 2048)
    attn = params["block_6"]["attn"]
    assert attn["query"]["kernel"].shape == attn["gate"]["kernel"].shape == (2048, 16, 256)
    assert attn["key"]["kernel"].shape == attn["value"]["kernel"].shape == (2048, 2, 256)
    assert attn["q_norm"]["scale"].shape == attn["k_norm"]["scale"].shape == (256,)
    moe = params["block_1"]["moe"]
    assert moe["router"]["kernel"].shape == (2048, 512)
    assert moe["w_gate"].shape == moe["w_up"].shape == (32, 2048, 512)
    assert moe["shared_expert_gate"]["kernel"].shape == (2048, 1)
    assert [count(params[f"block_{i}"]) for i in (0, 2, 4)] == [33_720_512] * 3
    assert count(params["block_6"]) == 27_265_536
    for layer in (1, 3, 5, 7):
        assert count(params[f"block_{layer}"]) == 104_861_696
        assert count(params[f"block_{layer}"]) - 32 * 3_145_728 == 4_198_400
    assert (
        count(params["tok_embed"]) + count(params["lm_head"]) + count(params["RMSNorm_0"])
        == 77_793_280
    )
    assert count(params) == 625_667_136
    assert "625,667,136" in config["reduced_why"]
    assert set(shapes["router_stats"]) == {"block_1", "block_3", "block_5", "block_7"}
    assert set(shapes["delta_state"]) == {"block_0", "block_2", "block_4"}


# ---- the cell's own readers ----------------------------------------------------


def synthetic_run(**kernels):
    cell = manifest_lib.Cell(repo_manifest(), CELL)
    return {
        "cell": cell,
        "trace": {
            "busy_s": 4.0,
            "op_self_s": {
                "flash_fwd.4": 0.30, "flash_dq.5": 0.20, "flash_dkv.6": 0.25,
                "expert_gmm_fwd.7": 0.02, "expert_gmm_dx.8": 0.03,
                "expert_gmm_dw.9": 0.05, "fusion.10": 2.65, **kernels,
            },
            "details": {},
        },
        "traced_steps": 8,
        "flops_per_step_chip": cell.flops_per_record(),
        "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
    }


def test_the_delta_readers_read_the_kernels_by_their_names():
    from perf import gdn_rooflines

    cell = manifest_lib.Cell(repo_manifest(), CELL)
    run = synthetic_run(**{"gdn_fwd.1": 0.30, "gdn_fwd.2": 0.10, "gdn_bwd.3": 0.60})
    spec, peaks = cell.config["flops"], run["peaks"]
    for kernel, seconds in (("gdn_fwd", 0.40), ("gdn_bwd", 0.60)):
        least = gdn_rooflines.least_seconds(kernel, 16384, spec, peaks)["least_s"]
        # three layers a step, eight steps; the recomputed call's time is in
        # the divisor and its work is not in the dividend
        want = 100.0 * 8 * 3 * least / seconds
        name = f"delta_{kernel[4:]}_roofline.gdn"
        assert cell.reader(name)(run) == pytest.approx(want)
        assert 0 < want < 100
    assert cell.reader("delta_rule_time_share.gdn")(run) == pytest.approx(25.0)
    assert cell.reader("expert_gmm_time_share.gdn")(run) == pytest.approx(2.5)
    assert cell.reader("flash_time_share.lm")(run) == pytest.approx(18.75)


def test_the_readers_hand_back_none_on_a_run_without_the_kernels():
    """The parent's program under this PR's benchmark files: no kernel, no
    scope, no counter; every ``.gdn`` reader hands back None, never 0, and
    raises nothing (ledger, PR 60: ``benchmark_breaks_parent``)."""
    cell = manifest_lib.Cell(repo_manifest(), CELL)
    run = synthetic_run()
    untraced = {**run, "trace": None}
    for name in (
        "delta_rule_time_share.gdn", "delta_fwd_roofline.gdn",
        "delta_bwd_roofline.gdn", "linear_attention_operator_share.scope_gdn",
        "state_decay_mean.gdn",
    ):
        assert cell.reader(name)(run) is None, name
        assert cell.reader(name)(untraced) is None, name
    # a configuration without such layers reads nothing even with a kernel
    # of that name on the line
    other = synthetic_run(**{"gdn_fwd.1": 0.3})
    other["cell"] = manifest_lib.Cell(repo_manifest(), "mellum2_seq16384")
    assert cell.reader("delta_fwd_roofline.gdn")(other) is None


def test_the_state_counter_is_read_from_the_train_state():
    from elasticdl_tpu.telemetry import router_load

    assert router_load.read_delta_state({}) is None
    sown = {
        router_load.DELTA_STATE: {
            f"block_{i}": {"gdn": {"decay_mean": decay, "beta_mean": 0.5}}
            for i, decay in zip((0, 2, 4), (0.9, 0.8, 0.7))
        }
    }
    read = router_load.read_delta_state(sown)
    assert read["layers"] == 3
    assert read["decay_mean"] == pytest.approx(0.8)
    assert read["beta_mean"] == pytest.approx(0.5)


def test_the_cell_lists_its_own_entries_and_the_shared_ones():
    """Its OWN entries alone are counted: the eight under ``.gdn`` /
    ``.scope_gdn``, each with the cell as its one workload; and the cell is on
    the lists ISSUE 65 names."""
    manifest = repo_manifest()
    cell = manifest_lib.Cell(manifest, CELL)
    names = [m["name"] for m in cell.metrics("per_layer")]
    own = [n for n in names if n.endswith((".gdn", ".scope_gdn"))]
    assert own == [
        "delta_rule_time_share.gdn", "delta_fwd_roofline.gdn",
        "delta_bwd_roofline.gdn", "linear_attention_operator_share.scope_gdn",
        "state_decay_mean.gdn", "held_pair_share.gdn",
        "router_load_max_over_mean.gdn", "expert_gmm_time_share.gdn",
    ]
    for metric in cell.metrics("per_layer"):
        if metric["name"] in own:
            assert metric["workloads"] == [CELL], metric["name"]
            assert metric["moves"] == "tokens_per_s_chip"
    assert {
        "step_mfu.lm", "flash_fwd_roofline.lm", "flash_dq_roofline.lm",
        "flash_dkv_roofline.lm", "flash_time_share.lm", "state_hbm_gb",
        "step_temp_hbm_gb", "residuals_at_peak_hbm_gb",
        "head_loss_at_peak_hbm_gb", "hbm_unexplained_gb", "setup_trace_s",
        "setup_lower_s", "setup_compile_s", "recompute_share.scope_lm",
    } <= set(names)
    assert not [n for n in names if n.endswith((".swa", ".conv", ".hybrid"))]
    assert {m["name"] for m in cell.metrics("end_to_end")} == {
        "tokens_per_s_chip", "peak_hbm_gb", "setup_s"
    }
    assert (cell.chips, cell.traffic_name) == (1, "seq16384")
    assert cell.traffic["records"]["seq_len"] == 16384
    assert cell.config["name"] == "qwen3_next_80b_a3b"


def test_configuration_keeps_every_published_width():
    """Every key of the catalog row under its own name, the three cuts listed
    and no other key changed, and the model's fields equal to the keys they
    come from."""
    config = manifest_lib.Cell(repo_manifest(), CELL).config
    reduced = ["num_hidden_layers", "num_experts", "vocab_size"]
    assert config["reduced"] == reduced
    entry = next(c for c in repo_manifest()["configs"] if c["name"] == config["name"])
    assert entry["reduced"] == reduced and entry["source"] == config["source"]
    for key, value in CATALOG.items():
        if key in reduced:
            assert config["published"][key] == value, key
        else:
            assert config[key] == value, key
    assert set(config["published"]) == set(reduced)
    assert (config["num_hidden_layers"], config["num_experts"]) == (4, 32)
    assert config["vocab_size"] == 151936 // 8
    params = config["run"]["model_params"]
    published = {
        "hidden_size": "embed_dim", "num_attention_heads": "num_heads",
        "num_key_value_heads": "num_kv_heads", "head_dim": "head_dim",
        "rope_theta": "rope_theta", "partial_rotary_factor": "partial_rotary_factor",
        "rms_norm_eps": "norm_eps", "num_experts_per_tok": "experts_per_token",
        "moe_intermediate_size": "expert_width", "norm_topk_prob": "norm_topk_prob",
        "shared_expert_intermediate_size": "shared_expert_width",
        "linear_num_key_heads": "linear_key_heads",
        "linear_num_value_heads": "linear_value_heads",
        "linear_key_head_dim": "linear_key_dim",
        "linear_value_head_dim": "linear_value_dim",
        "linear_conv_kernel_dim": "conv_kernel",
        "num_experts": "experts_held", "vocab_size": "vocab_size",
    }
    assert {k: config[k] for k in published} == {k: params[v] for k, v in published.items()}
    assert params["num_experts"] == config["published"]["num_experts"] == 512
    # a layer is two letters: three d to one * (full_attention_interval), then E
    assert params["layer_pattern"] == "dEdEdE*E"
    assert params["num_layers"] == 2 * config["num_hidden_layers"]
    assert config["num_hidden_layers"] == config["full_attention_interval"]
    assert (params["expert_kind"], config["hidden_act"]) == ("swiglu", "silu")
    assert params["router_scoring"] == "softmax" and params["qk_norm_per_head"]
    assert params["output_gate"] and params["shared_expert_gate"]
    assert (params["router_aux_weight"], params["router_z_weight"]) == (0.0, 0.0)
    assert (params["dtype"], params["remat_layers"]) == ("bfloat16", True)
    flops = config["flops"]
    assert (flops["linear_layers"], flops["full_layers"]) == (3, 1)
    assert flops["chunk"] == params["delta_chunk"]
    assert (flops["heads"], flops["kv_heads"], flops["head_dim"]) == (16, 2, 256)
    assert (flops["d_model"], flops["expert_width"], flops["vocab"]) == (2048, 512, 18992)
    # the reference's constants are the file's
    module = manifest_lib.Cell(repo_manifest(), CELL).module("references", "qwen3_next")
    assert (module.LINEAR_KEY_HEADS, module.LINEAR_KEY_DIM) == (16, 128)
    assert module.ROTARY_DIM == int(config["head_dim"] * config["partial_rotary_factor"]) == 64
    assert (module.ROPE_THETA, module.RMS_NORM_EPS) == (1e7, 1e-6)
    assert (module.EXPERTS_PER_TOKEN, module.NORM_TOPK_PROB) == (10, True)
    assert "16 chips share each layer" in config["deployment"]
    assert len(config["assumed"]) >= 10
    assert config["run"]["train_args"] == ["--learning_rate", "0.0000073"]
    assert any(
        "7.3e-6" in line and "DeepSeek-V3" in line and "section 4.3" in line
        for line in config["assumed"]
    )
    assert params["router_trains"] is False and module.ROUTER_TRAINS is False
    assert any("router_trains" in line for line in config["departures"])
    assert any("MTP" in line for line in config["not_built"])
    assert any("1 + w" in line for line in config["assumed"])
    for key in ("deployment", "assumed", "departures", "not_built", "reference",
                "reduced_why"):
        assert config[key], key
    assert set(config["reference"]["tolerance"]) == {"loss", "grad"}


def test_the_reference_imports_nothing_of_the_program():
    path = os.path.join(ROOT, "perf", "references", "qwen3_next.py")
    with open(path) as f:
        source = f.read()
    assert "elasticdl_tpu" not in source.split('"""', 2)[2]
    for path in ("perf/gdn_rooflines.py", "perf/flop_functions/qwen3_next.py"):
        with open(os.path.join(ROOT, path)) as f:
            source = f.read()
        # the new modules of the program are not imported: names are literals
        assert "gated_delta" not in source.split('"""', 2)[2], path


# ---- the cell's control flow on the CPU ---------------------------------------


def manifest_with_tiny_qwen3_next() -> dict:
    manifest = copy.deepcopy(manifest_with_tiny_cell())
    manifest["configs"].append({
        "name": "tiny_qwen3_next",
        "source": "none: CPU rehearsal of the harness only",
        "file": "tests/perf/configs/tiny_qwen3_next.json",
        "reduced": [],
        "why": "a delta-rule part and a gated softmax part, two expert layers with a gated shared expert at width 64: control flow only",
    })
    manifest["workloads"].append({
        "name": TINY_CELL, "config": "tiny_qwen3_next", "traffic": "tiny",
        "chips": 1, "why": "2 x 64 tokens a step on the CPU backend through the delta rule's plain form and the partial rotary head",
    })
    for metric in manifest["end_to_end"] + manifest["per_layer"]:
        if CELL in metric.get("workloads", []):
            metric["workloads"].append(TINY_CELL)
    return manifest


@pytest.mark.compiles_a_model
def test_cell_rehearsal_on_cpu(tmp_path, trace=1):
    """Two tiny layers through ``perf/run.py --rehearse-cpu`` (the traced
    run, which measures untraced first): the path driver, the stacked
    dispatch, the delta rule, the flash kernels and the expert kernels
    interpreted, the layers recomputed, the counter riding in the state."""
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(manifest_with_tiny_qwen3_next()))
    env = {
        k: v for k, v in os.environ.items()
        if k not in ("XLA_FLAGS", "JAX_PLATFORMS", "BENCH_RUN")
    }
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cache")
    done = subprocess.run(
        [
            sys.executable, os.path.join(ROOT, "perf", "run.py"),
            "--workload", TINY_CELL, "--seed", str(2**31 + 65), "--seconds", "2",
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
