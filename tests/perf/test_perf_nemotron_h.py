"""The ``nemotron_twotower_30b_a3b`` configuration's files: the plain
reference against the zoo model with nemotron_h's fields at sizes a CPU holds
(pattern ``ME*ME``), every wrong term it must catch, the chip's share tied to
the whole layer, the FLOP and roofline figures from shapes, the new readers
on hand-made runs, and the cell's control flow rehearsed on the CPU through
a test-only configuration (``configs/tiny_nemotron.json``)."""

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

from perf import manifest as manifest_lib, reference, ssd_rooflines

CELL = "nemotron_twotower_seq8192"
TINY_CELL = "tiny_nemotron_tiny"
EXPERTS, HELD = 16, 8

FIELDS = dict(
    vocab_size=64, embed_dim=64, num_heads=4, num_kv_heads=2, head_dim=32,
    num_layers=5, layer_pattern="ME*ME", norm="rmsnorm", norm_eps=1e-5,
    use_bias=False, positions="none", num_experts=EXPERTS, experts_per_token=2,
    expert_width=32, norm_topk_prob=True, router_scoring="sigmoid",
    selection_bias=True, routed_scaling=2.5, expert_kind="relu2",
    shared_expert_width=48, experts_held=HELD, first_expert=0,
    router_aux_weight=1e-4, router_z_weight=0.0, mamba_heads=4,
    mamba_head_dim=16, ssm_groups=2, ssm_state=16, conv_kernel=4, ssd_chunk=8,
)
# what the parameter tree does not carry, at this size
CONSTANTS = {"SSM_GROUPS": 2, "EXPERTS_PER_TOKEN": 2}


def shipped_reference():
    module = manifest_lib.Cell(repo_manifest(), CELL).module("references", "nemotron_h")
    for name, value in CONSTANTS.items():
        setattr(module, name, value)
    return module


def tiny_nemotron(dtype: str, **fields):
    """The zoo model, seeded parameters nudged off their init (norm scales,
    ``D`` and the biases too), and a selection bias large enough to change
    which experts are chosen."""
    from elasticdl_tpu.models import long_seq_transformer as zoo

    model = zoo.custom_model(dtype=dtype, **{**FIELDS, **fields})
    tokens = np.random.default_rng(3).integers(64, size=(2, 41)).astype(np.int32)
    features, labels = {"tokens": tokens[:, :-1]}, tokens[:, 1:]
    variables = model.init(jax.random.PRNGKey(1), features, training=False)
    leaves, tree = jax.tree_util.tree_flatten(variables["params"])
    keys = jax.random.split(jax.random.PRNGKey(7), len(leaves))
    params = tree.unflatten(
        [x + 0.05 * jax.random.normal(k, x.shape) for x, k in zip(leaves, keys)]
    )
    state = {k: v for k, v in variables.items() if k != "params"}
    bias = 0.3 * jax.random.normal(jax.random.PRNGKey(5), (EXPERTS,))
    for block in state["router_stats"].values():
        block["moe"]["selection_bias"] = bias

    def system(p):
        logits, sown = model.apply(
            {"params": p, **state}, features, training=True, mutable=list(state)
        )
        loss = zoo.loss(labels, logits)
        for leaf in jax.tree_util.tree_leaves(sown["losses"]):
            loss = loss + jnp.sum(leaf)
        return loss.astype(jnp.float32)

    return system, params, state["router_stats"], features, labels, bias


@pytest.fixture(scope="module")
def float32_system():
    """Loss and gradient of the float32 zoo model, computed once for every
    test that holds a variant of the reference against it."""
    system, params, buffers, features, labels, bias = tiny_nemotron(
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


# float32 against float32: the two differ by the order of their sums (measured
# here: loss 1e-7, gradient 9e-7).  bfloat16 activations against float32: 0.4%
# a rounding through nine roundings a layer (measured: loss 4.2e-4, gradient
# 5.5%, by block at most 8.8%).  A wrong term moves the loss or the gradient
# past the bf16 limits (below)
TOLERANCE = {"float32": (1e-5, 1e-5), "bfloat16": (2e-3, 0.09)}


@pytest.mark.compiles_a_model
def test_reference_agrees_with_the_zoo_model_in_float32(float32_system):
    loss, grads, *rest = float32_system
    got = reference_errors(shipped_reference(), loss, grads, *rest[:-1])
    assert got["loss_err"] <= 1e-5 and got["grad_err"] <= 1e-5, got
    assert set(got["by_block"]) == {
        "tok_embed", "RMSNorm_0", "lm_head", *(f"block_{i}" for i in range(5)),
    }
    assert max(got["by_block"].values()) <= 1e-5, got


@pytest.mark.compiles_a_model
def test_reference_agrees_with_the_zoo_model_in_bfloat16():
    system, params, buffers, features, labels, _ = tiny_nemotron("bfloat16")
    loss, grads = jax.jit(jax.value_and_grad(system))(params)
    got = reference_errors(
        shipped_reference(), loss, grads, params, buffers, features, labels
    )
    loss_limit, grad_limit = TOLERANCE["bfloat16"]
    assert got["loss_err"] <= loss_limit and got["grad_err"] <= grad_limit, got


def gate_after_the_norm(module):
    def norm(y, z, scale):
        parts = y.reshape(*y.shape[:-1], module.SSM_GROUPS, -1)
        parts = parts * jax.lax.rsqrt(
            jnp.mean(jnp.square(parts), -1, keepdims=True) + module.RMS_NORM_EPS
        )
        return parts.reshape(y.shape) * scale * jax.nn.silu(z)

    return {"gated_group_norm": norm}


def one_norm_group(module):
    def norm(y, z, scale):
        gated = y * jax.nn.silu(z)
        return gated * jax.lax.rsqrt(
            jnp.mean(jnp.square(gated), -1, keepdims=True) + module.RMS_NORM_EPS
        ) * scale

    return {"gated_group_norm": norm}


def bias_left_in_the_weights(module, bias):
    original = module.pair_weights
    return {"pair_weights": lambda scores, chosen: original(scores + bias, chosen)}


def scan_sees_the_next_step(module):
    original = module.selective_scan

    def scan(x, dt, a, b, c, d):
        return original(jnp.roll(x, -1, axis=1), dt, a, b, c, d)

    return {"selective_scan": scan}


def conv_sees_the_next_steps(module):
    def conv(x, kernel, bias):
        taps, steps = kernel.shape[0], x.shape[1]
        padded = jnp.pad(x, ((0, 0), (0, taps - 1), (0, 0)))
        return bias + sum(padded[:, j:j + steps] * kernel[j] for j in range(taps))

    return {"causal_conv": conv}


FAULTS = {
    "no_convolution": lambda m, b: {"causal_conv": lambda x, kernel, bias: x},
    "dt_without_softplus": lambda m, b: {"time_step": lambda dt, bias: dt + bias},
    "gate_after_the_norm": lambda m, b: gate_after_the_norm(m),
    "one_norm_group": lambda m, b: one_norm_group(m),
    "softmax_for_sigmoid": lambda m, b: {
        "score": lambda logits: jax.nn.softmax(logits, axis=-1)
    },
    "bias_left_in_the_weights": bias_left_in_the_weights,
    "bias_not_in_the_choice": lambda m, b: {
        "choose": lambda scores, bias: jax.lax.top_k(scores, m.EXPERTS_PER_TOKEN)[1]
    },
    "no_scaling_factor": lambda m, b: {"ROUTED_SCALING": 1.0},
    "weights_not_normalised": lambda m, b: {"NORM_TOPK_PROB": False},
    "relu_for_relu2": lambda m, b: {"activation": jax.nn.relu},
    "no_shared_expert": lambda m, b: {"SHARED_EXPERT": False},
    "every_expert_held": lambda m, b: {"FIRST_EXPERT": 4},
    "scan_sees_the_future": lambda m, b: scan_sees_the_next_step(m),
    "convolution_sees_the_future": lambda m, b: conv_sees_the_next_steps(m),
    "causal_mask_dropped": lambda m, b: {
        "visible": lambda rows, cols: jnp.ones((len(rows), len(cols)), bool)
    },
}


@pytest.mark.parametrize("fault", FAULTS)
@pytest.mark.compiles_a_model
def test_comparison_fails_on_wrong_mathematics(monkeypatch, float32_system, fault):
    """Each wrong term, in float32 where nothing else differs, is outside the
    bf16 limits and four orders over the float32 agreement.  (``every_expert_
    held`` is the share moved to experts 4..11: other experts' parts.)"""
    loss, grads, params, buffers, features, labels, bias = float32_system
    module = shipped_reference()
    for name, value in FAULTS[fault](module, bias).items():
        monkeypatch.setattr(module, name, value)
    got = reference_errors(module, loss, grads, params, buffers, features, labels)
    loss_limit, grad_limit = TOLERANCE["bfloat16"]
    assert not (got["loss_err"] <= loss_limit and got["grad_err"] <= grad_limit), got
    assert not got["grad_err"] <= 0.05, (fault, got)  # a NaN is not correct either


@pytest.mark.compiles_a_model
def test_control_in_fp8_fails(float32_system):
    """The reference in the program's place with its weights rounded through
    float8 (e4m3), the nearest precision below the bfloat16 the configuration
    states: not correct under the bf16 tolerance."""
    _, _, params, buffers, features, labels, _ = float32_system
    module = shipped_reference()
    rounded = jax.tree_util.tree_map(
        lambda x: x.astype(jnp.float8_e4m3fn).astype(jnp.float32), params
    )
    loss_sys, grads_sys = jax.jit(
        lambda p, f, l, b: module.loss_and_grads(p, f, l, b)
    )(rounded, features, labels, buffers)
    got = reference_errors(
        module, loss_sys, grads_sys, params, buffers, features, labels
    )
    assert got["grad_err"] > 1.5 * TOLERANCE["bfloat16"][1], got


# ---- the chip's share tied to the model ------------------------------------------


@pytest.mark.compiles_a_model
def test_sixteen_shares_of_eight_experts_add_up_to_the_whole_layer():
    """16 chips, 8 of 128 experts each (``experts_held`` / ``first_expert``),
    the shared expert counted once: the parts add up to what the uncut
    reference gives for the whole E layer; and each share's pair counts add
    up to every pair, none dropped, none counted twice."""
    from elasticdl_tpu.layers.moe import MoEMLP
    from elasticdl_tpu.telemetry import router_load

    experts, held, per_token, width, shared = 128, 8, 6, 16, 24
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(2, 40, 32), jnp.float32)
    whole = {
        "router": {"kernel": jnp.asarray(rng.randn(32, experts) * 0.5, jnp.float32)},
        "w_up": jnp.asarray(rng.randn(experts, 32, width) * 0.2, jnp.float32),
        "w_down": jnp.asarray(rng.randn(experts, width, 32) * 0.2, jnp.float32),
        "shared_up": {"kernel": jnp.asarray(rng.randn(32, shared) * 0.2, jnp.float32)},
        "shared_down": {"kernel": jnp.asarray(rng.randn(shared, 32) * 0.2, jnp.float32)},
    }
    bias = jnp.asarray(rng.randn(experts) * 0.2, jnp.float32)
    module = shipped_reference()
    module.EXPERTS_PER_TOKEN = per_token
    want, _ = module.experts(x, whole, bias)
    shared_part = module.shared_expert(x.reshape(-1, 32), whole).reshape(x.shape)

    total, pairs_held, pairs = jnp.zeros_like(x), 0, None
    for chip in range(experts // held):
        first = chip * held
        layer = MoEMLP(
            num_experts=experts, experts_per_token=per_token, expert_width=width,
            norm_topk_prob=True, scoring="sigmoid", selection_bias=True,
            routed_scaling=2.5, expert_kind="relu2", shared_width=shared,
            experts_held=held, first_expert=first, aux_loss_weight=1e-4,
            z_loss_weight=0.0,
        )
        params = {
            **whole, "w_up": whole["w_up"][first:first + held],
            "w_down": whole["w_down"][first:first + held],
        }
        stats = {"selection_bias": bias}
        y, sown = layer.apply(
            {"params": params, router_load.ROUTER_STATS: stats}, x,
            mutable=["losses", router_load.ROUTER_STATS],
        )
        total = total + (y - shared_part)
        load = router_load.read(sown)
        assert load["dropped_pairs"] == 0
        assert load["held_pairs"] + load["absent_pairs"] == load["pairs"]
        pairs_held += load["held_pairs"]
        pairs = load["pairs"]
    assert pairs_held == pairs == 2 * 40 * per_token
    np.testing.assert_allclose(total + shared_part, want, rtol=2e-5, atol=2e-6)


@pytest.mark.compiles_a_model
def test_eight_vocabulary_slices_give_the_whole_heads_columns():
    """A chip's head is rows ``[i V/8, (i+1) V/8)`` of the vocabulary: its
    logits are those columns of the whole head's, so the eight slices side by
    side are the whole head's logits (the loss over a slice is over the
    slice: a sliced vocabulary is a smaller vocabulary)."""
    import flax.linen as nn

    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(2, 8, 32), jnp.float32)
    kernel = jnp.asarray(rng.randn(32, 128), jnp.float32)
    whole = x @ kernel
    slices = [
        nn.Dense(16, use_bias=False).apply(
            {"params": {"kernel": kernel[:, i * 16:(i + 1) * 16]}}, x
        )
        for i in range(8)
    ]
    np.testing.assert_allclose(
        jnp.concatenate(slices, -1), whole, rtol=1e-5, atol=1e-5
    )
    module = shipped_reference()
    labels = jnp.asarray(rng.randint(0, 16, (2, 8)), jnp.int32)
    sliced = module.next_token_loss(x, {"kernel": kernel[:, :16]}, labels)
    picked = jnp.take_along_axis(whole[..., :16], labels[..., None], -1)[..., 0]
    want = jnp.mean(jax.nn.logsumexp(whole[..., :16], -1) - picked)
    np.testing.assert_allclose(sliced, want, rtol=1e-5)


# ---- arithmetic -----------------------------------------------------------------


@pytest.mark.compiles_a_model
def test_flops_and_parameters_come_from_the_published_shapes():
    cell = manifest_lib.Cell(repo_manifest(), CELL)
    per_token = {k: v / 8192 for k, v in cell.flops_per_record().items()}
    d = 2688
    assert per_token["mamba_projections"] == 6 * 4 * (
        d * (4096 + 6144 + 64) + 4 * 6144 + 4096 * d
    )
    assert per_token["ssd"] == 6 * 4 * (8 * 128 * 128 + 64 * 64 * (128 + 256))
    assert per_token["attention_projections"] == 6 * d * (2 * 4096 + 2 * 256)
    assert per_token["causal_attention"] == 6 * 8192 * 4096
    assert per_token["shared_expert"] == 6 * 4 * 2 * d * 3712
    assert per_token["experts"] == 6 * 4 * 6 * 8 / 128 * 2 * d * 1856
    assert per_token["router"] == 6 * 4 * d * 128
    assert per_token["head"] == 6 * d * 16384
    assert per_token["train"] == pytest.approx(2.15e9, rel=5e-3)
    step = cell.flops_per_record()  # one sequence a step
    assert step["train"] == pytest.approx(17.6e12, rel=5e-3)
    share = {k: v / step["train"] for k, v in step.items()}
    assert share["ssd"] == pytest.approx(0.019, abs=0.001)
    assert share["mamba_projections"] + share["ssd"] == pytest.approx(0.45, abs=0.01)
    assert share["shared_expert"] == pytest.approx(0.22, abs=0.01)
    assert share["experts"] == pytest.approx(0.04, abs=0.005)
    assert share["head"] == pytest.approx(0.12, abs=0.01)
    # parameters, from the same shapes
    mamba = d * (4096 + 6144 + 64) + 4 * 6144 + 6144 + 3 * 64 + 4096 + 4096 * d + d
    attention = d * (4096 + 256 + 256) + 4096 * d + d
    outside, expert = d * 128 + 2 * d * 3712 + d, 2 * d * 1856
    assert (mamba, attention, outside, expert) == (
        38_744_896, 23_399_040, 20_302_464, 9_977_856
    )
    total = 4 * mamba + attention + 4 * (outside + 8 * expert) + 2 * 16384 * d + d
    assert total == 666_962_944
    # ... and what the zoo model builds from the configuration's fields
    from elasticdl_tpu.models import long_seq_transformer as zoo

    model = zoo.custom_model(**cell.config["run"]["model_params"])
    shapes = jax.eval_shape(
        lambda: model.init(
            jax.random.PRNGKey(0), {"tokens": jnp.zeros((1, 128), jnp.int32)}
        )
    )
    built = sum(int(np.prod(s.shape)) for s in jax.tree_util.tree_leaves(shapes["params"]))
    assert built == total
    assert [sorted(shapes["params"][f"block_{i}"])[-1] for i in range(9)] == [
        {"M": "mamba", "E": "moe", "*": "attn"}[kind] for kind in "MEMEM*EME"
    ]


def test_scan_kernels_sit_under_the_ridge_at_the_cells_shapes():
    from perf.peaks import peaks_for

    peaks = peaks_for("TPU v5 lite")
    spec = manifest_lib.Cell(repo_manifest(), CELL).config["flops"]
    forward = ssd_rooflines.kernel_flops("ssd_fwd", 8192, spec)
    backward = ssd_rooflines.kernel_flops("ssd_bwd", 8192, spec)
    # the two kernels of the four layers are the step's "ssd" part
    cell = manifest_lib.Cell(repo_manifest(), CELL)
    assert 4 * (forward + backward) == pytest.approx(cell.flops_per_record()["ssd"])
    assert backward == 2 * forward == pytest.approx(55.8e9, rel=1e-2)
    moved = ssd_rooflines.kernel_bytes("ssd_fwd", 8192, spec)
    # x and y 64 MB each in bf16, B and C 16 MB each, the states 128 MiB
    assert moved == 2 * 2**26 + 2 * 2**24 + 8192 * 64 * 4 + 64 * 64 * 128 * 64 * 4
    ridge = peaks["bf16_flops_per_s"] / peaks["hbm_bytes_per_s"]
    for kernel in ("ssd_fwd", "ssd_bwd"):
        least = ssd_rooflines.least_seconds(kernel, 8192, spec, peaks)
        intensity = ssd_rooflines.kernel_flops(kernel, 8192, spec) / (
            ssd_rooflines.kernel_bytes(kernel, 8192, spec)
        )
        assert 50 < intensity < ridge and not least["compute_bound"]
        assert least["least_s"] == least["memory_s"] > least["compute_s"]


# ---- the readers ----------------------------------------------------------------


class HandMadeCell:
    config = {"flops": {
        "pattern": "MEM", "chunk": 128, "ssm_state": 128, "ssm_groups": 8,
        "mamba_heads": 64, "mamba_head_dim": 64,
    }}
    traffic = {"batch_per_chip": 1, "records": {"seq_len": 8192}}


def hand_made_run():
    return {
        "cell": HandMadeCell,
        "trace": {
            "busy_s": 2.0,
            "op_self_s": {
                "ssd_fwd.1": 0.02, "ssd_fwd.2": 0.02, "ssd_bwd.3": 0.06,
                "flash_fwd.5": 0.30, "fusion.6": 1.60,
            },
            "details": {},
        },
        "traced_steps": 10,
        "flops_per_step_chip": {"train": 9e12},
        "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
    }


def test_scan_readers_on_a_hand_made_run():
    cell = manifest_lib.Cell(repo_manifest(), CELL)
    run = hand_made_run()
    assert cell.reader("ssd_time_share.hybrid")(run) == pytest.approx(5.0)
    spec, peaks = HandMadeCell.config["flops"], run["peaks"]
    least = {
        k: ssd_rooflines.least_seconds(k, 8192, spec, peaks)["least_s"]
        for k in ("ssd_fwd", "ssd_bwd")
    }
    # 2 layers x 10 steps = 20 calls of each kernel
    assert cell.reader("ssd_fwd_roofline.hybrid")(run) == pytest.approx(
        100 * 20 * least["ssd_fwd"] / 0.04
    )
    assert cell.reader("ssd_bwd_roofline.hybrid")(run) == pytest.approx(
        100 * 20 * least["ssd_bwd"] / 0.06
    )
    assert cell.reader("ssd_roofline.hybrid")(run) == pytest.approx(
        100 * 20 * (least["ssd_fwd"] + least["ssd_bwd"]) / 0.10
    )
    assert 0 < cell.reader("ssd_roofline.hybrid")(run) < 100
    for name in (
        "ssd_time_share.hybrid", "ssd_roofline.hybrid",
        "ssd_fwd_roofline.hybrid", "ssd_bwd_roofline.hybrid",
    ):
        read = cell.reader(name)
        # nothing to read: no trace, no scan kernel on the op line (the parent
        # commit), a configuration without Mamba-2 layers
        assert read({**hand_made_run(), "trace": None}) is None
        no_kernel = hand_made_run()
        no_kernel["trace"]["op_self_s"] = {"flash_fwd.5": 0.3, "fusion.6": 1.2}
        assert read(no_kernel) is None
        if "roofline" in name:
            dense = hand_made_run()
            dense["cell"] = type("Dense", (), {
                "config": {"flops": {"function": "transformer_lm"}},
                "traffic": HandMadeCell.traffic,
            })
            assert read(dense) is None


def test_held_pair_share_reader_reads_the_programs_counter(monkeypatch):
    from elasticdl_tpu.telemetry import router_load

    read = manifest_lib.Cell(repo_manifest(), CELL).reader("held_pair_share.hybrid")
    monkeypatch.setattr(router_load, "_watched", None)
    assert read({}) is None  # no trainer, or a model without experts
    load = {"pairs": 4000, "held_pairs": 250, "absent_pairs": 3750, "dropped_pairs": 0}
    monkeypatch.setattr(router_load, "read", lambda: load)
    assert read({}) == 6.25
    monkeypatch.setattr(router_load, "read", lambda: {**load, "dropped_pairs": 3})
    with pytest.raises(RuntimeError, match="dropped"):
        read({})
    # a program whose counter does not tell held from absent (the parent
    # commit's), or without the counter: nothing, no error
    monkeypatch.setattr(
        router_load, "read", lambda: {"pairs": 4000, "dropped_pairs": 0}
    )
    assert read({}) is None
    import elasticdl_tpu.telemetry as telemetry

    monkeypatch.delattr(telemetry, "router_load")
    monkeypatch.setitem(sys.modules, "elasticdl_tpu.telemetry.router_load", None)
    assert read({}) is None


def test_cell_reports_the_lm_metrics_it_can_and_its_own():
    manifest = repo_manifest()
    cell = manifest_lib.Cell(manifest, CELL)
    names = {m["name"] for m in cell.metrics("per_layer")}
    lm_metrics = {m["name"] for m in manifest["per_layer"] if m["name"].endswith(".lm")}
    # what the cell reports today at least: it may join the expert kernels'
    # and the router's lists (they would read right here, PERF.md section 7),
    # and a later PR's ``.lm`` entry need not list it; one chip has no collective
    assert lm_metrics - names >= {"collective_exposed_share.lm"}
    assert names >= {
        "input_wait_share.lm", "dispatch_ms.lm", "step_device_ms.lm", "step_mfu.lm",
        "flash_time_share.lm", "flash_roofline.lm", "flash_fwd_roofline.lm",
        "flash_dq_roofline.lm", "flash_dkv_roofline.lm", "bookkeeping_ms.lm",
        "assemble_ms.lm", "place_ms.lm", "enqueue_ms.lm", "fetch_wait_ms.lm",
        "producer_batch_ms.lm", "producer_busy_share.lm",
    }
    assert {"setup_trace_s", "setup_lower_s", "setup_compile_s"} <= names
    own = [m for m in cell.metrics("per_layer") if m["name"].endswith(".hybrid")]
    assert {m["name"] for m in own} >= {
        "ssd_time_share.hybrid", "ssd_roofline.hybrid", "ssd_fwd_roofline.hybrid",
        "ssd_bwd_roofline.hybrid", "held_pair_share.hybrid",
    }
    assert all(m["workloads"][:1] == [CELL] for m in own)
    assert {m["layer"] for m in own} >= {
        "state-space (layers/mamba.py, ops/ssd.py)",
        "experts (layers/moe.py, ops/grouped_matmul.py)",
    }
    assert {m["name"] for m in cell.metrics("end_to_end")} == {
        "tokens_per_s_chip", "peak_hbm_gb", "setup_s"
    }
    assert (cell.chips, cell.traffic_name) == (1, "seq8192")


def test_configuration_keeps_every_published_width():
    """Every number of the catalog row under its own key, the three cuts
    listed, and the model's fields equal to the keys they come from."""
    config = manifest_lib.Cell(repo_manifest(), CELL).config
    assert config["reduced"] == [
        "num_hidden_layers", "hybrid_override_pattern", "n_routed_experts", "vocab_size"
    ]
    assert config["published"] == {
        "num_hidden_layers": 52, "n_routed_experts": 128, "vocab_size": 131072,
        "hybrid_override_pattern":
            "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME",
    }
    pattern = config["hybrid_override_pattern"]
    assert config["published"]["hybrid_override_pattern"].startswith(pattern)
    assert len(pattern) == config["num_hidden_layers"] == 9
    params = config["run"]["model_params"]
    published = {
        "hidden_size": "embed_dim", "num_attention_heads": "num_heads",
        "num_key_value_heads": "num_kv_heads", "head_dim": "head_dim",
        "num_hidden_layers": "num_layers", "hybrid_override_pattern": "layer_pattern",
        "norm_eps": "norm_eps", "num_experts_per_tok": "experts_per_token",
        "moe_intermediate_size": "expert_width", "norm_topk_prob": "norm_topk_prob",
        "routed_scaling_factor": "routed_scaling", "n_routed_experts": "experts_held",
        "moe_shared_expert_intermediate_size": "shared_expert_width",
        "mamba_num_heads": "mamba_heads", "mamba_head_dim": "mamba_head_dim",
        "n_groups": "ssm_groups", "ssm_state_size": "ssm_state",
        "conv_kernel": "conv_kernel", "chunk_size": "ssd_chunk",
        "vocab_size": "vocab_size",
    }
    assert {k: config[k] for k in published} == {k: params[v] for k, v in published.items()}
    assert params["num_experts"] == config["published"]["n_routed_experts"]
    assert (params["expert_kind"], config["mlp_hidden_act"]) == ("relu2", "relu2")
    assert config["use_bias"] is params["use_bias"] is False
    for key in ("deployment", "assumed", "departures", "not_built", "reference",
                "reduced_why"):
        assert config[key], key


# ---- the cell's control flow on the CPU ---------------------------------------


def manifest_with_tiny_nemotron() -> dict:
    manifest = copy.deepcopy(manifest_with_tiny_cell())
    manifest["configs"].append({
        "name": "tiny_nemotron",
        "source": "none: CPU rehearsal of the harness only",
        "file": "tests/perf/configs/tiny_nemotron.json",
        "reduced": [],
        "why": "nemotron_h's three kinds of layer at width 64, pattern ME*ME: control flow only",
    })
    manifest["workloads"].append({
        "name": TINY_CELL, "config": "tiny_nemotron", "traffic": "tiny",
        "chips": 1, "why": "2 x 64 tokens a step on the CPU backend through the hybrid path",
    })
    for metric in manifest["end_to_end"] + manifest["per_layer"]:
        if CELL in metric.get("workloads", []):
            metric["workloads"].append(TINY_CELL)
    return manifest


@pytest.mark.compiles_a_model
def test_cell_rehearsal_on_cpu(tmp_path, trace=1):
    """Five tiny layers of the hybrid stack through ``perf/run.py
    --rehearse-cpu`` (the traced run, which measures untraced first): the path
    driver, the stacked dispatch, the scan, flash
    and expert kernels interpreted, the layers recomputed, the selection bias
    riding in the state."""
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(manifest_with_tiny_nemotron()))
    env = {
        k: v for k, v in os.environ.items()
        if k not in ("XLA_FLAGS", "JAX_PLATFORMS", "BENCH_RUN")
    }
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cache")
    done = subprocess.run(
        [
            sys.executable, os.path.join(ROOT, "perf", "run.py"),
            "--workload", TINY_CELL, "--seed", str(2**31 + 32), "--seconds", "2",
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
