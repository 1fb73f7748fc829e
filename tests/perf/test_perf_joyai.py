"""The ``joyai_llm_flash_48b_a3b`` configuration's files: the plain reference
against the zoo model with the configuration's fields at sizes a CPU holds
(a dense layer, an expert layer and the multi-token-prediction module, a
non-zero selection bias), wrong terms it must catch, the chip's share tied
to the whole layer, the FLOP figures against a count by hand, the ``.mla``
readers on synthetic runs, and the cell's control flow rehearsed on the CPU
through a test-only configuration (``configs/tiny_joyai.json``)."""

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

CELL = "joyai_flash_seq8192"
TINY_CELL = "tiny_joyai_tiny"
EXPERTS, HELD = 16, 8

FIELDS = dict(
    vocab_size=64, embed_dim=32, num_heads=4, num_layers=4, layer_pattern="*-*E",
    norm="rmsnorm", norm_eps=1e-6, use_bias=False, positions="rope",
    rope_theta=3.2e7, rope_interleave=True, q_lora_rank=24, kv_lora_rank=16,
    qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8, mlp="swiglu",
    mlp_width=48, num_experts=EXPERTS, experts_per_token=2, expert_width=16,
    norm_topk_prob=True, router_scoring="sigmoid", selection_bias=True,
    routed_scaling=2.5, expert_kind="swiglu", shared_expert_width=16,
    experts_held=HELD, first_expert=0, router_aux_weight=0.0, router_z_weight=0.0,
    mtp_depth=1, mtp_weight=0.3,
)
# what the parameter tree does not carry, at this size
CONSTANTS = {"EXPERTS_PER_TOKEN": 2}


def shipped_reference():
    module = manifest_lib.Cell(repo_manifest(), CELL).module(
        "references", "joyai_llm_flash"
    )
    for name, value in CONSTANTS.items():
        setattr(module, name, value)
    return module


def tiny_joyai(dtype: str, **fields):
    """The zoo model, seeded parameters nudged off their init (norm scales
    too), and a selection bias large enough to change which experts are
    chosen, in the main expert layer and in the module's."""
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
    assert set(state["router_stats"]) == {"block_3", "mtp_1_block"}
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
    system, params, buffers, features, labels, bias = tiny_joyai(
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
# against float32: 0.4% a rounding, through the roundings of three attention
# parts and two heads.  A wrong term moves the loss or the gradient past the
# bf16 limits (below)
TOLERANCE = {"float32": (1e-5, 2e-5), "bfloat16": (3e-3, 0.09)}


@pytest.mark.compiles_a_model
def test_reference_agrees_with_the_zoo_model_in_float32(float32_system):
    loss, grads, *rest = float32_system
    got = reference_errors(shipped_reference(), loss, grads, *rest[:-1])
    loss_limit, grad_limit = TOLERANCE["float32"]
    assert got["loss_err"] <= loss_limit and got["grad_err"] <= grad_limit, got
    assert set(got["by_block"]) == {
        "tok_embed", "RMSNorm_0", "lm_head", *(f"block_{i}" for i in range(4)),
        "mtp_1_proj", "mtp_1_hnorm", "mtp_1_enorm", "mtp_1_norm", "mtp_1_block",
    }
    assert max(got["by_block"].values()) <= 1e-4, got


@pytest.mark.compiles_a_model
def test_reference_agrees_with_the_zoo_model_in_bfloat16():
    system, params, buffers, features, labels, _ = tiny_joyai("bfloat16")
    loss, grads = jax.jit(jax.value_and_grad(system))(params)
    got = reference_errors(
        shipped_reference(), loss, grads, params, buffers, features, labels
    )
    loss_limit, grad_limit = TOLERANCE["bfloat16"]
    assert got["loss_err"] <= loss_limit and got["grad_err"] <= grad_limit, got


def rope_on_halves(module):
    def rotate(x):  # the rotate-half convention on the rotary slice
        steps, d = x.shape[1], x.shape[-1]
        rate = module.ROPE_THETA ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
        angle = jnp.arange(steps, dtype=jnp.float32)[:, None] * rate[None, :]
        cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
        a, b = jnp.split(x, 2, axis=-1)
        return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)
    return rotate


def scale_by_the_value_width(module):
    def attention(q, k, v):
        scale = (q.shape[-1] / v.shape[-1]) ** 0.5  # 1/sqrt(d_v) in place of d_qk
        return module_causal(q * scale, k, v)
    module_causal = module.causal_attention
    return attention


def second_loss_over_t_minus_one(module):
    def parts(params, tokens, labels, buffers=None):
        main, second = original(params, tokens, labels, buffers)
        return main, second * labels.shape[1] / (labels.shape[1] - 1)
    original = module.loss_parts
    return parts


def inputs_a_token_late(module):
    def parts(params, tokens, labels, buffers=None):
        return original(params, jnp.roll(tokens, 1, axis=1), labels, buffers)
    original = module.loss_parts
    return parts


FAULTS = {
    "rope_on_halves": lambda m, bias: {"rotate_pairs": rope_on_halves(m)},
    "scale_by_the_value_width": lambda m, bias: {
        "causal_attention": scale_by_the_value_width(m)
    },
    "bias_left_out": lambda m, bias: {
        "selection_bias": lambda buffers, name, moe: jnp.zeros_like(bias)
    },
    "no_routed_scaling": lambda m, bias: {"ROUTED_SCALING": 1.0},
    "weight_of_the_second_loss": lambda m, bias: {"MTP_WEIGHT": 0.1},
    "second_loss_over_t_minus_one": lambda m, bias: {
        "loss_parts": second_loss_over_t_minus_one(m)
    },
    "every_expert_held": lambda m, bias: {"FIRST_EXPERT": 4},
    "inputs_a_token_late": lambda m, bias: {"loss_parts": inputs_a_token_late(m)},
}


@pytest.mark.parametrize("fault", FAULTS)
@pytest.mark.compiles_a_model
def test_comparison_fails_on_wrong_mathematics(monkeypatch, float32_system, fault):
    """Each wrong term, in float32 where nothing else differs, is far outside
    the float32 agreement.  (``every_expert_held`` is the share moved to
    experts 4..11: other experts' parts; ``second_loss_over_t_minus_one``
    moves the loss by lambda L / T alone, so it is held to the float32
    limits, which it passes by three orders.)"""
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
def test_sixteen_shares_of_sixteen_experts_add_up_to_the_whole_layer():
    """16 chips, 16 of 256 experts each (``experts_held`` / ``first_expert``),
    the shared expert counted once: the parts add up to what the uncut
    reference gives for the whole expert layer; and each share's pair counts
    add up to every pair, none dropped, none counted twice."""
    from elasticdl_tpu.layers.moe import MoEMLP
    from elasticdl_tpu.telemetry import router_load

    experts, held, per_token, width, shared = 256, 16, 8, 16, 16
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
            routed_scaling=2.5, expert_kind="swiglu", shared_width=shared,
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
    per_token = {k: v / 8192 for k, v in cell.flops_per_record().items()}
    d, heads, seq = 2048, 32, 8192
    # six attention parts (five layers and the module's), by hand
    projections = (
        d * 1536 + 1536 * heads * 192 + d * (512 + 64) + 512 * heads * 256
        + heads * 128 * d
    )
    assert projections == 26_345_472
    assert per_token["attention_projections"] == 6 * 6 * projections
    # half the square visible: T/2 keys a query, 192 + 128 wide, 3 x 2 FLOPs
    assert per_token["causal_attention"] == 6 * 6 * (seq // 2) * heads * (192 + 128)
    assert per_token["dense_mlp"] == 6 * 3 * d * 7168
    assert per_token["shared_expert"] == 6 * 5 * 3 * d * 768
    assert per_token["experts"] == 6 * 5 * (8 * 16 / 256) * 3 * d * 768
    assert per_token["router"] == 6 * 5 * d * 256
    assert per_token["mtp_projection"] == 6 * 2 * d * d
    assert per_token["head"] == 6 * 2 * d * 16160
    assert per_token["train"] == pytest.approx(
        sum(v for k, v in per_token.items() if k != "train")
    )
    # ISSUE 34: 27.8 T a step; attention 72% of it, the flash kernels 44%
    assert 8192 * per_token["train"] == pytest.approx(27.84e12, rel=1e-3)
    attention = per_token["attention_projections"] + per_token["causal_attention"]
    assert attention / per_token["train"] == pytest.approx(0.723, abs=2e-3)
    assert per_token["causal_attention"] / per_token["train"] == pytest.approx(
        0.444, abs=2e-3
    )


def test_parameters_of_the_cut_are_the_files_count():
    """The model ``run.model_params`` builds has the 680,439,808 parameters
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
    assert count(params["block_0"]) == 26_347_520 + 2048  # attention and its norm
    assert count(params["block_0"]) + count(params["block_1"]) == 70_391_808
    assert count(params["block_2"]) + count(params["block_3"]) == 107_091_968
    assert count(params["mtp_1_block"]) == 107_091_968
    module = sum(count(v) for k, v in params.items() if k.startswith("mtp_1"))
    assert module == 115_486_720
    assert count(params["tok_embed"]) + count(params["lm_head"]) == 66_191_360
    assert count(params) == 680_439_808
    assert "680,439,808" in config["reduced_why"]
    assert set(shapes["router_stats"]) == {
        "block_3", "block_5", "block_7", "block_9", "mtp_1_block"
    }


# ---- the readers ----------------------------------------------------------------


def synthetic_run():
    return {
        "trace": {
            "busy_s": 2.0,
            "op_self_s": {
                "expert_gmm_fwd.1": 0.02, "expert_gmm_dx.2": 0.03,
                "expert_gmm_dw.3": 0.05, "flash_fwd.5": 0.30, "fusion.6": 1.60,
            },
            "details": {},
        },
        "traced_steps": 10,
        "flops_per_step_chip": {"train": 27.8e12},
        "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
    }


def test_expert_time_share_reader_on_a_synthetic_run():
    read = manifest_lib.Cell(repo_manifest(), CELL).reader("expert_gmm_time_share.mla")
    assert read(synthetic_run()) == pytest.approx(5.0)
    assert read({**synthetic_run(), "trace": None}) is None
    no_kernel = synthetic_run()
    no_kernel["trace"]["op_self_s"] = {"flash_fwd.5": 0.3, "fusion.6": 1.2}
    assert read(no_kernel) is None  # a program without the kernels: nothing


@pytest.mark.parametrize(
    "metric,value",
    [("held_pair_share.mla", 6.25), ("router_load_max_over_mean.mla", 3.5)],
)
def test_counter_readers_read_the_programs_counter(monkeypatch, metric, value):
    from elasticdl_tpu.telemetry import router_load

    read = manifest_lib.Cell(repo_manifest(), CELL).reader(metric)
    monkeypatch.setattr(router_load, "_watched", None)
    assert read({}) is None  # no trainer, or a model without experts
    load = {
        "pairs": 4000, "held_pairs": 250, "absent_pairs": 3750, "dropped_pairs": 0,
        "max_over_mean": 3.5,
    }
    monkeypatch.setattr(router_load, "read", lambda: load)
    assert read({}) == value
    monkeypatch.setattr(router_load, "read", lambda: {**load, "dropped_pairs": 3})
    with pytest.raises(RuntimeError, match="dropped"):
        read({})
    # a program without the counter (the parent of the PR that brought it):
    # nothing, no error
    import elasticdl_tpu.telemetry as telemetry

    monkeypatch.delattr(telemetry, "router_load")
    monkeypatch.setitem(sys.modules, "elasticdl_tpu.telemetry.router_load", None)
    assert read({}) is None


def test_cell_reports_the_lm_metrics_its_sibling_reports_and_its_own():
    manifest = repo_manifest()
    cell = manifest_lib.Cell(manifest, CELL)
    sibling = manifest_lib.Cell(manifest, "nemotron_twotower_seq8192")
    names = {m["name"] for m in cell.metrics("per_layer")}
    lm = {m["name"] for m in sibling.metrics("per_layer") if m["name"].endswith(".lm")}
    # what the two cells share at least; either may join further lists
    assert len(lm) >= 16 and lm <= names
    assert {"setup_trace_s", "setup_lower_s", "setup_compile_s"} <= names
    own = [m for m in cell.metrics("per_layer") if m["name"].endswith(".mla")]
    assert {m["name"] for m in own} >= {
        "held_pair_share.mla", "router_load_max_over_mean.mla",
        "expert_gmm_time_share.mla",
    }
    assert all(m["workloads"][:1] == [CELL] for m in own)
    assert {m["layer"] for m in own} >= {"experts (layers/moe.py, ops/grouped_matmul.py)"}
    # no share of a roofline on a balanced expert count (ISSUE 34): trained
    # routers steer away from a share whose absent experts add nothing
    assert not [n for n in names if "expert" in n and "roofline" in n]
    assert {m["name"] for m in cell.metrics("end_to_end")} == {
        "tokens_per_s_chip", "peak_hbm_gb", "setup_s"
    }
    assert (cell.chips, cell.traffic_name) == (1, "seq8192")


def test_configuration_keeps_every_published_width():
    """Every number of the catalog row under its own key, the three cuts
    listed, and the model's fields equal to the keys they come from."""
    config = manifest_lib.Cell(repo_manifest(), CELL).config
    assert config["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert config["published"] == {
        "num_hidden_layers": 40, "n_routed_experts": 256, "vocab_size": 129280
    }
    assert (config["num_hidden_layers"], config["vocab_size"]) == (5, 129280 // 8)
    assert config["first_k_dense_replace"] == config["num_nextn_predict_layers"] == 1
    params = config["run"]["model_params"]
    published = {
        "hidden_size": "embed_dim", "num_attention_heads": "num_heads",
        "q_lora_rank": "q_lora_rank", "kv_lora_rank": "kv_lora_rank",
        "qk_nope_head_dim": "qk_nope_head_dim", "qk_rope_head_dim": "qk_rope_head_dim",
        "v_head_dim": "v_head_dim", "rope_interleave": "rope_interleave",
        "rope_theta": "rope_theta", "rms_norm_eps": "norm_eps",
        "intermediate_size": "mlp_width", "num_experts_per_tok": "experts_per_token",
        "moe_intermediate_size": "expert_width", "norm_topk_prob": "norm_topk_prob",
        "routed_scaling_factor": "routed_scaling", "n_routed_experts": "experts_held",
        "scoring_func": "router_scoring", "num_nextn_predict_layers": "mtp_depth",
        "vocab_size": "vocab_size",
    }
    assert {k: config[k] for k in published} == {k: params[v] for k, v in published.items()}
    assert config["qk_head_dim"] == params["qk_nope_head_dim"] + params["qk_rope_head_dim"]
    assert params["num_experts"] == config["published"]["n_routed_experts"]
    assert params["shared_expert_width"] == (
        config["n_shared_experts"] * config["moe_intermediate_size"]
    )
    # a layer is two letters: the leading dense layers, then the expert layers
    dense = config["first_k_dense_replace"]
    assert params["layer_pattern"] == "*-" * dense + "*E" * (
        config["num_hidden_layers"] - dense
    )
    assert params["num_layers"] == 2 * config["num_hidden_layers"]
    assert (params["mlp"], config["hidden_act"]) == ("swiglu", "silu")
    assert config["attention_bias"] is params["use_bias"] is False
    assert (config["topk_method"], params["selection_bias"]) == ("noaux_tc", True)
    flops = config["flops"]
    assert (flops["dense_layers"], flops["expert_layers"], flops["mtp_modules"]) == (1, 4, 1)
    for key in ("deployment", "assumed", "departures", "not_built", "reference",
                "reduced_why"):
        assert config[key], key


# ---- the cell's control flow on the CPU ---------------------------------------


def manifest_with_tiny_joyai() -> dict:
    manifest = copy.deepcopy(manifest_with_tiny_cell())
    manifest["configs"].append({
        "name": "tiny_joyai",
        "source": "none: CPU rehearsal of the harness only",
        "file": "tests/perf/configs/tiny_joyai.json",
        "reduced": [],
        "why": "latent attention, a dense and an expert layer and the module at width 64: control flow only",
    })
    manifest["workloads"].append({
        "name": TINY_CELL, "config": "tiny_joyai", "traffic": "tiny",
        "chips": 1, "why": "2 x 64 tokens a step on the CPU backend through the latent path",
    })
    for metric in manifest["end_to_end"] + manifest["per_layer"]:
        if CELL in metric.get("workloads", []):
            metric["workloads"].append(TINY_CELL)
    return manifest


@pytest.mark.compiles_a_model
def test_cell_rehearsal_on_cpu(tmp_path, trace=1):
    """Two tiny layers and the module through ``perf/run.py --rehearse-cpu``
    (the traced run, which measures untraced first): the path driver, the
    stacked dispatch, the flash kernels at two widths and the expert kernels
    interpreted, the layers recomputed, the selection bias and the two losses
    riding in the state."""
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(manifest_with_tiny_joyai()))
    env = {
        k: v for k, v in os.environ.items()
        if k not in ("XLA_FLAGS", "JAX_PLATFORMS", "BENCH_RUN")
    }
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cache")
    done = subprocess.run(
        [
            sys.executable, os.path.join(ROOT, "perf", "run.py"),
            "--workload", TINY_CELL, "--seed", str(2**31 + 34), "--seconds", "2",
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
