"""The plain references against the zoo models they describe, on seeded
random weights at sizes a CPU holds: loss and gradient, through
``perf/reference.py``'s own error arithmetic.  The chip's comparison at full
width is ``perf/run.py --trace 1``'s (``reference_agrees``); its tolerances
sit in the configuration files and were found there.  The tolerances here are
for these sizes and say why beside each."""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from perf_testlib import (
    ROOT,
    TINY_CELL,
    TINY_RESIDENT_CELL,
    manifest_with_tiny_cell,
    repo_manifest,
)

from perf import manifest as manifest_lib, reference

REFERENCES = os.path.join(ROOT, "perf", "references")


def load(cell: str, module: str):
    return manifest_lib.Cell(manifest_with_tiny_cell(), cell).module(
        "references", module
    )


def perturbed(params, scale: float = 0.05):
    """Seeded noise on every leaf: biases and scales leave their zeros and
    ones, so a reference that drops one of them is caught."""
    leaves, tree = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(7), len(leaves))
    return tree.unflatten(
        [x + scale * jax.random.normal(k, x.shape) for x, k in zip(leaves, keys)]
    )


def compared(system, module, params, features, labels) -> dict:
    loss_sys, grads_sys = jax.jit(jax.value_and_grad(system))(params)
    loss_ref, grads_ref = jax.jit(module.loss_and_grads)(params, features, labels)
    got = jax.device_get(reference.errors(loss_sys, grads_sys, loss_ref, grads_ref))
    assert jax.tree_util.tree_structure(grads_ref) == jax.tree_util.tree_structure(params)
    return got


# ---- the LM -----------------------------------------------------------------


def tiny_lm(dtype: str, seq: int = 64):
    from elasticdl_tpu.models import long_seq_transformer as zoo

    model = zoo.custom_model(
        vocab_size=512, embed_dim=64, num_heads=2, num_layers=2, dtype=dtype
    )
    tokens = np.random.default_rng(3).integers(512, size=(4, seq + 1)).astype(np.int32)
    features, labels = {"tokens": tokens[:, :-1]}, tokens[:, 1:]
    params = perturbed(
        model.init(jax.random.PRNGKey(1), features, training=False)["params"]
    )

    def system(p):
        logits = model.apply({"params": p}, features, training=True)
        return zoo.loss(labels, logits).astype(jnp.float32)

    return system, params, features, labels


# float32 against float32: the two differ by the order of their sums
# (measured here: loss equal, gradient 4.7e-7).  bfloat16 activations against
# float32: bf16 keeps 8 bits, so 0.4% a rounding, a few dozen roundings deep
# (measured: loss 6.5e-6, gradient 0.9%; 1.2-2.3% on trained weights in the
# rehearsal).  A wrong term is 10% or more (below)
LM_TOLERANCE = {"float32": (1e-5, 1e-5), "bfloat16": (1e-3, 0.05)}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.compiles_a_model
def test_lm_reference_agrees_with_the_zoo_model(dtype):
    system, params, features, labels = tiny_lm(dtype)
    got = compared(system, load(TINY_CELL, "transformer_lm"), params, features, labels)
    loss_limit, grad_limit = LM_TOLERANCE[dtype]
    assert got["loss_err"] <= loss_limit and got["grad_err"] <= grad_limit, got
    assert set(got["by_block"]) == {
        "tok_embed", "block_0", "block_1", "LayerNorm_0", "lm_head"
    }
    assert max(got["by_block"].values()) <= 2 * grad_limit


@pytest.mark.compiles_a_model
def test_lm_reference_in_blocks_is_the_plain_one(monkeypatch):
    """Rows of 16 at a context of 64: four blocks of attention rows and of
    the head, each recomputed in the backward pass, against the same file
    holding everything at once, as it does at a context its ``QUERY_BLOCK``
    does not divide (``tests/perf/references/plain_lm.py`` re-exports it so)."""
    _, params, features, labels = tiny_lm("float32")
    blocked, plain = load(TINY_CELL, "transformer_lm"), load(TINY_CELL, "plain_lm")
    assert blocked.block_rows(64) == 64
    monkeypatch.setattr(blocked, "QUERY_BLOCK", 16)
    assert blocked.block_rows(64) == 16
    got = compared(
        lambda p: plain.loss_and_grads(p, features, labels)[0],
        blocked, params, features, labels,
    )
    assert got["loss_err"] <= 1e-6 and got["grad_err"] <= 1e-5, got


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.compiles_a_model
def test_lm_comparison_fails_when_the_causal_mask_is_dropped(monkeypatch, dtype):
    system, params, features, labels = tiny_lm(dtype)
    module = load(TINY_CELL, "transformer_lm")
    monkeypatch.setattr(
        module, "visible", lambda rows, columns: jnp.ones((len(rows), len(columns)), bool)
    )
    got = compared(system, module, params, features, labels)
    loss_limit, grad_limit = LM_TOLERANCE[dtype]
    assert got["grad_err"] > 2 * grad_limit, got
    assert not (got["loss_err"] <= loss_limit and got["grad_err"] <= grad_limit)


@pytest.mark.compiles_a_model
def test_lm_control_in_fp8_fails(monkeypatch):
    """The contract's control at a size a test can hold: the reference put in
    the program's place with its weights rounded through float8 (e4m3), the
    nearest precision below the bfloat16 the configuration states.  It has to
    come out as not correct under the bf16 tolerance."""
    _, params, features, labels = tiny_lm("bfloat16")
    module = load(TINY_CELL, "transformer_lm")
    rounded = reference.float8_weights(params)
    assert all(
        x.dtype == jnp.float32
        and jnp.array_equal(x, x.astype(jnp.float8_e4m3fn).astype(jnp.float32))
        for x in jax.tree_util.tree_leaves(rounded)
    )
    loss_sys, grads_sys = module.loss_and_grads(rounded, features, labels)
    loss_ref, grads_ref = module.loss_and_grads(params, features, labels)
    got = jax.device_get(reference.errors(loss_sys, grads_sys, loss_ref, grads_ref))
    assert got["grad_err"] > 2 * LM_TOLERANCE["bfloat16"][1], got


# ---- ResNet-50 ----------------------------------------------------------------


def small_resnet(dtype: str, last_scale: float = 1.0):
    """The zoo ResNet-50 on 8 seeded 32x32 uint8 images, 10 classes,
    BatchNorm in training mode.  ``last_scale`` multiplies the scale of each
    block's last BatchNorm (the seeded init has 1; trained networks, and the
    zero-gamma initialisation, sit nearer 0): a better-conditioned point."""
    from elasticdl_tpu.models import imagenet_resnet50 as zoo

    model = zoo.custom_model(num_classes=10, dtype=dtype)
    rng = np.random.default_rng(3)
    features = {"image": rng.integers(0, 256, size=(8, 32, 32, 3), dtype=np.uint8)}
    labels = rng.integers(10, size=(8,)).astype(np.int32)
    variables = model.init(
        jax.random.PRNGKey(1), zoo.device_parse(features), training=False
    )
    stats = variables["batch_stats"]
    params = jax.tree_util.tree_map_with_path(
        lambda path, x: x * last_scale
        if "bn_c" in str(path) and "scale" in str(path) else x,
        variables["params"],
    )

    def system(p):
        probs, _ = model.apply(
            {"params": p, "batch_stats": stats}, zoo.device_parse(features),
            training=True, mutable=["batch_stats"],
        )
        return zoo.loss(labels, probs).astype(jnp.float32)

    return system, params, features, labels


# (loss, whole gradient, gradient of the last layer ``fc``).  This network
# amplifies rounding: at the seeded init the float32 zoo model and the
# float32 reference differ by 4-7% in the whole gradient while the loss
# agrees to 1e-4 and ``fc``'s gradient to 6e-4 (measured here and at
# 224x224; the cause is the ReLU's kink, next test but one).  So the whole
# gradient carries a loose limit in float32 and none in bfloat16 (measured
# 0.5 at last_scale 0.25, 1.4 at the init: the chip's honest reading is
# 1.2, which is why ``resnet50_imagenet.json`` names no reference and says
# ``not_compared``); here the loss and the last layer carry the comparison.
# Measured: float32 4.7e-5 / 0.044 / 6.3e-4; bfloat16 at last_scale 0.25
# 3.9e-3 / 0.51 / 0.050.  BatchNorm in inference mode is caught by the last
# layer's gradient (below)
RESNET_TOLERANCE = {
    "float32": (1e-3, 0.15, 5e-3, 1.0),
    "bfloat16": (0.02, None, 0.15, 0.25),
}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.compiles_a_model
def test_resnet_reference_agrees_with_the_zoo_model(dtype):
    loss_limit, grad_limit, fc_limit, last_scale = RESNET_TOLERANCE[dtype]
    system, params, features, labels = small_resnet(dtype, last_scale)
    got = compared(system, load("resnet50_imagenet_resident", "resnet50"),
                   params, features, labels)
    assert got["loss_err"] <= loss_limit, got
    assert got["by_block"]["fc"] <= fc_limit, got
    if grad_limit is not None:
        assert got["grad_err"] <= grad_limit, got
    assert len(got["by_block"]) == 19  # conv1, bn_conv1, 16 blocks, fc


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.compiles_a_model
def test_resnet_comparison_fails_with_batch_norm_in_inference_mode(monkeypatch, dtype):
    loss_limit, _, fc_limit, last_scale = RESNET_TOLERANCE[dtype]
    system, params, features, labels = small_resnet(dtype, last_scale)
    module = load("resnet50_imagenet_resident", "resnet50")

    def running_averages(x, p):  # the init's: mean 0, variance 1
        return x / jnp.sqrt(1.0 + module.BATCH_NORM_EPSILON) * p["scale"] + p["bias"]

    monkeypatch.setattr(module, "batch_norm", running_averages)
    got = compared(system, module, params, features, labels)
    # the last layer's gradient is wrong by far more than the limit allows
    # (the loss moves less: 0.3 in float32, 0.02 at this bfloat16 point)
    assert got["by_block"]["fc"] > 2 * fc_limit, got
    assert not (got["loss_err"] <= loss_limit and got["by_block"]["fc"] <= fc_limit)


@pytest.mark.parametrize(
    "activation,low,high",
    [("relu", 0.01, 0.5), ("gelu", 0.0, 0.005)],
)
@pytest.mark.compiles_a_model
def test_resnet_gradient_noise_is_the_relu_kink(monkeypatch, activation, low, high):
    """Why no limit holds the bfloat16 ResNet-50's gradient.  The reference
    against itself with one float32 rounding difference (BatchNorm's variance
    as E[x^2] - E[x]^2, as flax takes it): the loss agrees to 1e-5 and the
    gradient is 3-7% off (measured 0.057 here, 0.070 at 224x224 on 16
    images); with a smooth activation in the ReLU's place the same difference
    reads under 0.1% (8.5e-4 here, 4.4e-4 at 224x224).  A forward difference
    of d flips the sign of about d of the pre-activations, and a flipped
    unit's gradient changes by all of itself: sqrt(d) a layer, not d."""
    _, params, features, labels = small_resnet("float32")
    plain = load("resnet50_imagenet_resident", "resnet50")
    fast = load("resnet50_imagenet_resident", "resnet50")

    def fast_variance(x, p):
        mean = jnp.mean(x, axis=(0, 1, 2))
        var = jnp.mean(jnp.square(x), axis=(0, 1, 2)) - jnp.square(mean)
        return (x - mean) / jnp.sqrt(var + fast.BATCH_NORM_EPSILON) * p["scale"] + p["bias"]

    monkeypatch.setattr(fast, "batch_norm", fast_variance)
    if activation == "gelu":
        monkeypatch.setattr(jax.nn, "relu", lambda x: jax.nn.gelu(x, approximate=True))
    got = compared(
        lambda p: fast.loss_and_grads(p, features, labels)[0],
        plain, params, features, labels,
    )
    assert got["loss_err"] <= 1e-4, got
    assert low <= got["grad_err"] <= high, got


# ---- the seam ---------------------------------------------------------------


@pytest.mark.parametrize(
    "cell,rows",
    [
        ("gpt2s_seq1024", 2), ("gpt2s_seq8192", 1), ("gpt2s_seq1024_dp4", 4),
        (TINY_RESIDENT_CELL, 4), (TINY_CELL, 4),
    ],
)
def test_sample_is_sized_in_the_configurations_work_unit(cell, rows):
    resolved = manifest_lib.Cell(manifest_with_tiny_cell(), cell)
    assert reference.sample_rows(resolved) == rows
    assert rows % resolved.chips == 0


def test_sample_is_seeded_and_not_a_shard_of_the_job():
    cell = manifest_lib.Cell(manifest_with_tiny_cell(), TINY_CELL)
    (a, la), (b, lb) = reference.draw_sample(cell, 2**31 + 5), reference.draw_sample(cell, 2**31 + 5)
    assert (a["tokens"] == b["tokens"]).all() and (la == lb).all()
    other, _ = reference.draw_sample(cell, 6)
    assert (a["tokens"] != other["tokens"]).any()
    kind = cell.record_kind()
    for shard in range(cell.traffic["num_shards"]):
        first = kind.columns(
            np.random.default_rng([2**31 + 5, shard]), cell.traffic["records"], 4
        )
        assert (kind.batch(first)[0]["tokens"] != a["tokens"]).any()


@pytest.mark.parametrize(
    "name", sorted(n for n in os.listdir(REFERENCES) if n.endswith(".py"))
)
def test_reference_imports_nothing_of_the_program(name):
    """Plain ``jax.numpy``: no layer, kernel, model or trainer of the program,
    and no flax or optax either."""
    with open(os.path.join(REFERENCES, name)) as f:
        source = f.read()
    imported = re.findall(r"^\s*(?:from|import)\s+([\w.]+)", source, re.M)
    assert set(imported) <= {"__future__", "math", "jax", "jax.numpy"}, imported
    assert "elasticdl_tpu" not in " ".join(imported)
    assert 'default_matmul_precision("highest")' in source
    assert "pallas" not in source.replace("Pallas flash kernels", "")
    assert "departure" in source


def test_every_shipped_configuration_names_a_reference_or_says_why_not():
    """For any number of configurations: a ``reference`` group names a file
    under ``perf/references``, two limits, what they do not cover and the chip
    run they were found on; a configuration the comparison cannot hold says so
    under ``not_compared`` and ``correct`` claims nothing for it.  Never
    both, and no group is parked under another key (``reference_found``)."""
    shipped = {n for n in os.listdir(REFERENCES) if n.endswith(".py")}
    named = set()
    for entry in repo_manifest()["configs"]:
        config = manifest_lib.load_json(os.path.join(ROOT, entry["file"]))
        assert "reference_found" not in config, entry["name"]
        assert ("reference" in config) != ("not_compared" in config), entry["name"]
        if "reference" in config:
            group = config["reference"]
            assert group["module"] + ".py" in shipped
            assert all(isinstance(v, float) for v in group["tolerance"].values())
            assert set(reference.limits(group)) == {"loss", "grad"}
            assert group["does_not_cover"] and "chip" in group["why"]
            named.add(group["module"] + ".py")
        else:
            assert len(config["not_compared"]) > 80
    # a shipped reference no configuration names is the one ``not_compared``
    # explains (ResNet-50's, PERF.md section 2)
    assert shipped - named <= {"resnet50.py"}


@pytest.mark.parametrize(
    "config,module,loss,grad,buffers",
    [
        ("gpt2_small", "transformer_lm", 8e-4, 0.1, None),
        ("olmoe_1b7b", "olmoe", 2e-3, 0.1, None),
        ("nemotron_twotower_30b_a3b", "nemotron_h", 2e-3, 0.05, "router_stats"),
        ("joyai_llm_flash_48b_a3b", "joyai_llm_flash", 1e-3, 0.035, "router_stats"),
        ("keye_vl2_30b_a3b", "keye_vl2", 6e-4, 0.023, None),
    ],
)
def test_the_five_references_that_exist_decide_correct(config, module, loss, grad, buffers):
    """The limits as they were found on the chip (PERF.md section 6, PRs 26,
    27, 32, 34, 39; ``nemotron_h``'s ``grad`` found again by PR 41 against the
    genuine float8 rounding), the collection a reference's routers read their selection
    bias from, and a function that takes it where the group names one."""
    import inspect

    manifest = repo_manifest()
    entry = next(c for c in manifest["configs"] if c["name"] == config)
    group = manifest_lib.load_json(os.path.join(ROOT, entry["file"]))["reference"]
    assert group["module"] == module
    assert reference.limits(group) == {"loss": loss, "grad": grad}
    assert group.get("buffers") == buffers
    cell = next(w["name"] for w in manifest["workloads"] if w["config"] == config)
    function = manifest_lib.Cell(manifest, cell).reference().loss_and_grads
    assert len(inspect.signature(function).parameters) == (4 if buffers else 3)


class _Executor:
    """What ``compare`` asks of the layer that owns the model."""

    def __init__(self, model_state):
        self.model_state, self.released = model_state, False

    def release_optimizer_state(self):
        self.released = True

    def model_loss_and_grads(self, features, labels):
        assert self.released  # the moments leave before the gradient trees arrive
        params = {"w": jnp.ones((2,))}
        return params, self.model_state, jnp.float32(2.0), {"w": jnp.ones((2,))}


@pytest.mark.parametrize("buffers", [None, "router_stats"])
def test_the_comparison_hands_a_reference_the_buffers_its_group_names(
    monkeypatch, buffers
):
    """A group with ``"buffers": "router_stats"`` gets that collection, as the
    window left it, as the fourth positional argument; a group without the
    key is called with three, as before."""
    seen = []

    class Module:
        @staticmethod
        def loss_and_grads(params, features, labels, *rest):
            seen.append(rest)
            shift = sum(jnp.sum(x) for x in jax.tree_util.tree_leaves(rest))
            return jnp.float32(2.0) + shift, {"w": jnp.ones((2,))}

    group = {
        "module": "made_up", "sample": {"units": 1},
        "tolerance": {"loss": 1e-3, "grad": 1e-3}, "does_not_cover": ["everything"],
        "why": "a test",
    }
    if buffers:
        group["buffers"] = buffers

    class Cell:
        config = {"reference": group}
        reference = staticmethod(lambda: Module)

    monkeypatch.setattr(
        reference, "draw_sample", lambda cell, seed: ({"tokens": np.zeros((1, 4))}, np.zeros((1, 4)))
    )
    state = {
        "router_stats": {"block_1": {"moe": {"selection_bias": jnp.full((4,), 0.25)}}},
        "loss_parts": {"main": jnp.float32(7.0)},
    }
    report = reference.compare(Cell, _Executor(state), 5)
    (rest,) = seen
    if buffers:
        assert len(rest) == 1 and set(rest[0]) == {"block_1"}
        # a bias of 0.25 x 4 moved this reference's loss: it was read
        assert report["loss_ref"] == 3.0 and report["agrees"] is False
    else:
        assert rest == () and report["loss_ref"] == 2.0 and report["agrees"] is True
    assert report["grad_err"] == 0.0 and report["sample"] == {"records": 1, "seed": 5}


def test_a_limit_that_is_not_a_number_is_refused():
    """Both limits are held: a configuration cannot opt a quantity out."""

    with pytest.raises(TypeError):
        reference.limits({"tolerance": {"loss": 1e-3, "grad": None}})
    assert reference.limits({"tolerance": {"loss": 1e-3, "grad": 0.1}}) == {
        "loss": 1e-3, "grad": 0.1
    }


def test_the_comparison_keeps_a_compile_cache_of_its_own(tmp_path):
    """Its programs never enter (or evict from) the cache the step's
    programs are loaded from, and the step's cache is back afterwards."""
    step_cache = str(tmp_path / "steps")
    previous = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", step_cache)
    cap = jax.config.jax_compilation_cache_max_size
    jax.config.update("jax_compilation_cache_max_size", 192 * 2**20)
    try:
        with reference.own_compile_cache():
            assert jax.config.jax_compilation_cache_dir == reference.COMPILE_CACHE_DIR
            # a machine's cap on the step programs' cache is not this one's
            assert jax.config.jax_compilation_cache_max_size == -1
        assert jax.config.jax_compilation_cache_dir == step_cache
        assert jax.config.jax_compilation_cache_max_size == 192 * 2**20
    finally:
        jax.config.update("jax_compilation_cache_dir", previous)
        jax.config.update("jax_compilation_cache_max_size", cap)
    relative = os.path.relpath(reference.COMPILE_CACHE_DIR, ROOT)
    assert relative == os.path.join("perf", ".data", "reference_compile_cache")
