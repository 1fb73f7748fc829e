"""A looped model (``models/long_seq_transformer.py`` with ``loop_steps`` over
1; docs/designs/looped_layers.md) at sizes a CPU holds, in float32: what
``loop_steps=1`` builds, the shared weights' gradient as the sum over their
uses, the exit distribution, the loss by hand, the system against the plain
reference (``perf/references/ouro.py``), the step's masked loss and what it
leaves in the state, the head's gradient formed beside the logits against the
plain composition, and what a loop refuses."""

import functools
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from elasticdl_tpu.layers.attention import TransformerBlock, make_norm
from elasticdl_tpu.models import long_seq_transformer as zoo
from elasticdl_tpu.telemetry import op_scopes, router_load
from elasticdl_tpu.trainer.state import TrainState
from elasticdl_tpu.trainer.step import build_train_step, weighted_mean_loss

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
VOCAB, SEQ, WIDTH, LAYERS, PASSES = 64, 32, 64, 2, 4
FIELDS = dict(
    vocab_size=VOCAB, embed_dim=WIDTH, num_heads=2, num_layers=LAYERS,
    norm="rmsnorm", norm_eps=1e-6, use_bias=False, positions="rope",
    rope_theta=1e6, mlp="swiglu", mlp_width=96, norm_outputs=True,
)


def batch(rows=3, seed=0):
    tokens = np.random.default_rng(seed).integers(VOCAB, size=(rows, SEQ + 1))
    tokens = tokens.astype(np.int32)
    return {"tokens": tokens[:, :-1]}, tokens[:, 1:]


def seeded(model, features):
    """The model's variables with every parameter moved off its init (a
    gate at zero would hide its own terms)."""
    variables = model.init(jax.random.PRNGKey(0), features, training=False)
    leaves, tree = jax.tree_util.tree_flatten(variables["params"])
    keys = jax.random.split(jax.random.PRNGKey(7), len(leaves))
    params = tree.unflatten(
        [x + 0.05 * jax.random.normal(k, x.shape) for x, k in zip(leaves, keys)]
    )
    params["tok_embed"]["embedding"] = 0.3 * params["tok_embed"]["embedding"]
    if "exit_gate" in params:
        # (the gate's logit is its product over sqrt(width))
        params["exit_gate"]["kernel"] = 4.0 * WIDTH**0.5 * params["exit_gate"]["kernel"]
    state = {k: v for k, v in variables.items() if k != "params"}
    return params, state


def training_outputs(model, params, state, features):
    outputs, _ = model.apply(
        {"params": params, **state}, features, training=True, mutable=list(state)
    )
    return outputs


def loss_and_grads(model, params, state, features, labels):
    def loss_of(p):
        outputs = training_outputs(model, p, state, features)
        return zoo.loss(labels, outputs).astype(jnp.float32)

    return jax.jit(jax.value_and_grad(loss_of))(params)


@functools.lru_cache(maxsize=None)
def float32_system(remat=False):
    """``(params, features, labels, loss, grads)`` of the looped model, made
    once: the tests that hold it to something share it."""
    features, labels = batch()
    model = zoo.custom_model(loop_steps=PASSES, remat_layers=remat, **FIELDS)
    params, state = seeded(model, features)
    return (params, features, labels) + tuple(
        loss_and_grads(model, params, state, features, labels)
    )


@functools.lru_cache(maxsize=None)
def benchmark_tool(name):
    """A builder's tool under ``benchmarks/``, as a module."""
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(ROOT, "benchmarks", name + ".py")
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def loop_control():
    """``benchmarks/ouro_loop_control.py``: the faults of the loop alone that
    are read on the chip at the cell's own state and limits."""
    return benchmark_tool("ouro_loop_control")


def plain_reference():
    """A copy of ``perf/references/ouro.py`` of this caller's own."""
    return loop_control().plain_reference()


def relative(got, want):
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


# ---- loop_steps = 1 is the model of before ------------------------------------------


def test_one_pass_is_the_model_of_before():
    features, labels = batch()
    default = zoo.custom_model(**FIELDS)
    one = zoo.custom_model(loop_steps=1, **FIELDS)
    variables = default.init(jax.random.PRNGKey(0), features, training=False)
    assert set(variables) == {"params"}
    assert set(variables["params"]) == {
        "tok_embed", "block_0", "block_1", "RMSNorm_0", "lm_head",
    }
    again = one.init(jax.random.PRNGKey(0), features, training=False)
    assert jax.tree_util.tree_structure(again) == jax.tree_util.tree_structure(variables)
    logits = default.apply(variables, features, training=True)
    assert logits.shape == (3, SEQ, VOCAB)  # an array, no exits
    np.testing.assert_array_equal(logits, one.apply(variables, features, training=True))
    assert set(zoo.loss_parts(labels, logits)) == {"main"}
    assert zoo.loss.weighted_mean(labels, logits, None) is None


@pytest.mark.parametrize("passes", [2, 3, 4])
def test_parameters_do_not_depend_on_the_passes(passes):
    features, _ = batch()
    shapes = jax.eval_shape(
        lambda: zoo.custom_model(loop_steps=passes, **FIELDS).init(
            jax.random.PRNGKey(0), features, training=False
        )
    )
    params = shapes["params"]
    assert set(params) == {
        "tok_embed", "block_0", "block_1", "RMSNorm_0", "lm_head", "exit_gate",
    }
    assert params["exit_gate"]["kernel"].shape == (WIDTH, 1)
    count = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(params))
    one_pass = jax.eval_shape(
        lambda: zoo.custom_model(**FIELDS).init(
            jax.random.PRNGKey(0), features, training=False
        )
    )["params"]
    # the gate and its bias, whatever the passes
    assert count == WIDTH + 1 + sum(
        int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(one_pass)
    )
    assert set(shapes[router_load.LOSS_PARTS]) == set(zoo.LOOPED_PARTS)
    assert list(shapes[router_load.LOSS_OBSERVED]) == sorted(
        router_load.observed_names(passes)
    )


def test_the_gate_starts_at_one_half():
    features, _ = batch()
    model = zoo.custom_model(loop_steps=PASSES, **FIELDS)
    variables = model.init(jax.random.PRNGKey(0), features, training=False)
    gate = variables["params"]["exit_gate"]
    assert not np.any(gate["kernel"]) and not np.any(gate["bias"])
    state = {k: v for k, v in variables.items() if k != "params"}
    outputs = training_outputs(model, variables["params"], state, features)
    assert not np.any(outputs["exit_gates"])
    p = jnp.exp(zoo.exit_distribution(outputs["exit_gates"]))
    np.testing.assert_allclose(p[:, 0, 0], [0.5, 0.25, 0.125, 0.125], rtol=1e-6)


# ---- the exit distribution and the loss -------------------------------------------


@pytest.mark.parametrize("passes", [2, 3, 4, 6])
def test_exit_distribution_sums_to_one(passes):
    gates = 3.0 * jax.random.normal(jax.random.PRNGKey(passes), (passes, 5, 7))
    p = np.exp(np.asarray(zoo.exit_distribution(gates), np.float64))
    np.testing.assert_allclose(p.sum(axis=0), 1.0, rtol=1e-5)
    g = 1.0 / (1.0 + np.exp(-np.asarray(gates, np.float64)))
    stayed = np.ones_like(g[0])
    for t in range(passes - 1):
        np.testing.assert_allclose(p[t], g[t] * stayed, rtol=1e-5)
        stayed = stayed * (1.0 - g[t])
    np.testing.assert_allclose(p[-1], stayed, rtol=1e-5)  # the last gate is not read
    at_zero = np.exp(np.asarray(zoo.exit_distribution(jnp.zeros((passes, 1)))))[:, 0]
    np.testing.assert_allclose(
        at_zero, [0.5 ** (t + 1) for t in range(passes - 1)] + [0.5 ** (passes - 1)],
        rtol=1e-6,
    )


@pytest.mark.parametrize("head", ["untied", "tied"])
def test_loss_parts_equal_a_loss_written_by_hand(head):
    features, labels = batch()
    model = zoo.custom_model(
        loop_steps=PASSES, exit_entropy_weight=0.25,
        tie_embedding=head == "tied", **FIELDS,
    )
    params, state = seeded(model, features)
    assert ("lm_head" in params) == (head == "untied")
    outputs = training_outputs(model, params, state, features)
    assert outputs["exit_states"].shape == (PASSES, 3, SEQ, WIDTH)
    assert outputs["exit_gates"].shape == (PASSES, 3, SEQ)
    kernel = (
        params["tok_embed"]["embedding"].T if head == "tied"
        else params["lm_head"]["kernel"]
    )
    np.testing.assert_array_equal(outputs["head"]["kernel"], kernel)
    states = np.asarray(outputs["exit_states"], np.float64)
    g = 1.0 / (1.0 + np.exp(-np.asarray(outputs["exit_gates"], np.float64)))
    logits = states @ np.asarray(kernel, np.float64)
    log_z = np.log(np.exp(logits).sum(-1))
    picked = np.take_along_axis(
        logits, np.broadcast_to(labels, (PASSES, *labels.shape))[..., None], -1
    )[..., 0]
    cross_entropy = log_z - picked
    p = np.stack([g[0], g[1] * (1 - g[0]), g[2] * (1 - g[0]) * (1 - g[1]),
                  (1 - g[0]) * (1 - g[1]) * (1 - g[2])])
    expected = (p * cross_entropy).sum(0).mean()
    entropy = -(p * np.log(p)).sum(0).mean()
    parts = zoo.loss_parts(labels, outputs)
    assert set(parts) == {*zoo.LOOPED_PARTS, router_load.LOSS_OBSERVED}
    np.testing.assert_allclose(parts["expected_ce"], expected, rtol=2e-5)
    np.testing.assert_allclose(parts["exit_entropy"], -0.25 * entropy, rtol=2e-5)
    np.testing.assert_allclose(
        zoo.loss(labels, outputs), expected - 0.25 * entropy, rtol=2e-5
    )
    observed = parts[router_load.LOSS_OBSERVED]
    assert sorted(observed) == sorted(router_load.observed_names(PASSES))
    for t in range(PASSES):
        np.testing.assert_allclose(observed[f"ce_{t + 1}"], cross_entropy[t].mean(), rtol=2e-5)
        np.testing.assert_allclose(observed[f"exit_{t + 1}"], p[t].mean(), rtol=2e-5)
    # an evaluation forward is the last pass's logits
    np.testing.assert_allclose(
        model.apply({"params": params, **state}, features), logits[-1],
        rtol=2e-4, atol=2e-4,
    )


# ---- one set of weights, used four times --------------------------------------------


def unshared_loss(copies, params, features, labels, weight):
    """The looped model's loss with a set of block weights a pass: the
    model's own block and norm, applied a pass and a layer at a time."""
    block = TransformerBlock(
        causal=True, norm="rmsnorm", norm_eps=1e-6, norm_outputs=True,
        use_bias=False, mlp="swiglu", mlp_width=96,
        attention_fields=(("num_heads", 2), ("rope_theta", 1e6)),
    )
    norm = make_norm("rmsnorm", 1e-6, None)
    h = params["tok_embed"]["embedding"][features["tokens"]]
    states, gates = [], []
    for blocks in copies:
        for layer in range(LAYERS):
            h = block.apply({"params": blocks[f"block_{layer}"]}, h, True)
        h = norm.apply({"params": params["RMSNorm_0"]}, h)
        states.append(h)
        gate = params["exit_gate"]
        gates.append(((h @ gate["kernel"])[..., 0] + gate["bias"][0]) / WIDTH**0.5)
    return zoo.loss(labels, {
        "exit_states": jnp.stack(states), "exit_gates": jnp.stack(gates),
        "head": params["lm_head"], "exit_entropy_weight": jnp.float32(weight),
    })


@pytest.mark.parametrize("remat", [True, False])
def test_a_shared_weights_gradient_is_the_sum_over_its_four_uses(remat):
    params, features, labels, loss, grads = float32_system(remat)
    blocks = {name: params[name] for name in params if name.startswith("block_")}
    copies = [blocks] * PASSES
    loss_unshared, by_use = jax.jit(
        jax.value_and_grad(unshared_loss), static_argnums=(4,)
    )(copies, params, features, labels, 0.1)
    np.testing.assert_allclose(loss, loss_unshared, rtol=1e-5)
    summed = jax.tree_util.tree_map(lambda *uses: sum(uses), *by_use)
    for name in blocks:
        for use in by_use:  # every pass reaches every layer's weights
            assert float(optax.global_norm(use[name])) > 1e-4
        got, want = (
            jnp.concatenate([x.ravel() for x in jax.tree_util.tree_leaves(g[name])])
            for g in (grads, summed)
        )
        assert relative(got, want) < 2e-5, name
        # and is no single use's
        first = jnp.concatenate(
            [x.ravel() for x in jax.tree_util.tree_leaves(by_use[0][name])]
        )
        assert relative(got, first) > 0.1, name


@pytest.mark.parametrize("remat", [True, False])
def test_system_agrees_with_the_plain_reference(remat):
    from perf import reference

    params, features, labels, loss, grads = float32_system(remat)
    module = plain_reference()
    loss_ref, grads_ref = jax.jit(module.loss_and_grads)(params, features, labels)
    assert jax.tree_util.tree_structure(grads_ref) == jax.tree_util.tree_structure(grads)
    got = jax.device_get(reference.errors(loss, grads, loss_ref, grads_ref))
    assert got["loss_err"] < 1e-5 and got["grad_err"] < 3e-5, got
    assert set(got["by_block"]) == set(params) and "exit_gate" in params
    # every leaf, the gate's among them
    for (path, leaf), want in zip(
        jax.tree_util.tree_leaves_with_path(grads), jax.tree_util.tree_leaves(grads_ref)
    ):
        assert float(jnp.linalg.norm(want)) > 0, path
        assert relative(leaf, want) < 2e-4, jax.tree_util.keystr(path)


def test_bfloat16_system_is_near_the_reference():
    from perf import reference

    features, labels = batch()
    model = zoo.custom_model(
        loop_steps=PASSES, remat_layers=True, dtype="bfloat16", **FIELDS
    )
    params, state = seeded(model, features)
    loss, grads = loss_and_grads(model, params, state, features, labels)
    loss_ref, grads_ref = jax.jit(plain_reference().loss_and_grads)(
        params, features, labels
    )
    got = jax.device_get(reference.errors(loss, grads, loss_ref, grads_ref))
    assert got["loss_err"] < 5e-3 and got["grad_err"] < 0.15, got
    # the gate and the distribution are float32 whatever the model's dtype
    outputs = training_outputs(model, params, state, features)
    assert outputs["exit_gates"].dtype == jnp.float32
    assert outputs["exit_states"].dtype == jnp.bfloat16


def _a_gate_a_pass_too_many(module):
    def distribution(gates):
        stayed, p = 1.0, []
        for g in gates:
            p.append(g * stayed)
            stayed = stayed * (1.0 - g)
        return p

    module.exit_distribution = distribution


def _no_entropy_term(module):
    module.EXIT_ENTROPY_WEIGHT = 0.0


def _the_last_pass_alone(module):
    module.exit_distribution = lambda gates: (
        [0.0 * g for g in gates[:-1]] + [1.0 + 0.0 * gates[-1]]
    )


def _the_gate_at_the_logits_own_scale(module):
    module.exit_gate = lambda h, gate: jax.nn.sigmoid(
        (h @ gate["kernel"])[..., 0] + gate["bias"][0]
    )


WRONG = {
    **loop_control().FAULTS,
    "a_gate_a_pass_too_many": _a_gate_a_pass_too_many,
    "no_entropy_term": _no_entropy_term,
    "the_last_pass_alone": _the_last_pass_alone,
    "the_gate_at_the_logits_own_scale": _the_gate_at_the_logits_own_scale,
}


@pytest.mark.parametrize("fault", WRONG)
def test_comparison_fails_on_wrong_mathematics(fault):
    from perf import reference

    params, features, labels, loss, grads = float32_system()
    module = plain_reference()
    WRONG[fault](module)
    loss_ref, grads_ref = jax.jit(
        lambda p, f, l: module.loss_and_grads(p, f, l)
    )(params, features, labels)
    got = jax.device_get(reference.errors(loss, grads, loss_ref, grads_ref))
    assert not (got["loss_err"] <= 1e-3 and got["grad_err"] <= 3e-3), got


# ---- the step ----------------------------------------------------------------------


def test_the_step_masks_rows_and_leaves_the_passes_in_the_state():
    features, labels = batch(rows=4)
    model = zoo.custom_model(loop_steps=PASSES, remat_layers=True, **FIELDS)
    variables = model.init(jax.random.PRNGKey(0), features, training=False)
    model_state = {k: v for k, v in variables.items() if k != "params"}
    assert set(model_state) == {router_load.LOSS_PARTS, router_load.LOSS_OBSERVED}
    assert router_load.read_exits(model_state)["exit_step_mean"] == 0.0  # no step yet

    def fresh():
        return TrainState.create(
            model.apply, variables["params"], optax.sgd(0.0), model_state
        )

    step = build_train_step(zoo.loss, donate=False)
    weights = jnp.asarray([1.0, 1.0, 0.0, 1.0])
    masked, metrics = step(fresh(), features, labels, weights)
    kept = [0, 1, 3]
    only, kept_metrics = step(
        fresh(), {"tokens": features["tokens"][kept]}, labels[kept], jnp.ones(3)
    )
    np.testing.assert_allclose(metrics["loss"], kept_metrics["loss"], rtol=1e-6)
    unweighted, plain_metrics = step(fresh(), features, labels)
    all_ones, ones_metrics = step(fresh(), features, labels, jnp.ones(4))
    np.testing.assert_allclose(plain_metrics["loss"], ones_metrics["loss"], rtol=1e-6)
    # the parts add up to the loss; the gate is at one half
    parts = router_load.read_loss_parts(masked.model_state)
    assert set(parts) == set(zoo.LOOPED_PARTS)
    np.testing.assert_allclose(sum(parts.values()), metrics["loss"], rtol=1e-6)
    exits = router_load.read_exits(masked.model_state)
    np.testing.assert_allclose(
        exits["exit_distribution"], [0.5, 0.25, 0.125, 0.125], rtol=1e-6
    )
    assert exits["exit_step_mean"] == pytest.approx(1.875, rel=1e-6)
    assert len(exits["cross_entropy"]) == PASSES
    assert all(3.0 < ce < 7.0 for ce in exits["cross_entropy"])
    entropy = -sum(p * np.log(p) for p in (0.5, 0.25, 0.125, 0.125))
    assert parts["exit_entropy"] == pytest.approx(-0.1 * entropy, rel=1e-5)
    assert parts["expected_ce"] == pytest.approx(
        sum(p * ce for p, ce in zip(exits["exit_distribution"], exits["cross_entropy"])),
        rel=1e-5,
    )
    assert router_load.read_exits({}) is None
    # the state keeps its tree from one step to the next
    assert jax.tree_util.tree_structure(masked.model_state) == jax.tree_util.tree_structure(
        model_state
    )


def test_a_masked_rows_gradient_is_zero():
    features, labels = batch(rows=2)
    model = zoo.custom_model(loop_steps=PASSES, **FIELDS)
    params, state = seeded(model, features)

    def masked(p, tokens):
        outputs = training_outputs(model, p, state, {"tokens": tokens})
        parts = weighted_mean_loss(
            zoo.loss_parts, labels, outputs, jnp.asarray([1.0, 0.0])
        )
        return sum(v for k, v in parts.items() if k != router_load.LOSS_OBSERVED)

    other = np.array(features["tokens"])
    other[1] = (other[1] + 1) % VOCAB
    grad = jax.jit(jax.grad(masked))  # one program for both batches
    a, b = (grad(params, jnp.asarray(t)) for t in (features["tokens"], other))
    for x, y in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)):
        np.testing.assert_allclose(x, y, rtol=1e-5, atol=1e-7)


# ---- the head's gradient is formed where the logits are -------------------------------


def plain_parts(labels, outputs, weights=None):
    """The looped loss as a plain composition (the form up to PR 55, what
    the shipped one is compared with here and, on the chip at the cell's
    size, by ``benchmarks/ouro_loss_forms.py``, which keeps it): a pass's
    logits and their cross-entropy under ``jax.checkpoint`` inside
    ``jax.lax.map``, the rows' terms, then the step's weighted mean."""
    return benchmark_tool("ouro_loss_forms").plain_parts(labels, outputs, weights)


def both_forms(labels, outputs_of, weights):
    """``(shipped, plain)``, each the loss's parts by what is differentiated:
    through the module's own loss as the step asks it, and through
    ``plain_parts``."""
    tool = benchmark_tool("ouro_loss_forms")
    return tuple(
        lambda d, form=form: form(labels, outputs_of(d), weights)
        for form in (tool.shipped_parts, tool.plain_parts)
    )


LOOSE_VOCAB = 80  # no other dimension of these exits


def loose_exits(head, dtype=jnp.float32, rows=3):
    """``(differentiated, labels, outputs_of)``: exits with no model under
    them, the states, the gates and the head's parameters as what a gradient
    is taken by; a tied head's parameter is the embedding."""
    keys = jax.random.split(jax.random.PRNGKey(5), 5)
    labels = jax.random.randint(keys[0], (rows, SEQ), 0, LOOSE_VOCAB)
    kernel = 0.3 * jax.random.normal(keys[1], (WIDTH, LOOSE_VOCAB))
    differentiated = {
        "states": jax.random.normal(keys[2], (PASSES, rows, SEQ, WIDTH)).astype(dtype),
        "gates": 1.5 * jax.random.normal(keys[3], (PASSES, rows, SEQ)),
        "head": {
            "untied": {"kernel": kernel},
            "untied_with_bias": {
                "kernel": kernel,
                "bias": jax.random.normal(keys[4], (LOOSE_VOCAB,)),
            },
            "tied": {"embedding": kernel.T},
        }[head],
    }

    def outputs_of(d):
        return {
            "exit_states": d["states"], "exit_gates": d["gates"],
            "head": (
                {"kernel": d["head"]["embedding"].T} if head == "tied"
                else d["head"]
            ),
            "exit_entropy_weight": jnp.float32(0.1),
        }

    return differentiated, labels, outputs_of


def _total(parts):
    return benchmark_tool("ouro_loss_forms").total(parts)


MASKS = {"no_mask": None, "a_zero_row": [1.0, 0.0, 2.0]}


@pytest.mark.parametrize("mask", MASKS)
@pytest.mark.parametrize("head", ["untied", "untied_with_bias", "tied"])
def test_the_fused_loss_and_its_gradients_are_the_plain_compositions(head, mask):
    differentiated, labels, outputs_of = loose_exits(head)
    weights = None if MASKS[mask] is None else jnp.asarray(MASKS[mask])
    shipped, plain = both_forms(labels, outputs_of, weights)

    got, want = shipped(differentiated), plain(differentiated)
    assert jax.tree_util.tree_structure(got) == jax.tree_util.tree_structure(want)
    for (path, x), y in zip(
        jax.tree_util.tree_leaves_with_path(got), jax.tree_util.tree_leaves(want)
    ):
        assert x.shape == () and x.dtype == jnp.float32, path
        np.testing.assert_allclose(x, y, rtol=2e-6, err_msg=jax.tree_util.keystr(path))
    if weights is not None:
        # the step's other spelling, the loss as one number
        np.testing.assert_allclose(
            weighted_mean_loss(zoo.loss, labels, outputs_of(differentiated), weights),
            _total(want), rtol=2e-6,
        )
    # under a cotangent of 1 and under one that is not
    for scale in (1.0, 3.0):
        grads, grads_plain = (
            jax.jit(jax.grad(lambda d: scale * _total(f(d))))(differentiated)
            for f in (shipped, plain)
        )
        for (path, x), y in zip(
            jax.tree_util.tree_leaves_with_path(grads),
            jax.tree_util.tree_leaves(grads_plain),
        ):
            assert x.dtype == y.dtype and float(jnp.linalg.norm(y)) > 0
            assert relative(x, y) < 5e-6, (scale, jax.tree_util.keystr(path))
    if weights is not None:
        # the zero row: nothing of it in any gradient a row has
        assert not np.any(grads["states"][:, 1]) and not np.any(grads["gates"][:, 1])
        assert np.all(np.any(np.asarray(grads["states"][:, 2]) != 0, axis=(1, 2)))


@pytest.mark.parametrize("mask", MASKS)
def test_at_bfloat16_the_value_is_the_plain_forms_and_the_gradient_rounds_where_autodiffs_did(
    mask,
):
    """bfloat16 exits, as the cell runs them.  The loss VALUE is the plain
    form's to float32 summation order (the number a traced run's ``loss_err``
    is read from: the logits are rounded to bfloat16 where they were, the
    statistics are float32); the logits' gradient is rounded to the logits'
    dtype as autodiff rounds it, so the states' gradient is the plain form's
    bit for bit; the head's is summed over tokens and passes in float32
    where the plain form rounds a pass's to bfloat16 first, so it is no
    farther from the float32 gradient."""
    differentiated, labels, outputs_of = loose_exits("untied_with_bias", jnp.bfloat16)
    weights = None if MASKS[mask] is None else jnp.asarray(MASKS[mask])
    shipped, plain = both_forms(labels, outputs_of, weights)

    (value, grads), (value_plain, grads_plain) = (
        jax.jit(jax.value_and_grad(lambda d: _total(f(d))))(differentiated)
        for f in (shipped, plain)
    )
    assert value.dtype == value_plain.dtype == jnp.float32
    assert abs(float(value) - float(value_plain)) <= 1e-6 * abs(float(value_plain))
    assert grads["states"].dtype == jnp.bfloat16
    assert grads["head"]["kernel"].dtype == jnp.float32
    # (a gradient not rounded to the logits' dtype there reads ~1e-3)
    np.testing.assert_array_equal(
        np.asarray(grads["states"].astype(jnp.float32)),
        np.asarray(grads_plain["states"].astype(jnp.float32)),
    )
    as_float = functools.partial(jax.tree_util.tree_map, lambda x: x.astype(jnp.float32))
    grads, grads_plain = as_float(grads), as_float(grads_plain)
    assert relative(grads["gates"], grads_plain["gates"]) < 1e-5
    exact = as_float(jax.grad(lambda d: _total(plain(d)))(
        {**as_float(differentiated), "gates": differentiated["gates"]}
    ))
    for name in ("kernel", "bias"):
        fused, rounded = (
            relative(g["head"][name], exact["head"][name]) for g in (grads, grads_plain)
        )
        assert fused <= rounded * 1.05 and fused < 5e-3, (name, fused, rounded)


def _vocabulary_products(jaxpr, vocab):
    """``(in no loop, [in each loop's body])``: the ``dot_general``s of a
    jaxpr that have the vocabulary as a dimension, by the loop they are in."""
    own, loops = 0, []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            own += any(vocab in v.aval.shape for v in (*eqn.invars, *eqn.outvars))
        for inner in jax.core.jaxprs_in_params(eqn.params):
            inside, deeper = _vocabulary_products(inner, vocab)
            if eqn.primitive.name in ("scan", "while"):
                loops += [inside] + deeper if inside or deeper else []
            else:
                own, loops = own + inside, loops + deeper
    return own, loops


def test_the_heads_product_is_made_three_times_a_pass_and_never_in_the_backward_rule():
    features, labels = batch()
    model = zoo.custom_model(
        loop_steps=PASSES, **{**FIELDS, "vocab_size": LOOSE_VOCAB}
    )
    variables = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), features, training=False)
    )
    state = {k: v for k, v in variables.items() if k != "params"}

    def step_gradient(parts):
        def loss_of(p):
            return _total(parts(labels, training_outputs(model, p, state, features)))

        return jax.make_jaxpr(jax.grad(loss_of))(variables["params"]).jaxpr

    # logits, the states' gradient, the head's: one loop body, and no other
    assert _vocabulary_products(step_gradient(zoo.loss_parts), LOOSE_VOCAB) == (0, [3])
    # the plain form: the product in the forward loop, then made again with
    # its two gradients in the backward loop
    assert _vocabulary_products(step_gradient(plain_parts), LOOSE_VOCAB) == (0, [1, 3])
    # the backward rule alone, its residuals given
    differentiated, labels, outputs_of = loose_exits("untied_with_bias")
    for parts, products in ((zoo.loss_parts, (0, [])), (plain_parts, (0, [3]))):
        _, pullback = jax.vjp(
            lambda d: _total(parts(labels, outputs_of(d))), differentiated
        )
        backward = jax.make_jaxpr(pullback)(jnp.float32(1.0)).jaxpr
        assert _vocabulary_products(backward, LOOSE_VOCAB) == products, parts


def _weighted_mean_loss_of_before(loss_fn, labels, outputs, weights):
    """``trainer/step.py::weighted_mean_loss`` with no seam in it."""

    def one_row(labels_row, outputs_row):
        labels_1 = jax.tree_util.tree_map(lambda x: x[None], labels_row)
        outputs_1 = jax.tree_util.tree_map(lambda x: x[None], outputs_row)
        return loss_fn(labels_1, outputs_1)

    def mean(per_row):
        w = weights.astype(per_row.dtype)
        return jnp.sum(w * per_row) / jnp.maximum(jnp.sum(w), 1.0)

    return jax.tree_util.tree_map(mean, jax.vmap(one_row)(labels, outputs))


@pytest.mark.parametrize("loss_fn", ["loss", "loss_parts"])
@pytest.mark.parametrize("outputs", ["logits", "logits_and_a_second_tokens"])
def test_a_model_that_is_not_looped_gets_the_weighted_mean_of_before(outputs, loss_fn):
    _, labels = batch(rows=4)
    logits = jax.random.normal(jax.random.PRNGKey(1), (4, SEQ, VOCAB))
    given = logits if outputs == "logits" else {
        "logits": logits, "mtp_logits": (logits[::-1],),
        "mtp_weight": jnp.full((4,), 0.3),
    }
    weights = jnp.asarray([1.0, 1.0, 0.0, 1.0])
    fn = getattr(zoo, loss_fn)
    assert fn.weighted_mean(labels, given, weights) is None
    now, before = (
        jax.make_jaxpr(functools.partial(f, fn))(labels, given, weights)
        for f in (weighted_mean_loss, _weighted_mean_loss_of_before)
    )
    assert str(now) == str(before)


# ---- what a loop refuses ---------------------------------------------------------------


def test_generate_refuses_a_loop():
    features, _ = batch()
    model = zoo.custom_model(loop_steps=PASSES, **FIELDS)
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), features, training=False)
    )["params"]
    with pytest.raises(NotImplementedError, match="loop of layers"):
        zoo.generate(
            jax.tree_util.tree_map(lambda s: jnp.zeros(s.shape, s.dtype), params),
            np.zeros((1, 2), np.int32), 2, model=model,
        )


@pytest.mark.parametrize(
    "fields,error",
    [
        (dict(mtp_depth=1), "mtp_depth"),
        (dict(num_experts=4), "num_experts"),
        (dict(index_topk=8, index_heads=2, index_head_dim=8), "index_topk"),
        (dict(sliding_window=8, layer_pattern="w-", num_layers=2), "sliding_window"),
        (dict(loop_steps=0), "loop_steps 0"),
    ],
)
def test_a_loop_refuses_what_keeps_a_collection_a_layer(fields, error):
    features, _ = batch()
    model = zoo.custom_model(**{**FIELDS, "loop_steps": PASSES, **fields})
    with pytest.raises(ValueError, match=error):
        model.init(jax.random.PRNGKey(0), features, training=False)


# ---- the loop's name in the op -> scope map -----------------------------------------


@pytest.mark.parametrize(
    "op_name,part,phase",
    [
        ("jit(train_step)/jvp(TransformerLM)/loop/while/body/block_1/attn/query/dot_general",
         "block/attn/query", "forward"),
        ("jit(train_step)/jvp(TransformerLM)/loop/block_1/mlp/mlp_up/dot_general",
         "block/mlp/mlp_up", "forward"),
        ("jit(train_step)/transpose(jvp(TransformerLM))/loop/while/body/exit/norm/RMSNorm_0/mul",
         "exit/norm/RMSNorm", "backward"),
        ("jit(train_step)/jvp(TransformerLM)/loop/while/body/dynamic_update_slice",
         "loop", "forward"),
        ("jit(train_step)/transpose(jvp(TransformerLM))/loop/while/body/add_any",
         "loop", "backward"),
        ("jit(train_step)/loss/while/body/checkpoint/lm_head/dot_general",
         "loss/lm_head", "forward"),
    ],
)
def test_the_loop_names_its_own_ops_and_no_part_inside_it(op_name, part, phase):
    assert op_scopes.canonical(op_name) == (part, phase)
