"""The framework's gather-free integer-label losses (trainer/losses.py):
equal to optax's gathering form, exact under the masked per-row loss, and
— the structural guard — no gather or scatter anywhere in the gradient of
``weighted_mean_loss`` over a zoo loss (on the chip a vmapped gather's
transpose is a scatter into the flattened logits plus two layout loops:
docs/designs/shape_canonicalization.md)."""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from elasticdl_tpu.trainer.losses import (
    pick_label,
    softmax_cross_entropy_with_integer_labels,
)
from elasticdl_tpu.trainer.step import weighted_mean_loss

VOCAB = 37

# module -> (labels shape, predictions shape, predictions are probabilities)
ZOO_LOSSES = {
    "long_seq_transformer": ((4, 6), (4, 6, VOCAB), False),
    "mnist_functional_api": ((4,), (4, 10), False),
    "mnist_subclass": ((4, 1), (4, 10), False),
    "cifar10_functional_api": ((4,), (4, 10), False),
    "cifar10_subclass": ((4,), (4, 10), False),
    "odps_iris_dnn_model": ((4,), (4, 3), False),
    "resnet50_subclass": ((4,), (4, VOCAB), True),
    "imagenet_resnet50": ((4, 1), (4, VOCAB), True),
}


def _labels_and_logits(shape, dtype, seed=0):
    rng = np.random.default_rng(seed)
    labels = rng.integers(0, shape[-1], size=shape[:-1]).astype(np.int32)
    # both ends of the class axis are selected somewhere
    labels.reshape(-1)[0] = 0
    labels.reshape(-1)[-1] = shape[-1] - 1
    logits = jnp.asarray(4.0 * rng.standard_normal(shape), dtype)
    return jnp.asarray(labels), logits


def _zoo_case(name):
    labels_shape, shape, probabilities = ZOO_LOSSES[name]
    labels, predictions = _labels_and_logits(shape, jnp.float32)
    if probabilities:
        predictions = jax.nn.softmax(predictions, axis=-1)
    loss = importlib.import_module(f"elasticdl_tpu.models.{name}").loss
    return loss, labels.reshape(labels_shape), predictions


@pytest.mark.parametrize("shape", [(5, VOCAB), (3, 4, VOCAB)], ids=["2d", "3d"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
def test_cross_entropy_equals_optax_in_value_and_gradient(dtype, shape):
    labels, logits = _labels_and_logits(shape, dtype)

    def ours(x):
        return softmax_cross_entropy_with_integer_labels(x, labels)

    def theirs(x):
        return optax.softmax_cross_entropy_with_integer_labels(
            x.astype(jnp.float32), labels
        )

    assert ours(logits).dtype == jnp.float32
    assert ours(logits).shape == shape[:-1]
    np.testing.assert_allclose(ours(logits), theirs(logits), rtol=1e-6, atol=1e-6)
    ours_grad = jax.grad(lambda x: ours(x).mean())(logits)
    theirs_grad = jax.grad(lambda x: theirs(x).mean())(logits)
    assert ours_grad.dtype == dtype
    np.testing.assert_allclose(
        ours_grad.astype(jnp.float32),
        theirs_grad.astype(jnp.float32),
        rtol=1e-6,
        atol=1e-7 if dtype == jnp.float32 else 1e-3,
    )


def test_pick_label_is_take_along_axis_in_value_and_gradient():
    labels, values = _labels_and_logits((3, 4, VOCAB), jnp.float32, seed=1)

    def gathered(x):
        return jnp.take_along_axis(x, labels[..., None], axis=-1)[..., 0]

    np.testing.assert_array_equal(pick_label(values, labels), gathered(values))
    np.testing.assert_array_equal(
        jax.grad(lambda x: pick_label(x, labels).sum())(values),
        jax.grad(lambda x: gathered(x).sum())(values),
    )


@pytest.mark.parametrize("name", sorted(ZOO_LOSSES))
def test_all_ones_weights_reproduce_the_zoo_loss(name):
    loss, labels, predictions = _zoo_case(name)
    ones = jnp.ones((labels.shape[0],), jnp.float32)
    np.testing.assert_allclose(
        weighted_mean_loss(loss, labels, predictions, ones),
        loss(labels, predictions),
        rtol=1e-6,
    )
    np.testing.assert_allclose(
        jax.grad(lambda p: weighted_mean_loss(loss, labels, p, ones))(predictions),
        jax.grad(lambda p: loss(labels, p))(predictions),
        rtol=1e-5,
        atol=1e-7,
    )


@pytest.mark.parametrize("name", sorted(ZOO_LOSSES))
def test_weight_zero_row_gets_exactly_zero_gradient(name):
    loss, labels, predictions = _zoo_case(name)
    weights = jnp.asarray([1.0, 0.0, 1.0, 1.0])
    grad = np.asarray(
        jax.grad(lambda p: weighted_mean_loss(loss, labels, p, weights))(
            predictions
        )
    )
    assert not grad[1].any()
    assert grad[0].any() and grad[2].any() and grad[3].any()
    # and the masked loss is the loss of the real rows alone
    real = np.asarray([0, 2, 3])
    np.testing.assert_allclose(
        weighted_mean_loss(loss, labels, predictions, weights),
        loss(labels[real], predictions[real]),
        rtol=1e-6,
    )


def _primitives(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn.primitive.name
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _primitives(sub)


@pytest.mark.parametrize("name", sorted(ZOO_LOSSES))
def test_masked_zoo_loss_gradient_holds_no_gather_or_scatter(name):
    loss, labels, predictions = _zoo_case(name)
    weights = jnp.ones((labels.shape[0],), jnp.float32)
    jaxpr = jax.make_jaxpr(
        jax.grad(lambda p: weighted_mean_loss(loss, labels, p, weights))
    )(predictions)
    found = {
        p for p in _primitives(jaxpr.jaxpr) if "gather" in p or "scatter" in p
    }
    assert not found, (
        f"{name}.loss gathers by label: under weighted_mean_loss's vmap that "
        f"is a scatter over the flattened logits on the chip ({sorted(found)}); "
        "use elasticdl_tpu.trainer.losses"
    )


def test_the_guard_sees_the_gathering_form():
    """The guard above is not vacuous: optax's gathering loss under the
    same vmap does leave a gather and a scatter in the gradient."""
    labels, logits = _labels_and_logits((4, 6, VOCAB), jnp.float32)

    def gathering(labels, logits):
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, labels
        ).mean()

    jaxpr = jax.make_jaxpr(
        jax.grad(
            lambda p: weighted_mean_loss(
                gathering, labels, p, jnp.ones((4,), jnp.float32)
            )
        )
    )(logits)
    names = set(_primitives(jaxpr.jaxpr))
    assert any("gather" in p for p in names)
    assert any("scatter" in p for p in names)
