"""Plain float32 reference of the zoo's ResNet-50, as
``configs/resnet50_imagenet.json`` describes it: loss and gradient of one
batch in training mode.

It follows He et al., "Deep Residual Learning for Image Recognition"
(arXiv:1512.03385), Table 1, the 50-layer column: a 7x7/2 convolution of
width 64, a 3x3/2 max pool, stages of 3, 4, 6 and 3 bottleneck blocks
(1x1, 3x3, 1x1; widths 64-64-256 doubling per stage), the stride of a stage
on the first 1x1 of its first block, projection shortcuts where the shape
changes (option B), BatchNorm after every convolution and before the
activation, global average pool, a 1,000-way linear layer, softmax.  Where
the zoo model departs from the paper the reference follows the zoo, and the
line that does says so.  Everything is ``jax.numpy`` and
``jax.lax.conv_general_dilated`` in float32 under
``default_matmul_precision("highest")``; nothing of the program is
imported: the parameter tree is read by its leaf names."""

from __future__ import annotations

import jax
import jax.numpy as jnp

# the zoo's (models/resnet50_model.py), which are the reference
# implementation's; the paper gives none
BATCH_NORM_EPSILON = 1e-5
# blocks per stage and the stride of each stage's first block, Table 1
STAGES = ((3, 1), (4, 2), (6, 2), (3, 2))


def conv(x, p, stride: int = 1, padding="SAME"):
    return jax.lax.conv_general_dilated(
        x, p["kernel"], (stride, stride), padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )


def batch_norm(x, p):
    """Training mode: the batch's own mean and biased variance over batch,
    height and width (Ioffe & Szegedy 2015, algorithm 1); the running
    averages play no part in the loss."""
    mean = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(x - mean), axis=(0, 1, 2))
    return (x - mean) / jnp.sqrt(var + BATCH_NORM_EPSILON) * p["scale"] + p["bias"]


def bottleneck(x, p, stride: int):
    """1x1, 3x3, 1x1 with the stride on the first 1x1, as in the paper
    (later implementations move it to the 3x3)."""
    shortcut = x
    if "conv_shortcut" in p:  # option B: a projection where the shape changes
        shortcut = batch_norm(
            conv(x, p["conv_shortcut"], stride), p["bn_shortcut"]
        )
    y = jax.nn.relu(batch_norm(conv(x, p["conv_a"], stride), p["bn_a"]))
    y = jax.nn.relu(batch_norm(conv(y, p["conv_b"]), p["bn_b"]))
    y = batch_norm(conv(y, p["conv_c"]), p["bn_c"])
    return jax.nn.relu(y + shortcut)


def loss_fn(params, images, labels):
    x = jnp.pad(images, ((0, 0), (3, 3), (3, 3), (0, 0)))
    x = conv(x, params["conv1"], 2, "VALID")  # 7x7/2 over the padded image
    x = jax.nn.relu(batch_norm(x, params["bn_conv1"]))
    x = jax.lax.reduce_window(  # 3x3/2 max pool
        x, -jnp.inf, jax.lax.max, (1, 3, 3, 1), (1, 2, 2, 1), "SAME"
    )
    for stage, (blocks, stride) in enumerate(STAGES, start=2):
        x = bottleneck(x, params[f"conv_block_{stage}"], stride)
        for b in range(1, blocks):
            x = bottleneck(x, params[f"identity_block_{stage}_{b}"], 1)
    x = jnp.mean(x, axis=(1, 2))
    logits = x @ params["fc"]["kernel"] + params["fc"]["bias"]
    # the zoo's loss (models/resnet50_subclass.py): the model emits softmax
    # probabilities and the loss is -log of the label's, clipped below at
    # 1e-8 as the reference implementation's Keras loss does.  It carries no
    # L2 term: the zoo applies L2 1e-4 as decoupled decay in the optimizer
    probs = jax.nn.softmax(logits, axis=-1)
    picked = jnp.take_along_axis(probs, labels[:, None], axis=-1)[:, 0]
    return -jnp.mean(jnp.log(jnp.clip(picked, 1e-8, 1.0)))


def loss_and_grads(params, features, labels):
    """``(loss, grads)``; ``grads`` has the tree of ``params``.  Depth and
    widths are the parameter tree's own shapes."""
    params = jax.tree_util.tree_map(lambda p: jnp.asarray(p, jnp.float32), params)
    # departure: records carry decoded uint8 pixels; the zoo scales them to
    # [0, 1] on the device (models/_image_wire.py) and subtracts no mean
    images = jnp.asarray(features["image"]).astype(jnp.float32) / 255.0
    labels = jnp.asarray(labels, jnp.int32).reshape(-1)
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(loss_fn)(params, images, labels)
