"""ResNet-50 classifier.

Reference: ``model_zoo/resnet50_subclass/resnet50_subclass.py`` — ResNet-50
over ``features["image"]`` emitting softmax probabilities; sparse
categorical cross-entropy on probabilities; SGD(0.02); L2 1e-4 kernel decay
(applied here via optax, see resnet50_model.py); accuracy metric.  The
reference's dataset decodes JPEG bytes and bilinear-resizes to 224; this
build's record codec carries dense arrays, so images arrive as
``(H, W, 3)`` uint8 already (the imagenet_resnet50 prep module packs them).
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np
import optax

from elasticdl_tpu.data.reader import decode_example
from elasticdl_tpu.models.resnet50_model import L2_WEIGHT_DECAY, ResNet50
from elasticdl_tpu.trainer.losses import pick_label
from elasticdl_tpu.trainer.metrics import Accuracy
from elasticdl_tpu.trainer.state import Modes
from elasticdl_tpu.models._image_wire import (  # noqa: F401
    batch_parse,
    device_parse,
)


class CustomModel(ResNet50):
    pass


def custom_model(num_classes=10, **kwargs):
    return CustomModel(num_classes=num_classes, **kwargs)


def loss(labels, predictions):
    labels = labels.reshape(-1)
    # predictions are probabilities (softmax output, like the reference)
    picked = pick_label(predictions, labels)
    return -jnp.log(jnp.clip(picked, 1e-8, 1.0)).mean()


def _decay_mask(params):
    # the reference decays conv/dense kernels plus the final fc bias
    # (resnet50_subclass.py:118-121), not BN scale/bias
    import jax

    def _decays(path, _):
        leaf = str(getattr(path[-1], "key", path[-1]))
        parent = str(getattr(path[-2], "key", path[-2])) if len(path) > 1 else ""
        return "kernel" in leaf or (parent == "fc" and "bias" in leaf)

    return jax.tree_util.tree_map_with_path(_decays, params)


def optimizer(lr=0.02):
    # keras l2(1e-4) penalty contributes grad 2e-4 * w; with plain SGD that
    # equals decoupled weight decay of the same magnitude
    return optax.chain(
        optax.add_decayed_weights(2 * L2_WEIGHT_DECAY, mask=_decay_mask),
        optax.sgd(lr),
    )


def dataset_fn(dataset, mode, metadata):
    def _parse(record):
        ex = decode_example(record)
        image = ex["image"].astype(np.float32) / 255.0
        if mode == Modes.PREDICTION:
            return {"image": image}
        return {"image": image}, ex["label"].astype(np.int32)

    dataset = dataset.map(_parse)
    if mode == Modes.TRAINING:
        dataset = dataset.shuffle(1024, seed=0)
    return dataset




def eval_metrics_fn():
    return {"accuracy": Accuracy()}
