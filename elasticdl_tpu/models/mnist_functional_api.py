"""MNIST CNN — the functional-API reference model, in flax.

Reference: ``model_zoo/mnist_functional_api/mnist_functional_api.py``:
Conv(32,3x3,relu) -> Conv(64,3x3,relu) -> BatchNorm -> MaxPool(2) ->
Dropout(0.25) -> Flatten -> Dense(10); SGD(lr=0.1);
sparse-softmax-xent loss; accuracy metric; images scaled to [0,1].
"""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax.numpy as jnp
import numpy as np
import optax

from elasticdl_tpu.data.reader import decode_example
from elasticdl_tpu.trainer.losses import (
    softmax_cross_entropy_with_integer_labels,
)
from elasticdl_tpu.trainer.metrics import Accuracy
from elasticdl_tpu.trainer.state import Modes
from elasticdl_tpu.models._image_wire import (  # noqa: F401
    batch_parse,
    device_parse,
)


class MnistCNN(nn.Module):
    num_classes: int = 10
    # compute dtype (e.g. "bfloat16"); params/BN stats stay f32, logits
    # cast back up for the loss — same contract as the other CNN and
    # transformer zoo models (the FM/DNN recommenders are gather-bound
    # and stay f32-only)
    dtype: Any = None

    @nn.compact
    def __call__(self, features, training: bool = False):
        x = features["image"] if isinstance(features, dict) else features
        x = x.reshape((x.shape[0], 28, 28, 1))
        if self.dtype is not None:
            x = x.astype(self.dtype)
        x = nn.relu(nn.Conv(32, (3, 3), padding="VALID", dtype=self.dtype)(x))
        x = nn.relu(nn.Conv(64, (3, 3), padding="VALID", dtype=self.dtype)(x))
        # momentum 0.9 (not flax's 0.99 default) so running stats are usable
        # after short training runs; eval-mode forward depends on them
        x = nn.BatchNorm(
            use_running_average=not training, momentum=0.9, dtype=self.dtype
        )(x)
        x = nn.max_pool(x, (2, 2), strides=(2, 2))
        x = nn.Dropout(0.25, deterministic=not training)(x)
        x = x.reshape((x.shape[0], -1))
        return nn.Dense(self.num_classes, dtype=self.dtype)(x).astype(
            jnp.float32
        )


def custom_model(**kwargs):
    return MnistCNN(**kwargs)


def loss(labels, predictions):
    labels = labels.reshape(-1)
    return softmax_cross_entropy_with_integer_labels(
        predictions, labels
    ).mean()


def optimizer(lr=0.1):
    return optax.sgd(lr)


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
