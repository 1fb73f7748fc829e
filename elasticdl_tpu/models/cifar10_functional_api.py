"""CIFAR-10 CNN — the functional-API reference model, in flax.

Reference: ``model_zoo/cifar10_functional_api/cifar10_functional_api.py``:
three [Conv-BN-relu ×2, MaxPool, Dropout(0.2/0.3/0.4)] blocks with
32/64/128 channels (SAME padding, BN eps 1e-6 momentum 0.9), Flatten,
Dense(10); SGD(0.1) with a step learning-rate schedule
(0.1 → 0.01 @5000 → 0.001 @15000 model versions); sparse-softmax-xent;
accuracy metric; images scaled to [0,1].
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


class Cifar10CNN(nn.Module):
    num_classes: int = 10
    dtype: Any = None  # compute dtype; params/BN stats stay f32

    @nn.compact
    def __call__(self, features, training: bool = False):
        x = features["image"] if isinstance(features, dict) else features
        x = x.reshape((x.shape[0], 32, 32, 3))
        if self.dtype is not None:
            x = x.astype(self.dtype)
        for channels, rate in ((32, 0.2), (64, 0.3), (128, 0.4)):
            for _ in range(2):
                x = nn.Conv(
                    channels, (3, 3), padding="SAME", dtype=self.dtype
                )(x)
                x = nn.BatchNorm(
                    use_running_average=not training,
                    momentum=0.9,
                    epsilon=1e-6,
                    dtype=self.dtype,
                )(x)
                x = nn.relu(x)
            x = nn.max_pool(x, (2, 2), strides=(2, 2))
            # train-time dropout; the step builder threads the 'dropout' rng
            x = nn.Dropout(rate, deterministic=not training)(x)
        x = x.reshape((x.shape[0], -1))
        return nn.Dense(
            self.num_classes, dtype=self.dtype, name="output"
        )(x).astype(jnp.float32)


def custom_model(**kwargs):
    return Cifar10CNN(**kwargs)


def loss(labels, predictions):
    labels = labels.reshape(-1)
    return softmax_cross_entropy_with_integer_labels(
        predictions, labels
    ).mean()


def optimizer(lr=0.1):
    return optax.sgd(lr)


def learning_rate_scheduler(model_version):
    # reference cifar10_functional_api.py:119-125.  model_version is a
    # traced array inside the jitted step (optax schedule input), so this
    # must be branch-free
    return jnp.where(
        model_version < 5000,
        0.1,
        jnp.where(model_version < 15000, 0.01, 0.001),
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
