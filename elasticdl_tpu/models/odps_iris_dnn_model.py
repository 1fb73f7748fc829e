"""Iris linear classifier (the ODPS-table demo model).

Reference: ``model_zoo/odps_iris_dnn_model/odps_iris_dnn_model.py`` —
``(4, 1)`` input, Flatten, Dense(3); sparse-softmax-xent; SGD(0.1);
accuracy.  The reference's dataset_fn parses ODPS table rows; this build's
reads the framework record codec (ODPS reader delivers the same dict
records when configured).
"""

from __future__ import annotations

import flax.linen as nn
import numpy as np
import optax

from elasticdl_tpu.data.reader import decode_example
from elasticdl_tpu.trainer.losses import (
    softmax_cross_entropy_with_integer_labels,
)
from elasticdl_tpu.trainer.metrics import Accuracy
from elasticdl_tpu.trainer.state import Modes


class IrisDNN(nn.Module):
    num_classes: int = 3

    @nn.compact
    def __call__(self, features, training: bool = False):
        x = features["features"] if isinstance(features, dict) else features
        x = x.reshape((x.shape[0], -1))
        return nn.Dense(self.num_classes, name="output")(x)


def custom_model(**kwargs):
    return IrisDNN(**kwargs)


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
        feats = {"features": ex["features"].astype(np.float32)}
        if mode == Modes.PREDICTION:
            return feats
        return feats, ex["label"].astype(np.int32)

    return dataset.map(_parse)


def eval_metrics_fn():
    return {"accuracy": Accuracy()}
