"""Record kind ``tiny_digits``, added the way a later PR adds a kind of
record — this file, no edit to ``perf/trafficgen.py``: 28x28 uint8 images,
one fixed random template per class plus seeded noise, and int64 labels, as
the MNIST zoo model's wire expects."""

import numpy as np


def shared_state(spec):
    return np.random.default_rng(1234).integers(
        0, 256, size=(int(spec["num_classes"]), 28, 28)
    )


def columns(rng, spec, count, state=None):
    templates = shared_state(spec) if state is None else state
    labels = rng.integers(len(templates), size=count)
    noise = rng.integers(-8, 8, size=(count, 28, 28))
    return {
        "image": np.clip(templates[labels] + noise, 0, 255).astype(np.uint8),
        "label": labels.astype(np.int64),
    }


def batch(columns):
    return {"image": columns["image"]}, columns["label"].astype(np.int32)


def batch_shapes(spec, rows):
    return {"image": ((rows, 28, 28), "uint8")}, ((rows,), "int32")


def units(spec):
    return {"records": 1}
