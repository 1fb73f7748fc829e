"""Record kind ``template_images``: one uniform-random uint8 template per
class plus uniform integer pixel noise, clipped —
``synthetic.gen_mnist``'s class-template construction at the configured
image shape, in integer arithmetic so that 0.9 GB of it is made in
seconds.  A record is the decoded uint8 image and its int64 label."""

from __future__ import annotations

import numpy as np

# the class templates come from a fixed RNG, so every seed draws from one
# underlying distribution
_FIXED_RNG = 1234


def shared_state(spec: dict):
    """One uniform-random uint8 template per class, the same for every
    seed and shard."""
    shape = (int(spec["height"]), int(spec["width"]), int(spec["channels"]))
    return np.random.default_rng(_FIXED_RNG).integers(
        0, 256, size=(int(spec["num_classes"]), *shape), dtype=np.uint8
    )


def template_images(rng, labels, templates, amplitude: int):
    """``(len(labels), H, W, C)`` uint8 images: the label's template plus
    uniform integer noise in ``[-amplitude, amplitude)``, clipped."""
    images = templates[labels].astype(np.int16)
    images += rng.integers(
        -amplitude, amplitude, size=images.shape, dtype=np.int16
    )
    return np.clip(images, 0, 255, out=images).astype(np.uint8)


def columns(rng, spec: dict, count: int, state=None) -> dict:
    """``count`` records, one array a field of the record on disk."""
    templates = shared_state(spec) if state is None else state
    labels = rng.integers(int(spec["num_classes"]), size=count)
    images = template_images(
        rng, labels, templates, int(spec["noise_amplitude"])
    )
    return {"image": images, "label": labels.astype(np.int64)}


def batch(columns: dict):
    """``(features, labels)`` as the zoo's parse function hands them to
    the trainer."""
    return {"image": columns["image"]}, columns["label"].astype(np.int32)


def batch_shapes(spec: dict, rows: int):
    """``(features, labels)`` as ``(shape, dtype)`` pairs, for a compile
    without data."""
    shape = (rows, int(spec["height"]), int(spec["width"]), int(spec["channels"]))
    return {"image": (shape, "uint8")}, ((rows,), "int32")


def units(spec: dict) -> dict:
    """How many of each work unit one record is."""
    return {"records": 1}
