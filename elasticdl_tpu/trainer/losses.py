"""Integer-label losses that never gather.

A model module's ``loss(labels, predictions)`` runs inside the jitted step
under :func:`elasticdl_tpu.trainer.step.weighted_mean_loss`, which vmaps it
over single rows.  Picking the label's entry with ``take_along_axis`` (what
``optax.softmax_cross_entropy_with_integer_labels`` does) is a gather; under
that ``vmap`` its transpose is a scatter into the *flattened* logits plus two
layout-copy loops around it (docs/designs/shape_canonicalization.md, "What a
gathering loss costs").  The functions here select by comparing an iota with
the label instead, so forward and backward are element-wise and reduce ops
that fuse with the softmax.  Zoo modules and users' modules import them from
here.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def pick_label(values, labels):
    """``values[..., labels]`` along the last axis, by compare-and-sum.

    ``labels`` are integers in ``[0, values.shape[-1])`` shaped like
    ``values`` without its last axis; a label outside that range selects
    nothing and gives 0 (``take_along_axis`` would wrap or clamp it).
    """
    classes = jax.lax.broadcasted_iota(
        jnp.int32, values.shape, values.ndim - 1
    )
    hit = classes == jnp.asarray(labels).astype(jnp.int32)[..., None]
    return jnp.sum(jnp.where(hit, values, 0), axis=-1)


def softmax_cross_entropy_with_integer_labels(logits, labels):
    """Per-position ``logsumexp(logits) - logits[label]`` over the last
    axis, computed in float32 whatever the logits' dtype; the caller takes
    ``.mean()``.  Same mathematics as optax's function of this name.
    """
    logits = logits.astype(jnp.float32)
    # shifting by the (constant) max is the stable log-sum-exp; the label's
    # logit takes the same shift, so it cancels
    logits = logits - jax.lax.stop_gradient(
        jnp.max(logits, axis=-1, keepdims=True)
    )
    log_normalizers = jnp.log(jnp.sum(jnp.exp(logits), axis=-1))
    return log_normalizers - pick_label(logits, labels)
