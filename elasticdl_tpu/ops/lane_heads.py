"""Heads narrower than a lane tile, side by side in one block's lanes (two
of 64 in 128): what a kernel that works them together needs to keep a
head's product its own.  The flash kernels' ``lanes`` layout
(``ops/attention.py``) and the scan kernels' units (``ops/ssd.py``) share
these; both run inside a Pallas kernel body, on values."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def lane_head(shape, heads):
    """Which of the ``heads`` a block's lanes hold side by side each lane
    of an array of ``shape`` belongs to."""
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, len(shape) - 1)
    return lane // (shape[-1] // heads)


def only_head(x, h, heads):
    """``x`` with every lane that is not head ``h``'s zeroed: a product
    contracted over the block's whole width is then that head's alone (the
    zeros add exact zeros, and a 64-deep contraction costs the matrix unit
    the same pass as a 128-deep one)."""
    if heads == 1:
        return x
    return jnp.where(lane_head(x.shape, heads) == h, x, jnp.zeros_like(x))


def by_head(parts):
    """One array whose lanes of head ``h`` are ``parts[h]``'s: selected,
    not scaled, so what a head's product left in the other heads' lanes
    never reaches an accumulator."""
    out = parts[-1]
    for h in range(len(parts) - 2, -1, -1):
        out = jnp.where(lane_head(out.shape, len(parts)) == h, parts[h], out)
    return out
