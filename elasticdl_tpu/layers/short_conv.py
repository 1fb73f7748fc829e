"""The gated short convolution of Liquid AI's LFM2 stack (HF
``modeling_lfm2_moe.py``, ``Lfm2MoeShortConv``), the token mixer of three
layers in four::

    [B | C | X] = W_in u                 three widths d, a product a part of
                                         W_in's columns
    z = B * X                            the gate before the taps
    c_t = sum_{s < k} w_s z_{t-s}        depthwise, causal, zeros before the
                                         sequence's first token, no bias
    out = W_out (C * c)                  the gate after the taps

No activation, no bias, no scan.  What lies between the two projections is
one pass of ``ops/short_conv.py``'s kernel pair where it tiles the shape
(whole lane tiles of channels, rows that 16 divides), else the ``jax.numpy``
form below, which the kernels are tested against.  The product and the taps'
sums are float32 whatever ``dtype`` says.  ``conv_kernel[k - 1]`` is the tap
on the current token (``torch.nn.Conv1d``'s order, ``mamba.py``'s too).

The decode cache (the last ``k - 1`` rows of ``B * X`` a layer) is not built
(docs/designs/short_conv.md).

No reference counterpart; listed in DEVIATIONS.md additions.
"""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from elasticdl_tpu.layers.mamba import SplitDense, causal_conv
from elasticdl_tpu.ops import mamba_passes, on_mesh
from elasticdl_tpu.ops import short_conv as short_conv_ops


def gated_short_conv(b, c, x, kernel):
    """``c * causal_conv(b * x, kernel)`` in plain ``jnp``: ``b``, ``c``,
    ``x`` (batch, T, channels), ``kernel`` (k, channels).  Float32 inside,
    ``x``'s dtype out."""
    f32 = jnp.float32
    conv = causal_conv(b.astype(f32) * x.astype(f32), kernel, jnp.zeros((), f32))
    return (c.astype(f32) * conv).astype(x.dtype)


def short_conv(b, c, x, kernel):
    """:func:`gated_short_conv`: one pass of ``ops/short_conv.py``'s kernel
    where it tiles the shape, else the plain form."""
    taps, channels = kernel.shape
    if mamba_passes.conv_tile(x.shape[1], channels, taps):
        return on_mesh.over_batch(
            short_conv_ops.short_conv, (b, c, x), (kernel,)
        )
    return gated_short_conv(b, c, x, kernel)


class ShortConv(nn.Module):
    taps: int = 3
    dtype: Any = None  # compute dtype; params stay f32

    @nn.compact
    def __call__(self, u):
        """u: (batch, T, embed) -> (batch, T, embed)."""
        embed = u.shape[-1]
        b, c, x = SplitDense(
            (embed, embed, embed), dtype=self.dtype, name="in_proj"
        )(u)
        kernel = self.param(
            "conv_kernel", nn.initializers.lecun_normal(), (self.taps, embed)
        )
        # the region between the two projections, by
        # telemetry/op_scopes.py's names
        with jax.named_scope("pass"):
            y = short_conv(b, c, x, kernel)
        return nn.Dense(
            embed, use_bias=False, dtype=self.dtype, name="out_proj"
        )(y)
