"""The Mamba-2 mixer (Dao & Gu, arXiv:2405.21060) as NVIDIA's ``nemotron_h``
stack runs it (HF ``modeling_nemotron_h.py``, ``NemotronHMamba2Mixer``)::

    [z | xBC | dt] = W_in u                 widths d_in | d_in + 2 G N | H,
                                            a product a part of W_in's columns
    xBC = silu(causal depthwise conv_k(xBC) + b)
    x, B, C = split(xBC)                    x: H heads of P; B, C: G groups of N
    dt = softplus(dt + dt_bias)             A = -exp(A_log), a scalar a head
    h_t = exp(dt_t A) h_{t-1} + dt_t x_t (x) B_t;   y_t = C_t . h_t + D x_t
    y = RMSNorm_groups(y * silu(z)) * w     the gate BEFORE the norm, the mean
                                            square within each of the G groups
    out = W_out y

``d_in = H * P`` is given by the heads, not by an expansion factor.  No bias
but the convolution's.  The recurrence is ``ops/ssd.py``'s chunked scan,
which reads ``x``, ``B`` and ``C`` out of the convolved ``xBC`` where it
stands and writes ``y`` as ``(batch, T, d_in)``: the split above is the
kernels' index maps, not a copy.  The convolution with its SiLU and the gated
norm are one pass each of ``ops/mamba_passes.py``'s kernels where those tile
the shape (whole lane tiles of channels a group, rows that 16 divides), else
the ``jax.numpy`` forms below, which the kernels are tested against.  ``dt``, ``A``, the taps' sums
and the norm's statistics are float32 whatever ``dtype`` says.

No reference counterpart; listed in DEVIATIONS.md additions.
"""

from __future__ import annotations

import functools
import math
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from elasticdl_tpu.ops import mamba_passes, on_mesh
from elasticdl_tpu.ops import ssd as ssd_ops


def _a_log_init(key, shape, dtype=jnp.float32):
    """``A`` uniform in [1, 16] (Mamba-2's ``A_init_range``)."""
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


def _dt_bias_init(low: float, high: float, floor: float):
    """``softplus(dt_bias)`` log-uniform in [``low``, ``high``], not under
    ``floor`` (``time_step_min`` / ``_max`` / ``_floor``)."""

    def init(key, shape, dtype=jnp.float32):
        dt = jnp.exp(
            jax.random.uniform(key, shape, dtype)
            * (math.log(high) - math.log(low))
            + math.log(low)
        )
        dt = jnp.maximum(dt, floor)
        return dt + jnp.log(-jnp.expm1(-dt))  # softplus's inverse

    return init


def causal_conv(x, kernel, bias):
    """Depthwise convolution along time that sees the present and the
    ``k - 1`` steps before it: ``x`` (batch, T, channels), ``kernel`` (k,
    channels).  Float32 sums, ``x``'s dtype out."""
    taps, steps = kernel.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    out = bias.astype(jnp.float32)
    for tap in range(taps):
        out = out + padded[:, tap:tap + steps].astype(jnp.float32) * kernel[
            tap
        ].astype(jnp.float32)
    return out.astype(x.dtype)


def gated_group_norm(y, z, scale, groups: int, eps: float):
    """``RMSNorm(y * silu(z)) * scale`` with the mean square taken within
    each of ``groups`` equal parts of the channels; float32 inside."""
    gated = y.astype(jnp.float32) * nn.silu(z.astype(jnp.float32))
    parts = gated.reshape(*gated.shape[:-1], groups, -1)
    parts = parts * jax.lax.rsqrt(
        jnp.mean(jnp.square(parts), axis=-1, keepdims=True) + eps
    )
    return (parts.reshape(gated.shape) * scale.astype(jnp.float32)).astype(y.dtype)


def conv_silu(x, kernel, bias):
    """``silu(causal_conv(x, kernel, bias))``: one pass of
    ``ops/mamba_passes.py``'s kernel where it tiles the shape, else the plain
    form."""
    taps, channels = kernel.shape
    if mamba_passes.conv_tile(x.shape[1], channels, taps):
        return on_mesh.over_batch(mamba_passes.conv_silu, (x,), (kernel, bias))
    return nn.silu(causal_conv(x, kernel, bias))


def gate_norm(y, z, scale, groups: int, eps: float):
    """:func:`gated_group_norm`: one pass of ``ops/mamba_passes.py``'s kernel
    where it tiles the shape, else the plain form."""
    if mamba_passes.gate_norm_tile(y.shape[0] * y.shape[1], y.shape[2], groups):
        return on_mesh.over_batch(
            functools.partial(mamba_passes.gate_norm, groups=groups, eps=eps),
            (y, z), (scale,),
        )
    return gated_group_norm(y, z, scale, groups, eps)


class SplitDense(nn.Module):
    """``nn.Dense`` without a bias whose one kernel is applied in ranges of
    its columns, one matrix product and one output each: the parts come out
    as arrays of their own, where a split of one wide product is a copy a
    part (or, read in place by a kernel, a window whose rows are 80.5 lane
    tiles apart, which the chip's DMA reads at a third of its speed)."""

    widths: tuple
    dtype: Any = None

    @nn.compact
    def __call__(self, inputs):
        kernel = self.param(
            "kernel", nn.initializers.lecun_normal(),
            (inputs.shape[-1], sum(self.widths)),
        )
        inputs, kernel = nn.dtypes.promote_dtype(
            inputs, kernel, dtype=self.dtype
        )
        parts, first = [], 0
        for width in self.widths:
            parts.append(inputs @ kernel[:, first:first + width])
            first += width
        return parts


class Mamba2Mixer(nn.Module):
    num_heads: int
    head_dim: int
    groups: int = 1
    state_size: int = 128
    conv_kernel: int = 4
    chunk: int = 128
    norm_eps: float = 1e-5
    dt_min: float = 0.001
    dt_max: float = 0.1
    dt_floor: float = 1e-4
    dtype: Any = None  # compute dtype; params stay f32

    @nn.compact
    def __call__(self, u):
        """u: (batch, T, embed) -> (batch, T, embed)."""
        heads, groups, states = self.num_heads, self.groups, self.state_size
        inner = heads * self.head_dim
        conv_width = inner + 2 * groups * states
        z, xbc, dt = SplitDense(
            (inner, conv_width, heads), dtype=self.dtype, name="in_proj"
        )(u)
        # the regions between the modules and the kernel, by
        # telemetry/op_scopes.py's names
        with jax.named_scope("mamba_conv"):
            xbc = conv_silu(
                xbc,
                self.param(
                    "conv_kernel", nn.initializers.lecun_normal(),
                    (self.conv_kernel, conv_width),
                ),
                self.param("conv_bias", nn.initializers.zeros, (conv_width,)),
            )
        dt_bias = self.param(
            "dt_bias",
            _dt_bias_init(self.dt_min, self.dt_max, self.dt_floor), (heads,),
        )
        a_log = self.param("A_log", _a_log_init, (heads,))
        d = self.param("D", nn.initializers.ones, (heads,))
        with jax.named_scope("ssd_scan"):
            y = ssd_ops.ssd_scan(
                xbc, nn.softplus(dt.astype(jnp.float32) + dt_bias),
                -jnp.exp(a_log.astype(jnp.float32)), d,
                groups=groups, states=states, chunk=self.chunk,
            )
        with jax.named_scope("gate_norm"):
            y = gate_norm(
                y, z,
                self.param("norm_scale", nn.initializers.ones, (inner,)),
                groups, self.norm_eps,
            )
        return nn.Dense(
            u.shape[-1], use_bias=False, dtype=self.dtype, name="out_proj"
        )(y)
