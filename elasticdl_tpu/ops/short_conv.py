"""The gated short convolution's pass between its two projections as a Pallas
kernel pair that touches each array once (``layers/short_conv.py`` holds the
plain form)::

    short_conv   out = C * causal depthwise conv_k(B * X)

for three streams ``B``, ``C``, ``X`` (batch, T, channels) and ``k`` taps a
channel: a gate before the taps and a gate after them, no bias, no activation.
A forward and a backward kernel under a ``jax.custom_vjp`` whose residuals
are its inputs: the backward forms ``B * X`` and the taps' sums again from
what it reads anyway.  Float32 inside (the product, the taps' sums, every
gradient); outputs in the inputs' dtype.

The tiling is ``ops/mamba_passes.py``'s ``mamba_conv``'s, whose strip helpers
this file imports: a grid step is a tile of a sequence's steps x a block of
channels, worked through in strips of 32 rows; a strip of ``B`` and of ``X``
is read with the 16 rows before it as one window (a second, 16-row block of
the same array before a tile: zeros at a sequence's first tile, so no
sequence sees the one before it in the batch), the windows' product is the
convolution's input, and tap ``s`` steps back is that product rotated ``s``
sublanes down.  The backward walks a sequence's tiles, and a tile's strips,
last to first, each handing the first 16 rows of ``dOut * C`` to the one
before it (between tiles in VMEM scratch); the taps' gradient adds up in its
resident output block.

The names below are the device trace's op names; none starts with ``ssd_``,
``flash_``, ``expert_gmm_`` or ``mamba_conv``, which ``perf/`` reads as those
kernels.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from elasticdl_tpu.ops.mamba_passes import (
    _HALO,
    _as_taps_read,
    _by_sublane,
    _conv_specs,
    _per_channel,
    _pre_activation,
    _strips,
    conv_tile,
)

SHORT_CONV_FWD = "short_conv_fwd"
SHORT_CONV_BWD = "short_conv_bwd"

_f32 = jnp.float32


def _products(b_ref, b_before, x_ref, x_before, first_tile, last_first=False):
    """``B * X`` of a tile in strips, each with the 16 rows before it:
    ``(first row, rows, B's window, X's window, their product)``."""
    for (first, size, b), (_, _, x) in zip(
        _strips(b_ref, b_before, first_tile, last_first),
        _strips(x_ref, x_before, first_tile, last_first),
    ):
        yield first, size, b, x, b * x


def _fwd_kernel(b_ref, b_before, c_ref, x_ref, x_before, w_ref, out_ref):
    w = w_ref[...]
    for first, size, _, _, z in _products(
        b_ref, b_before, x_ref, x_before, pl.program_id(2) == 0
    ):
        conv = _pre_activation(_as_taps_read(z, w.shape[0]), w, 0.0)
        out_ref[first:first + size, :] = (
            c_ref[first:first + size, :].astype(_f32) * conv
        ).astype(out_ref.dtype)


def _bwd_kernel(
    b_ref, b_before, c_ref, x_ref, x_before, w_ref, dout_ref,
    db_ref, dc_ref, dx_ref, dw_ref, carry, *, tiles,
):
    sequence, step = pl.program_id(1), pl.program_id(2)

    @pl.when(step == 0)
    def _():
        carry[...] = jnp.zeros_like(carry)

    @pl.when((sequence == 0) & (step == 0))
    def _():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    w = w_ref[...]
    taps = w.shape[0]
    # a sequence's last strip first: each hands the first 16 rows of the
    # convolution's output gradient to the strip before it
    after = carry[...]
    sums = [0.0] * taps
    for first, size, b, x, z in _products(
        b_ref, b_before, x_ref, x_before, step == tiles - 1, last_first=True
    ):
        rows = slice(first, first + size)
        shifted = _as_taps_read(z, taps)
        d_out = dout_ref[rows, :].astype(_f32)
        dc_ref[rows, :] = (d_out * _pre_activation(shifted, w, 0.0)).astype(
            dc_ref.dtype
        )
        d_conv = d_out * c_ref[rows, :].astype(_f32)
        ahead = jnp.concatenate([d_conv, after])
        d_z = 0.0
        for tap in range(taps):
            back = taps - 1 - tap
            turned = (
                pltpu.roll(ahead, size + _HALO - back, 0) if back else ahead
            )
            d_z = d_z + turned[:size] * w[tap:tap + 1, :]
            sums[tap] = sums[tap] + _by_sublane(d_conv * shifted[tap])
        db_ref[rows, :] = (d_z * x[_HALO:]).astype(db_ref.dtype)
        dx_ref[rows, :] = (d_z * b[_HALO:]).astype(dx_ref.dtype)
        after = d_conv[:_HALO]
    carry[...] = after
    for tap in range(taps):
        dw_ref[tap:tap + 1, :] += jnp.sum(sums[tap], axis=0, keepdims=True)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def short_conv(b, c, x, kernel, interpret):
    """``c * causal_conv(b * x, kernel)`` for ``b``, ``c``, ``x`` (batch, T,
    channels) and ``kernel`` (k, channels), ``kernel[k - 1]`` on the current
    step.  The shape must tile (``mamba_passes.conv_tile``)."""
    batch, steps, channels = x.shape
    taps = kernel.shape[0]
    rows, lanes = conv_tile(steps, channels, taps)
    tile, before = _conv_specs(rows, lanes, lambda j: j)
    return pl.pallas_call(
        _fwd_kernel,
        grid=(channels // lanes, batch, steps // rows),
        in_specs=[tile, before, tile, tile, before, _per_channel(taps, lanes)],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")
        ),
        interpret=interpret,
        name=SHORT_CONV_FWD,
    )(b, b, c, x, x, kernel.astype(_f32))


def _short_conv_fwd(b, c, x, kernel, interpret):
    return short_conv(b, c, x, kernel, interpret), (b, c, x, kernel)


def _short_conv_bwd(interpret, residuals, d_out):
    b, c, x, kernel = residuals
    batch, steps, channels = x.shape
    taps = kernel.shape[0]
    rows, lanes = conv_tile(steps, channels, taps)
    tiles = steps // rows
    tile, before = _conv_specs(rows, lanes, lambda j: tiles - 1 - j)
    like = jax.ShapeDtypeStruct(x.shape, x.dtype)
    db, dc, dx, dw = pl.pallas_call(
        functools.partial(_bwd_kernel, tiles=tiles),
        grid=(channels // lanes, batch, tiles),
        in_specs=[
            tile, before, tile, tile, before, _per_channel(taps, lanes), tile,
        ],
        out_specs=[tile, tile, tile, _per_channel(taps, lanes)],
        out_shape=[
            like, like, like, jax.ShapeDtypeStruct((taps, channels), _f32),
        ],
        scratch_shapes=[pltpu.VMEM((_HALO, lanes), _f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")
        ),
        interpret=interpret,
        name=SHORT_CONV_BWD,
    )(b, b, c, x, x, kernel.astype(_f32), d_out.astype(x.dtype))
    return db, dc, dx, dw.astype(kernel.dtype)


short_conv.defvjp(_short_conv_fwd, _short_conv_bwd)
