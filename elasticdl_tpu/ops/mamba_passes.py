"""The Mamba-2 mixer's two bandwidth passes around the scan as Pallas kernels
that touch each array once (``layers/mamba.py`` holds their plain forms)::

    gate_norm    out = RMSNorm_groups(y * silu(z)) * scale
    mamba_conv   out = silu(causal depthwise conv_k(x) + bias)

Each has a forward and a backward kernel under a ``jax.custom_vjp`` whose
residuals are its inputs: the backward forms the gate, the mean square, the
pre-activation again from what it reads anyway.  Float32 inside (the gate, the
mean square and its ``rsqrt``, the taps' sums, SiLU and its derivative, every
parameter's gradient); outputs in the inputs' dtype.

``gate_norm``: a grid step is a tile of rows x a block of whole channel
groups (one group at the cell's 512 lanes a group), so a group's mean square
is a sum along lanes inside the block.  The backward walks a block's row
tiles in order and adds up ``d_scale`` in its resident output block.

``mamba_conv``: a grid step is a tile of a sequence's steps x a block of
channels, worked through in strips of 32 rows so that a strip's taps stay in
registers.  A strip is read with the 16 rows before it as one window; tap
``s`` steps back is the window rotated ``s`` sublanes down, whose wrapped rows
fall in the 16 that are dropped.  The 16 rows before a tile are a second,
16-row block of the same array: zeros at a sequence's first tile, so no
sequence sees the one before it in the batch.  The backward walks a
sequence's tiles, and a tile's strips, last to first, each handing the first
16 rows of its pre-activation's gradient to the one before it (between tiles
in VMEM scratch), which is all ``d_x`` needs from there; ``d_kernel`` and
``d_bias`` add up in their resident output blocks.

Every operand is an array of its own, whole lane tiles wide: a window of
columns of a wider array whose rows are no whole number of lane tiles (the
mixer's 10,304-wide projection) is read at 183 GB/s where the same window of
a 10,240-wide array is read at 591 (TPU v5e), so ``layers/mamba.py`` makes
``z`` and ``xBC`` with a matrix product each.

The names below are the device trace's op names; none starts with ``ssd_``,
``flash_`` or ``expert_gmm_``, which ``perf/`` reads as those kernels.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

GATE_NORM_FWD = "gate_norm_fwd"
GATE_NORM_BWD = "gate_norm_bwd"
MAMBA_CONV_FWD = "mamba_conv_fwd"
MAMBA_CONV_BWD = "mamba_conv_bwd"

_LANES = 128
# the rows a convolution tile reads beside itself: bfloat16's sublane tile
_HALO = 16
# elements of a block of the norm, and its lanes at most
_BLOCK = 512 * 512
_GATE_LANES = 512
_CONV_ROWS = 512
_CONV_LANES = 512
# rows a convolution tile is worked through at a time, to stay in registers
_CONV_STRIP = 32

_f32 = jnp.float32


def _row_tile(rows, most):
    """The largest power-of-two multiple of 16, at most ``most``, that
    divides ``rows``; None where 16 does not."""
    tile = _HALO
    while tile * 2 <= most and rows % (tile * 2) == 0:
        tile *= 2
    return tile if rows % tile == 0 else None


# ---- the gated group norm --------------------------------------------------


def gate_norm_tile(rows, channels, groups):
    """``(row tile, channel block)`` :func:`gate_norm` takes ``rows`` rows of
    ``channels`` channels in ``groups`` groups with: a block is some of the
    groups whole; None where the kernels do not tile the shape."""
    if channels % groups:
        return None
    width = channels // groups
    if width % _LANES:
        return None
    held = groups
    while held > 1 and (held * width > _GATE_LANES or groups % held):
        held -= 1
    tile = _row_tile(rows, max(_HALO, _BLOCK // (held * width)))
    return tile and (tile, held * width)


def _gate(y_ref, z_ref, columns):
    """A group's ``y`` and ``z`` of a block, and ``sigmoid(z)``."""
    y, z = y_ref[:, columns].astype(_f32), z_ref[:, columns].astype(_f32)
    return y, z, jax.nn.sigmoid(z)


def _groups_of(ref, width):
    return [
        slice(first, first + width) for first in range(0, ref.shape[1], width)
    ]


def _gate_fwd_kernel(y_ref, z_ref, scale_ref, out_ref, *, eps, width):
    for columns in _groups_of(y_ref, width):
        y, z, sig = _gate(y_ref, z_ref, columns)
        gated = y * (z * sig)
        rstd = jax.lax.rsqrt(
            jnp.mean(gated * gated, axis=-1, keepdims=True) + eps
        )
        out_ref[:, columns] = (gated * rstd * scale_ref[:, columns]).astype(
            out_ref.dtype
        )


def _gate_bwd_kernel(
    y_ref, z_ref, scale_ref, dout_ref, dy_ref, dz_ref, dscale_ref, *,
    eps, width,
):
    @pl.when(pl.program_id(1) == 0)
    def _():
        dscale_ref[...] = jnp.zeros_like(dscale_ref)

    for columns in _groups_of(y_ref, width):
        y, z, sig = _gate(y_ref, z_ref, columns)
        act = z * sig
        gated = y * act
        rstd = jax.lax.rsqrt(
            jnp.mean(gated * gated, axis=-1, keepdims=True) + eps
        )
        normed = gated * rstd
        d_out = dout_ref[:, columns].astype(_f32)
        dscale_ref[:, columns] += jnp.sum(d_out * normed, axis=0, keepdims=True)
        d_normed = d_out * scale_ref[:, columns]
        d_gated = rstd * (
            d_normed
            - normed * jnp.mean(d_normed * normed, axis=-1, keepdims=True)
        )
        dy_ref[:, columns] = (d_gated * act).astype(dy_ref.dtype)
        dz_ref[:, columns] = (
            d_gated * y * (sig * (1.0 + z * (1.0 - sig)))
        ).astype(dz_ref.dtype)


def _gate_specs(y, groups):
    """The grid (channel block, row tile), the block of an array shaped like
    ``y`` and the block of one a channel."""
    rows, channels = y.shape
    tile, lanes = gate_norm_tile(rows, channels, groups)
    by_rows = pl.BlockSpec((tile, lanes), lambda c, i: (i, c))
    by_channel = pl.BlockSpec((1, lanes), lambda c, i: (0, c))
    return (channels // lanes, rows // tile), by_rows, by_channel


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _gate_norm(y, z, scale, groups, eps, interpret):
    grid, by_rows, by_channel = _gate_specs(y, groups)
    return pl.pallas_call(
        functools.partial(
            _gate_fwd_kernel, eps=eps, width=y.shape[1] // groups
        ),
        grid=grid,
        in_specs=[by_rows, by_rows, by_channel],
        out_specs=by_rows,
        out_shape=jax.ShapeDtypeStruct(y.shape, y.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")
        ),
        interpret=interpret,
        name=GATE_NORM_FWD,
    )(y, z, scale.astype(_f32).reshape(1, -1))


def _gate_norm_fwd(y, z, scale, groups, eps, interpret):
    return _gate_norm(y, z, scale, groups, eps, interpret), (y, z, scale)


def _gate_norm_bwd(groups, eps, interpret, residuals, d_out):
    y, z, scale = residuals
    grid, by_rows, by_channel = _gate_specs(y, groups)
    dy, dz, dscale = pl.pallas_call(
        functools.partial(
            _gate_bwd_kernel, eps=eps, width=y.shape[1] // groups
        ),
        grid=grid,
        in_specs=[by_rows, by_rows, by_channel, by_rows],
        out_specs=[by_rows, by_rows, by_channel],
        out_shape=[
            jax.ShapeDtypeStruct(y.shape, y.dtype),
            jax.ShapeDtypeStruct(z.shape, z.dtype),
            jax.ShapeDtypeStruct((1, y.shape[1]), _f32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
        name=GATE_NORM_BWD,
    )(y, z, scale.astype(_f32).reshape(1, -1), d_out.astype(y.dtype))
    return dy, dz, dscale.reshape(scale.shape).astype(scale.dtype)


_gate_norm.defvjp(_gate_norm_fwd, _gate_norm_bwd)


def gate_norm(y, z, scale, *, groups, eps, interpret):
    """``RMSNorm(y * silu(z)) * scale``, the mean square within each of
    ``groups`` equal parts of the channels, for ``y``, ``z`` (batch, T,
    channels).  The shape must tile (:func:`gate_norm_tile`)."""
    flat = (y.shape[0] * y.shape[1], y.shape[2])
    return _gate_norm(
        y.reshape(flat), z.reshape(flat), scale, groups, eps, interpret
    ).reshape(y.shape)


# ---- the causal convolution and its SiLU -----------------------------------


def conv_tile(steps, channels, taps):
    """``(row tile, channel block)`` :func:`conv_silu` takes sequences of
    ``steps`` steps and ``channels`` channels with; None where the kernels
    do not tile the shape."""
    lanes = _CONV_LANES
    while lanes >= _LANES and channels % lanes:
        lanes //= 2
    rows = _row_tile(steps, _CONV_ROWS)
    if lanes < _LANES or rows is None or not 1 <= taps <= _HALO:
        return None
    return rows, lanes


def _strips(x_ref, before_ref, first_tile, last_first=False):
    """A tile in strips of rows, each with the 16 rows before it, float32:
    ``(first row, rows, window)``, the window one array of ``16 + rows``
    rows.  Nothing lies before a sequence's first tile."""
    rows = x_ref.shape[0]
    size = min(rows, _CONV_STRIP)
    for first in range(0, rows, size)[::-1 if last_first else 1]:
        if first:
            window = x_ref[first - _HALO:first + size, :].astype(_f32)
        else:
            before = jnp.where(first_tile, 0.0, before_ref[...].astype(_f32))
            window = jnp.concatenate([before, x_ref[:size, :].astype(_f32)])
        yield first, size, window


def _as_taps_read(window, taps):
    """``[tap]`` is a strip as tap ``tap`` reads it, ``taps - 1 - tap`` rows
    back: a rotation of the window down its sublanes, whose wrapped rows fall
    in the 16 that are dropped."""
    return [
        (pltpu.roll(window, back, 0) if back else window)[_HALO:]
        for back in range(taps - 1, -1, -1)
    ]


def _pre_activation(shifted, w, bias):
    out = bias
    for tap, rows in enumerate(shifted):
        out = out + rows * w[tap:tap + 1, :]
    return out


def _conv_fwd_kernel(x_ref, before_ref, w_ref, bias_ref, out_ref):
    w, bias = w_ref[...], bias_ref[...]
    for first, size, window in _strips(
        x_ref, before_ref, pl.program_id(2) == 0
    ):
        a = _pre_activation(_as_taps_read(window, w.shape[0]), w, bias)
        out_ref[first:first + size, :] = (a * jax.nn.sigmoid(a)).astype(
            out_ref.dtype
        )


def _by_sublane(x):
    """The sum of ``x``'s rows as far as eight: vector adds, no shuffle."""
    return sum(x[i:i + 8] for i in range(0, x.shape[0], 8))


def _conv_bwd_kernel(
    x_ref, before_ref, w_ref, bias_ref, dout_ref,
    dx_ref, dw_ref, dbias_ref, carry, *, tiles,
):
    sequence, step = pl.program_id(1), pl.program_id(2)

    @pl.when(step == 0)
    def _():
        carry[...] = jnp.zeros_like(carry)

    @pl.when((sequence == 0) & (step == 0))
    def _():
        dw_ref[...] = jnp.zeros_like(dw_ref)
        dbias_ref[...] = jnp.zeros_like(dbias_ref)

    w, bias = w_ref[...], bias_ref[...]
    taps = w.shape[0]
    # a sequence's last strip first: each hands the first 16 rows of its
    # pre-activation's gradient to the strip before it
    after = carry[...]
    sums = [0.0] * (taps + 1)
    for first, size, window in _strips(
        x_ref, before_ref, step == tiles - 1, last_first=True
    ):
        shifted = _as_taps_read(window, taps)
        a = _pre_activation(shifted, w, bias)
        sig = jax.nn.sigmoid(a)
        d_a = dout_ref[first:first + size, :].astype(_f32) * (
            sig * (1.0 + a * (1.0 - sig))
        )
        ahead = jnp.concatenate([d_a, after])
        d_x = 0.0
        for tap in range(taps):
            back = taps - 1 - tap
            rows = pltpu.roll(ahead, size + _HALO - back, 0) if back else ahead
            d_x = d_x + rows[:size] * w[tap:tap + 1, :]
            sums[tap] = sums[tap] + _by_sublane(d_a * shifted[tap])
        sums[taps] = sums[taps] + _by_sublane(d_a)
        dx_ref[first:first + size, :] = d_x.astype(dx_ref.dtype)
        after = d_a[:_HALO]
    carry[...] = after
    for tap in range(taps):
        dw_ref[tap:tap + 1, :] += jnp.sum(sums[tap], axis=0, keepdims=True)
    dbias_ref[...] += jnp.sum(sums[taps], axis=0, keepdims=True)


def _conv_specs(rows, lanes, tile_of):
    """Block specs over the grid (channel block, sequence, step):
    ``tile_of(step)`` is the tile of the sequence a step works on, and the
    16 rows before it."""
    halo = rows // _HALO
    tile = pl.BlockSpec(
        (None, rows, lanes), lambda c, b, j: (b, tile_of(j), c)
    )
    before = pl.BlockSpec(
        (None, _HALO, lanes),
        lambda c, b, j: (b, jnp.maximum(tile_of(j) * halo - 1, 0), c),
    )
    return tile, before


def _per_channel(taps, lanes):
    return pl.BlockSpec((taps, lanes), lambda c, b, j: (0, c))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def conv_silu(x, kernel, bias, interpret):
    """``silu(causal_conv(x, kernel, bias))`` for ``x`` (batch, T, channels)
    and ``kernel`` (k, channels).  The shape must tile (:func:`conv_tile`)."""
    batch, steps, channels = x.shape
    taps = kernel.shape[0]
    rows, lanes = conv_tile(steps, channels, taps)
    tile, before = _conv_specs(rows, lanes, lambda j: j)
    return pl.pallas_call(
        _conv_fwd_kernel,
        grid=(channels // lanes, batch, steps // rows),
        in_specs=[
            tile, before, _per_channel(taps, lanes), _per_channel(1, lanes),
        ],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")
        ),
        interpret=interpret,
        name=MAMBA_CONV_FWD,
    )(x, x, kernel.astype(_f32), bias.astype(_f32).reshape(1, channels))


def _conv_silu_fwd(x, kernel, bias, interpret):
    return conv_silu(x, kernel, bias, interpret), (x, kernel, bias)


def _conv_silu_bwd(interpret, residuals, d_out):
    x, kernel, bias = residuals
    batch, steps, channels = x.shape
    taps = kernel.shape[0]
    rows, lanes = conv_tile(steps, channels, taps)
    tiles = steps // rows
    tile, before = _conv_specs(rows, lanes, lambda j: tiles - 1 - j)
    dx, dw, dbias = pl.pallas_call(
        functools.partial(_conv_bwd_kernel, tiles=tiles),
        grid=(channels // lanes, batch, tiles),
        in_specs=[
            tile, before, _per_channel(taps, lanes), _per_channel(1, lanes),
            tile,
        ],
        out_specs=[tile, _per_channel(taps, lanes), _per_channel(1, lanes)],
        out_shape=[
            jax.ShapeDtypeStruct(x.shape, x.dtype),
            jax.ShapeDtypeStruct((taps, channels), _f32),
            jax.ShapeDtypeStruct((1, channels), _f32),
        ],
        scratch_shapes=[pltpu.VMEM((_HALO, lanes), _f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")
        ),
        interpret=interpret,
        name=MAMBA_CONV_BWD,
    )(
        x, x, kernel.astype(_f32), bias.astype(_f32).reshape(1, channels),
        d_out.astype(x.dtype),
    )
    return (
        dx, dw.astype(kernel.dtype),
        dbias.reshape(channels).astype(bias.dtype),
    )


conv_silu.defvjp(_conv_silu_fwd, _conv_silu_bwd)
