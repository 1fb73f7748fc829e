"""Chunked selective-state-space scan (Mamba-2's SSD) as two Pallas kernels.

The recurrence, a head (``x_t``: P channels, ``B_t``, ``C_t``: N states of
the head's group, ``dt_t > 0``, ``A < 0`` a scalar a head)::

    h_t = exp(dt_t A) h_{t-1} + dt_t x_t (x) B_t        h: (N, P)
    y_t = C_t . h_t + D x_t

is linear in ``h``, so a sequence is cut into chunks of ``chunk`` steps
(Dao & Gu, arXiv:2405.21060, section 6).  With ``cum_t`` the running sum of
``dt A`` inside a chunk and ``xd = dt x``::

    y   = ((C B^T) o L) xd + exp(cum) o (C H)            L[t,s] = exp(cum_t - cum_s), s <= t
    H' = exp(cum_last) H + (B o w)^T xd                  w_s = exp(cum_last - cum_s)

Inside a chunk everything is a matrix product on the MXU; between chunks
only ``H``, the ``heads`` states of (N, P) a sequence, is carried, in order,
in VMEM scratch: the chunk axis is the grid's innermost and sequential.
Decays are differences of ``cum`` taken before the exponential, in float32,
so no product of decays is ever formed (a chunk of strong decay underflows a
cumulative product; a difference is exact).  Products take their operands in
the inputs' dtype and accumulate in float32; the carried state is float32.

A grid step is one chunk of one group: its heads share ``B``, ``C`` and so
``C B^T``, which is computed once and used by each of them in turn.

``ssd_fwd`` also writes the state each chunk started from; ``ssd_bwd`` walks
the chunks in reverse carrying ``dH`` and recomputes ``C B^T`` and the
decays from them.  The gradient of ``cum`` needs no pass of its own: every
term of ``y_t`` carries ``exp(cum_t)`` and every term that reads ``xd_s``
carries ``exp(-cum_s)``, so ``dcum = sum_p dy y - sum_p dxd xd``, which the
backward kernel forms from its float32 products before anything is rounded
(a difference of two sums that nearly cancel where the decay is strong),
plus, at a chunk's last step, ``<H', dH'>`` (a scalar a head and chunk).
``dt``, ``A``, ``D`` and the running sums are plain ``jax.numpy`` around the
kernels, differentiated by JAX (docs/designs/ssd_scan.md); the products with
``x`` are formed in the kernels' layout, where ``x`` is taken once.

Each kernel has a name the device trace's op line shows, as the flash and
grouped-matmul kernels do: ``perf/`` reads them by it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from elasticdl_tpu.ops import on_mesh

SSD_FWD = "ssd_fwd"
SSD_BWD = "ssd_bwd"
# the transposes to and from the kernels' layout, by telemetry/op_scopes.py's
# name; never around a ``pallas_call`` (ops/attention.py)
_FOLD = "fold"

_LANES = 128
# a @ b.T and a.T @ b: the transposed products the MXU takes natively
_NT = (((1,), (1,)), ((), ()))
_TN = (((0,), (0,)), ((), ()))

_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary")
)


def _fit(square, width):
    """The first ``width`` columns of a matrix whose columns are all alike,
    or that many by repetition."""
    have = square.shape[1]
    if width == have:
        return square
    if width < have:
        return square[:, :width]
    if width % have == 0:
        return jnp.tile(square, (1, width // have))
    return jnp.broadcast_to(square[:, :1], (square.shape[0], width))


def _decays(cum_row):
    """From a chunk's running sums ``(1, L)``: ``column[t, :] = cum_t``
    (the row repeated over the sublanes, transposed: what the flash kernels'
    ``_row_to_lanes`` does) and ``L[t, s] = exp(cum_t - cum_s)`` for
    ``s <= t``, zero above the diagonal."""
    length = cum_row.shape[1]
    column = jnp.broadcast_to(cum_row, (length, length)).T
    ahead = jax.lax.broadcasted_iota(
        jnp.int32, (length, length), 0
    ) - jax.lax.broadcasted_iota(jnp.int32, (length, length), 1)
    decay = jnp.where(
        ahead >= 0, jnp.exp(jnp.minimum(column - cum_row, 0.0)), 0.0
    )
    return column, decay


def _dot(a, b, dims=None, dtype=None):
    """``a`` and ``b`` rounded once to ``dtype``, multiplied, accumulated in
    float32."""
    a, b = a.astype(dtype), b.astype(dtype)
    if dims is None:
        return jax.lax.dot(a, b, preferred_element_type=jnp.float32)
    return jax.lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def _fwd_kernel(xd_ref, b_ref, c_ref, cum_ref, y_ref, start_ref, state, *, heads):
    @pl.when(pl.program_id(2) == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    b, c = b_ref[...], c_ref[...]
    length, states = b.shape
    width = xd_ref.shape[-1]
    dot = functools.partial(_dot, dtype=b.dtype)
    scores = dot(c, b, _NT)
    for head in range(heads):
        column, decay = _decays(cum_ref[head:head + 1, :])
        last = column[length - 1:length, :]
        xd = xd_ref[head]
        h = state[head]
        start_ref[head] = h
        y = dot(scores * decay, xd)
        y += jnp.exp(_fit(column, width)) * dot(c, h)
        y_ref[head] = y.astype(y_ref.dtype)
        weight = jnp.exp(_fit(last, states) - _fit(column, states))
        state[head] = jnp.exp(_fit(last, width)) * h + dot(b * weight, xd, _TN)


def _bwd_kernel(
    xd_ref, b_ref, c_ref, cum_ref, dy_ref, start_ref,
    dxd_ref, db_ref, dc_ref, dcum_ref, end_ref, d_state, *, heads,
):
    @pl.when(pl.program_id(2) == 0)
    def _():
        d_state[...] = jnp.zeros_like(d_state)

    b, c = b_ref[...], c_ref[...]
    length, states = b.shape
    width = xd_ref.shape[-1]
    dot = functools.partial(_dot, dtype=b.dtype)
    scores = dot(c, b, _NT)
    d_scores = jnp.zeros((length, length), jnp.float32)
    d_b = jnp.zeros((length, states), jnp.float32)
    d_c = jnp.zeros((length, states), jnp.float32)
    for head in range(heads):
        column, decay = _decays(cum_ref[head:head + 1, :])
        last = column[length - 1:length, :]
        xd, dy = xd_ref[head], dy_ref[head]
        h, dh = start_ref[head], d_state[head]
        weight = jnp.exp(_fit(last, states) - _fit(column, states))
        dy_in = dy * jnp.exp(_fit(column, width))
        masked = scores * decay
        dxd = dot(masked, dy, _TN) + dot(b * weight, dh)
        dxd_ref[head] = dxd.astype(dxd_ref.dtype)
        d_masked = dot(dy, xd, _NT)
        d_scores += d_masked * decay
        # sum_p dy y - sum_p dxd xd, as a row: sum_p dy y is the row sums of
        # d_masked o masked plus the states' part of y
        within = dy_in * dot(c, h) - dxd * xd.astype(jnp.float32)
        dcum_ref[head:head + 1, :] = jnp.sum(
            (d_masked * masked).T, axis=0, keepdims=True
        ) + jnp.sum(within.T, axis=0, keepdims=True)
        d_c += dot(dy_in, h, _NT)
        d_b += dot(xd, dh, _NT) * weight
        dh = jnp.exp(_fit(last, width)) * dh + dot(c, dy_in, _TN)
        d_state[head] = dh
        # <H, dH> at this chunk's start = the previous chunk's <H', dH'>
        inner = jnp.sum(
            jnp.sum(h * dh, axis=0, keepdims=True), axis=1, keepdims=True
        )
        end_ref[head:head + 1, :] = jnp.broadcast_to(inner, (1, _LANES))
    dc_ref[...] = (d_c + dot(d_scores, b)).astype(dc_ref.dtype)
    db_ref[...] = (d_b + dot(d_scores, c, _TN)).astype(db_ref.dtype)


def _specs(heads, length, width, states, chunk_of):
    """Block specs of the arrays both kernels read, over the grid (batch,
    group, step); ``chunk_of(step)`` is the chunk a step works on."""
    per_head = pl.BlockSpec(
        (None, None, heads, length, width),
        lambda i, g, j: (i, g, 0, chunk_of(j), 0),
    )
    per_group = pl.BlockSpec(
        (None, None, length, states), lambda i, g, j: (i, g, chunk_of(j), 0)
    )
    sums = pl.BlockSpec(
        (None, None, None, heads, length),
        lambda i, g, j: (i, chunk_of(j), g, 0, 0),
    )
    starts = pl.BlockSpec(
        (None, None, None, heads, states, width),
        lambda i, g, j: (i, chunk_of(j), g, 0, 0, 0),
    )
    return per_head, per_group, sums, starts


def _forward(xd, b, c, cum, interpret):
    """``xd`` (batch, groups, heads a group, T, P); ``b``, ``c`` (batch,
    groups, T, N); ``cum`` (batch, chunks, groups, heads a group, L)."""
    batch, groups, heads, _, width = xd.shape
    states = b.shape[-1]
    chunks, length = cum.shape[1], cum.shape[-1]
    per_head, per_group, sums, starts = _specs(
        heads, length, width, states, lambda j: j
    )
    return pl.pallas_call(
        functools.partial(_fwd_kernel, heads=heads),
        grid=(batch, groups, chunks),
        in_specs=[per_head, per_group, per_group, sums],
        out_specs=[per_head, starts],
        out_shape=[
            jax.ShapeDtypeStruct(xd.shape, xd.dtype),
            jax.ShapeDtypeStruct(
                (batch, chunks, groups, heads, states, width), jnp.float32
            ),
        ],
        scratch_shapes=[pltpu.VMEM((heads, states, width), jnp.float32)],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
        name=SSD_FWD,
    )(xd, b, c, cum)


def _backward(xd, b, c, cum, dy, start, interpret):
    batch, groups, heads, _, width = xd.shape
    states = b.shape[-1]
    chunks, length = cum.shape[1], cum.shape[-1]
    per_head, per_group, sums, starts = _specs(
        heads, length, width, states, lambda j: chunks - 1 - j
    )
    ends = pl.BlockSpec(
        (None, None, None, heads, _LANES),
        lambda i, g, j: (i, chunks - 1 - j, g, 0, 0),
    )
    return pl.pallas_call(
        functools.partial(_bwd_kernel, heads=heads),
        grid=(batch, groups, chunks),
        in_specs=[per_head, per_group, per_group, sums, per_head, starts],
        out_specs=[per_head, per_group, per_group, sums, ends],
        out_shape=[
            jax.ShapeDtypeStruct(xd.shape, xd.dtype),
            jax.ShapeDtypeStruct(b.shape, b.dtype),
            jax.ShapeDtypeStruct(c.shape, c.dtype),
            jax.ShapeDtypeStruct(cum.shape, jnp.float32),
            jax.ShapeDtypeStruct(
                (batch, chunks, groups, heads, _LANES), jnp.float32
            ),
        ],
        scratch_shapes=[pltpu.VMEM((heads, states, width), jnp.float32)],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
        name=SSD_BWD,
    )(xd, b, c, cum, dy, start)


def _by_group(x, groups):
    """``(batch, T, heads, P)`` as the kernels read it: ``(batch, groups,
    heads a group, T, P)``."""
    batch, steps, heads, width = x.shape
    return x.transpose(0, 2, 1, 3).reshape(
        batch, groups, heads // groups, steps, width
    )


def _by_step(x):
    batch, groups, heads, steps, width = x.shape
    return x.reshape(batch, groups * heads, steps, width).transpose(0, 2, 1, 3)


def _sums_by_group(cum, groups, length):
    """``(batch, T, heads)`` float32 as ``(batch, chunks, groups, heads a
    group, L)``."""
    batch, steps, heads = cum.shape
    return cum.reshape(
        batch, steps // length, length, groups, heads // groups
    ).transpose(0, 1, 3, 4, 2)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _ssd_core(xd, cum, b, c, chunk, interpret):
    """``xd`` and the result as the kernels read and write them, (batch,
    groups, heads a group, T, P); ``cum`` (batch, T, heads) float32; ``b``,
    ``c`` (batch, T, groups, N)."""
    return _ssd_core_fwd(xd, cum, b, c, chunk, interpret)[0]


def _ssd_core_fwd(xd, cum, b, c, chunk, interpret):
    groups = b.shape[2]
    with jax.named_scope(_FOLD):
        operands = (
            xd, b.transpose(0, 2, 1, 3), c.transpose(0, 2, 1, 3),
            _sums_by_group(cum, groups, chunk),
        )
    y, start = _forward(*operands, interpret)
    return y, (xd, cum, b, c, start)


def _ssd_core_bwd(chunk, interpret, residuals, dy):
    xd, cum, b, c, start = residuals
    batch, groups, per_group, steps, _ = xd.shape
    heads = groups * per_group
    with jax.named_scope(_FOLD):
        operands = (
            xd, b.transpose(0, 2, 1, 3), c.transpose(0, 2, 1, 3),
            _sums_by_group(cum, groups, chunk), dy.astype(xd.dtype),
        )
    dxd, db, dc, dcum, ends = _backward(*operands, start, interpret)
    with jax.named_scope(_FOLD):
        # (batch, chunks, groups, heads a group, L) back to (batch, T, heads)
        dcum = dcum.transpose(0, 1, 4, 2, 3).reshape(
            batch, steps // chunk, chunk, heads
        )
        # a chunk's last running sum also scales the state it hands on: the
        # kernel gives <H, dH> at each chunk's start, which is the chunk
        # before's <H', dH'>; the last chunk hands nothing on
        ends = ends[..., 0].reshape(batch, steps // chunk, heads)
        ends = jnp.concatenate(
            [ends[:, 1:], jnp.zeros_like(ends[:, :1])], axis=1
        )
        dcum = dcum.at[:, :, -1, :].add(ends).reshape(batch, steps, heads)
        return dxd, dcum, db.transpose(0, 2, 1, 3), dc.transpose(0, 2, 1, 3)


_ssd_core.defvjp(_ssd_core_fwd, _ssd_core_bwd)


def ssd_chunked(x, dt, a, b, c, d, *, chunk: int, interpret: bool | None = None):
    """The scan on one device.  ``x`` (batch, T, heads, P); ``dt`` (batch,
    T, heads) float32, positive; ``a`` (heads,) negative; ``b``, ``c``
    (batch, T, groups, N), a head reading group ``head // (heads //
    groups)``; ``d`` (heads,).  Returns ``y`` like ``x``.  ``T`` that is no
    whole number of chunks is padded with steps of ``dt = 0``, which decay
    nothing and add nothing.  Differentiable in every argument.
    ``interpret=None`` follows the default backend."""
    if interpret is None:
        interpret = on_mesh.default_interpret()
    heads, groups = x.shape[2], b.shape[2]
    if heads % groups:
        raise ValueError(f"{heads} heads do not divide into {groups} groups")
    steps = x.shape[1]
    pad = -steps % chunk
    if pad:
        x, dt, b, c = (
            jnp.pad(v, [(0, 0), (0, pad)] + [(0, 0)] * (v.ndim - 2))
            for v in (x, dt, b, c)
        )
    dt = dt.astype(jnp.float32)
    decay = dt * a.astype(jnp.float32)
    # the running sums inside each chunk as a product with a triangle of
    # ones: XLA's cumsum over 128 steps is a reduce-window that takes 1.9 ms
    # for these 2 MB on the chip, and its transpose as long again
    cum = jnp.einsum(
        "ts,bcsh->bcth", jnp.tril(jnp.ones((chunk, chunk), jnp.float32)),
        decay.reshape(x.shape[0], -1, chunk, heads),
        precision=jax.lax.Precision.HIGHEST,
    ).reshape(decay.shape)
    # ``x`` goes to the kernels' layout once, in its own dtype, and ``dt x``,
    # the skip and its sum with the kernels' output are formed there.  Formed
    # in the layer's layout, the float32 ``x`` both products share is a 134
    # MB array that XLA writes and then copies to the layout its transposes
    # want (0.6 ms a layer and pass); the barrier keeps the conversion this
    # side of the transpose
    with jax.named_scope(_FOLD):
        x = jax.lax.optimization_barrier(_by_group(x, groups))
        dt = _by_group(dt[..., None], groups)
        d = d.astype(jnp.float32).reshape(groups, heads // groups, 1, 1)
    xd = (x.astype(jnp.float32) * dt).astype(x.dtype)
    y = _ssd_core(xd, cum, b, c, chunk, interpret)
    y = y + (d * x.astype(jnp.float32)).astype(x.dtype)
    with jax.named_scope(_FOLD):
        return _by_step(y)[:, :steps]


def ssd_scan(x, dt, a, b, c, d, *, chunk: int):
    """:func:`ssd_chunked` under the registered mesh, mapped over the batch
    (``ops/on_mesh.py::over_batch``)."""
    return on_mesh.over_batch(
        lambda x, dt, b, c, a, d, interpret: ssd_chunked(
            x, dt, a, b, c, d, chunk=chunk, interpret=interpret
        ),
        (x, dt, b, c), (a, d),
    )
