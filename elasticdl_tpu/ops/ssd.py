"""Chunked selective-state-space scan (Mamba-2's SSD) as two Pallas kernels
that read and write the layer's own ``(batch, T, channels)`` layout.

The recurrence, a head (``x_t``: P channels, ``B_t``, ``C_t``: N states of
the head's group, ``dt_t > 0``, ``A < 0`` a scalar a head)::

    h_t = exp(dt_t A) h_{t-1} + dt_t x_t (x) B_t        h: (N, P)
    y_t = C_t . h_t + D x_t

is linear in ``h``, so a sequence is cut into chunks of ``chunk`` steps
(Dao & Gu, arXiv:2405.21060, section 6).  With ``cum_t`` the running sum of
``dt A`` inside a chunk and ``xd = dt x``::

    y   = ((C B^T) o L) xd + exp(cum) o (C H) + D x      L[t,s] = exp(cum_t - cum_s), s <= t
    H' = exp(cum_last) H + (B o w)^T xd                  w_s = exp(cum_last - cum_s)

Inside a chunk everything is a matrix product on the MXU; between chunks
only ``H``, the ``heads`` states of (N, P) a sequence, is carried, in order,
in VMEM scratch: the grid is (batch, chunk, group), a sequence's chunks in
order and each chunk's groups one after another.
Decays are differences of ``cum`` taken before the exponential, in float32,
so no product of decays is ever formed (a chunk of strong decay underflows a
cumulative product; a difference is exact).  Products take their operands in
the inputs' dtype and accumulate in float32; the carried state is float32.

**Layout.**  The kernels take the convolved stream ``xBC`` as the mixer
holds it, ``(batch, T, H P + 2 G N)``, and cut their blocks out of it by
their index maps: a grid step is one chunk of one group, whose ``x`` is the
``L x (heads a group x P)`` window of the group's lanes and whose ``B`` and
``C`` are ``L x N`` windows further along the same rows; ``y`` and ``dy``
are ``(batch, T, H P)`` and blocked like ``x``; ``xBC``'s gradient is
written whole, a chunk's rows a block that stays while the chunk's groups
each write their three windows of it.  Heads narrower than a
lane tile sit side by side in it (two of 64): a *unit* of the loop below is
the heads of one tile, or one head of whole tiles.  A product that a head
shares with its neighbours in the tile (``C H``, ``dC``, ``dH``) is taken
once over the tile; one that is a head's own (its decay matrix differs) is
taken over the tile with its own lanes selected from the result, or with
the other heads' lanes zeroed where the tile's lanes are contracted
(``ops/lane_heads.py``, which the flash kernels share): the matrix unit's
pass is the same for 64 lanes as for 128.  ``dt x``, the skip
``D x`` and its sum with the scan's output are formed in the kernels from
``x`` read once, ``dt`` (float32 rows a head) and ``D`` (float32 a lane);
``dt x`` is rounded once to the inputs' dtype before its products.

``ssd_fwd`` also writes the state each chunk started from; ``ssd_bwd`` walks
the chunks in reverse carrying ``dH`` and recomputes ``C B^T`` and the
decays from them.  It returns ``xBC``'s gradient (``dx`` whole, the scan's
part times ``dt`` plus the skip's ``D dy``, with ``dB`` and ``dC`` beside it
as ``xBC`` holds them) and three float32 rows a head and chunk: ``dcum``, ``sum_p dxd x`` (``dt``'s own gradient) and ``sum_p dy x``
(``D``'s, summed over the steps outside).  The gradient of ``cum`` needs no
pass of its own: every term of ``y_t`` carries ``exp(cum_t)`` and every
term that reads ``xd_s`` carries ``exp(-cum_s)``, so ``dcum = sum_p dy y -
sum_p dxd xd``, formed from the float32 products before anything is rounded
(a difference of two sums that nearly cancel where the decay is strong),
plus, at a chunk's last step, ``<H', dH'>`` (a scalar a head and chunk).
``dt``, ``A`` and the running sums are plain ``jax.numpy`` on
``(batch, T, heads)`` arrays around the kernels, differentiated by JAX
(docs/designs/ssd_scan.md).

**Shapes the kernels take** (:func:`scan_tile`): a group's heads a whole
number of lane tiles, heads that divide a tile or are whole tiles, ``N`` a
whole number of tiles.  Any other shape runs :func:`_chunked_plain`, the
same mathematics in ``jax.numpy`` that the kernels are tested against.

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
from elasticdl_tpu.ops.lane_heads import by_head, only_head

SSD_FWD = "ssd_fwd"
SSD_BWD = "ssd_bwd"

_LANES = 128
# a @ b.T and a.T @ b: the transposed products the MXU takes natively
_NT = (((1,), (1,)), ((), ()))
_TN = (((0,), (0,)), ((), ()))

_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "arbitrary", "arbitrary")
)
_f32 = jnp.float32


def scan_tile(heads, width, groups, states):
    """``(heads a unit, lanes a unit)`` the kernels work a group's heads in,
    or None where they do not tile the shape: ``heads`` heads of ``width``
    channels in ``groups`` groups of ``states`` states."""
    per_group = heads // groups
    if (per_group * width) % _LANES or states % _LANES:
        return None
    if (heads * width) % states:  # B's windows start a whole number in
        return None
    if width % _LANES == 0:
        return 1, width
    if _LANES % width or per_group % (_LANES // width):
        return None
    return _LANES // width, _LANES


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


def _column(row):
    """A ``(1, L)`` row as ``(L, L)`` with ``[t, :] = row[t]``: the row
    repeated over the sublanes, transposed (what the flash kernels'
    ``_row_to_lanes`` does)."""
    length = row.shape[1]
    return jnp.broadcast_to(row, (length, length)).T


def _decays(cum_row):
    """From a chunk's running sums ``(1, L)``: their column and ``L[t, s] =
    exp(cum_t - cum_s)`` for ``s <= t``, zero above the diagonal."""
    length = cum_row.shape[1]
    column = _column(cum_row)
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
        return jax.lax.dot(a, b, preferred_element_type=_f32)
    return jax.lax.dot_general(a, b, dims, preferred_element_type=_f32)


def _rows_by_head(x, width, per):
    """``sum_p x[t, p]`` over each head's lanes, as ``per`` rows ``(1, L)``:
    the transpose's sublanes summed a head."""
    across = x.T
    return [
        jnp.sum(across[k * width:(k + 1) * width], axis=0, keepdims=True)
        for k in range(per)
    ]


class _Unit:
    """What both kernels make of one unit of a chunk: ``x`` float32, ``dt x``
    rounded, each head's running sums as a column and its decay matrix, and
    the sums a lane (``cum``, its last step) across the unit's heads."""

    def __init__(self, unit, per, width, x_ref, dt_ref, cum_ref):
        span = per * width
        self.lanes = slice(unit * span, (unit + 1) * span)
        self.heads = range(unit * per, (unit + 1) * per)
        self.x = x_ref[:, self.lanes].astype(_f32)
        made = [_decays(cum_ref[h:h + 1, :]) for h in self.heads]
        self.columns = [column for column, _ in made]
        self.decays = [decay for _, decay in made]
        self.cum = by_head([_fit(c, span) for c in self.columns])
        self.last = self.cum[-1:, :]
        self.dt = by_head(
            [_fit(_column(dt_ref[h:h + 1, :]), span) for h in self.heads]
        )
        self.xd = (self.x * self.dt).astype(x_ref.dtype)

    def weight(self, k, states):
        """``w_s = exp(cum_last - cum_s)`` of the unit's head ``k``, a
        column across ``states`` lanes."""
        column = self.columns[k]
        return jnp.exp(_fit(column[-1:, :], states) - _fit(column, states))


def _fwd_kernel(
    x_ref, b_ref, c_ref, dt_ref, cum_ref, d_ref, y_ref, start_ref, state, *,
    per, width,
):
    group = pl.program_id(2)

    @pl.when(pl.program_id(1) == 0)
    def _():
        state[group] = jnp.zeros(state.shape[1:], _f32)

    b, c = b_ref[...], c_ref[...]
    states = b.shape[1]
    dot = functools.partial(_dot, dtype=b.dtype)
    scores = dot(c, b, _NT)
    for unit in range(cum_ref.shape[0] // per):
        u = _Unit(unit, per, width, x_ref, dt_ref, cum_ref)
        h = state[group, unit]
        start_ref[unit] = h
        within = by_head([dot(scores * decay, u.xd) for decay in u.decays])
        y = within + jnp.exp(u.cum) * dot(c, h) + d_ref[:, u.lanes] * u.x
        y_ref[:, u.lanes] = y.astype(y_ref.dtype)
        handed = by_head(
            [dot(b * u.weight(k, states), u.xd, _TN) for k in range(per)]
        )
        state[group, unit] = jnp.exp(u.last) * h + handed


def _bwd_kernel(
    x_ref, b_ref, c_ref, dt_ref, cum_ref, d_ref, dy_ref, start_ref,
    dxbc_ref, dcum_ref, ddt_ref, dd_ref, end_ref, d_state, dx, *,
    per, width,
):
    group = pl.program_id(2)

    @pl.when(pl.program_id(1) == 0)
    def _():
        d_state[group] = jnp.zeros(d_state.shape[1:], _f32)

    b, c = b_ref[...], c_ref[...]
    length, states = b.shape
    dot = functools.partial(_dot, dtype=b.dtype)
    scores = dot(c, b, _NT)
    d_scores = jnp.zeros((length, length), _f32)
    d_b = jnp.zeros((length, states), _f32)
    d_c = jnp.zeros((length, states), _f32)
    for unit in range(cum_ref.shape[0] // per):
        u = _Unit(unit, per, width, x_ref, dt_ref, cum_ref)
        dy = dy_ref[:, u.lanes]
        h, dh = start_ref[unit], d_state[group, unit]
        dy_in = dy.astype(_f32) * jnp.exp(u.cum)
        dxd, score_rows = [], []
        for k, decay in enumerate(u.decays):
            weight = u.weight(k, states)
            masked = scores * decay
            dxd.append(dot(masked, dy, _TN) + dot(b * weight, dh))
            d_masked = dot(only_head(dy, k, per), u.xd, _NT)
            d_scores += d_masked * decay
            score_rows.append(
                jnp.sum((d_masked * masked).T, axis=0, keepdims=True)
            )
            d_b += dot(only_head(u.xd, k, per), dh, _NT) * weight
        dxd = by_head(dxd)
        dx[:, u.lanes] = (
            dxd * u.dt + d_ref[:, u.lanes] * dy.astype(_f32)
        ).astype(dx.dtype)
        # sum_p dy y - sum_p dxd xd, as a row a head: sum_p dy y is the row
        # sums of d_masked o masked plus the states' part of y
        within = dy_in * dot(c, h) - dxd * u.xd.astype(_f32)
        d_c += dot(dy_in, h, _NT)
        dh = jnp.exp(u.last) * dh + dot(c, dy_in, _TN)
        d_state[group, unit] = dh
        # <H, dH> at this chunk's start = the previous chunk's <H', dH'>
        inner = jnp.sum(h * dh, axis=0, keepdims=True)
        for k, head, in_scores, in_states, by_dt, by_d in zip(
            range(per), u.heads, score_rows,
            _rows_by_head(within, width, per),
            _rows_by_head(dxd * u.x, width, per),
            _rows_by_head(dy.astype(_f32) * u.x, width, per),
        ):
            dcum_ref[head:head + 1, :] = in_scores + in_states
            ddt_ref[head:head + 1, :] = by_dt
            dd_ref[head:head + 1, :] = by_d
            end_ref[head:head + 1, :] = jnp.broadcast_to(
                jnp.sum(only_head(inner, k, per), axis=1, keepdims=True),
                (1, _LANES),
            )
    d_c = (d_c + dot(d_scores, b)).astype(dxbc_ref.dtype)
    d_b = (d_b + dot(d_scores, c, _TN)).astype(dxbc_ref.dtype)
    # the chunk's block of xBC's gradient stays while its groups are worked
    # through: each writes its three windows of it, at lanes only a branch a
    # group can name
    lanes, groups = dx.shape[1], d_state.shape[0]
    first_b = groups * lanes
    first_c = first_b + groups * states
    for g in range(groups):
        @pl.when(group == g)
        def _(g=g):
            dxbc_ref[:, g * lanes:(g + 1) * lanes] = dx[...]
            dxbc_ref[:, first_b + g * states:first_b + (g + 1) * states] = d_b
            dxbc_ref[:, first_c + g * states:first_c + (g + 1) * states] = d_c


class _Plan:
    """The block specs of one call over the grid (batch, step, group), from
    the shapes alone; ``chunk_of(step)`` is the chunk a step works on."""

    def __init__(self, xbc, rows, states, tile, chunk_of):
        self.batch, self.chunks, self.groups, heads, length = rows.shape
        self.per, span = tile
        self.width = span // self.per
        lanes = heads * self.width
        inner = self.groups * lanes
        self.units = heads // self.per
        self.grid = (self.batch, self.chunks, self.groups)
        first_b = inner // states
        first_c = first_b + self.groups

        def window(size, first):
            return pl.BlockSpec(
                (None, length, size),
                lambda i, j, g: (i, chunk_of(j), first + g),
            )

        # x, B and C as windows of xBC's rows; y and dy as windows of arrays
        # of their own; xBC's gradient a chunk's rows whole
        self.of_xbc = [window(lanes, 0), window(states, first_b),
                       window(states, first_c)]
        self.per_head = window(lanes, 0)
        self.whole = pl.BlockSpec(
            (None, length, xbc.shape[2]), lambda i, j, g: (i, chunk_of(j), 0)
        )
        self.dx = pltpu.VMEM((length, lanes), xbc.dtype)
        self.rows = pl.BlockSpec(
            (None, None, None, heads, length),
            lambda i, j, g: (i, chunk_of(j), g, 0, 0),
        )
        self.ends = pl.BlockSpec(
            (None, None, None, heads, _LANES),
            lambda i, j, g: (i, chunk_of(j), g, 0, 0),
        )
        self.per_lane = pl.BlockSpec((1, lanes), lambda i, j, g: (0, g))
        self.starts = pl.BlockSpec(
            (None, None, None, self.units, states, span),
            lambda i, j, g: (i, chunk_of(j), g, 0, 0, 0),
        )
        self.state = pltpu.VMEM(
            (self.groups, self.units, states, span), _f32
        )
        self.steps = self.chunks * length
        self.inner, self.span = inner, span

    def like(self, lanes, dtype):
        return jax.ShapeDtypeStruct((self.batch, self.steps, lanes), dtype)


def _forward(xbc, dt, cum, d, states, tile, interpret):
    """``xbc`` (batch, T, H P + 2 G N); ``dt``, ``cum`` (batch, chunks,
    groups, heads a group, L) float32; ``d`` (1, H P) float32."""
    plan = _Plan(xbc, cum, states, tile, lambda j: j)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, per=plan.per, width=plan.width),
        grid=plan.grid,
        in_specs=[*plan.of_xbc, plan.rows, plan.rows, plan.per_lane],
        out_specs=[plan.per_head, plan.starts],
        out_shape=[
            plan.like(plan.inner, xbc.dtype),
            jax.ShapeDtypeStruct(
                (*cum.shape[:3], plan.units, states, plan.span), _f32
            ),
        ],
        scratch_shapes=[plan.state],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
        name=SSD_FWD,
    )(xbc, xbc, xbc, dt, cum, d)


def _backward(xbc, dt, cum, d, dy, start, states, tile, interpret):
    chunks = cum.shape[1]
    plan = _Plan(xbc, cum, states, tile, lambda j: chunks - 1 - j)
    rows = jax.ShapeDtypeStruct(cum.shape, _f32)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, per=plan.per, width=plan.width),
        grid=plan.grid,
        in_specs=[
            *plan.of_xbc, plan.rows, plan.rows, plan.per_lane,
            plan.per_head, plan.starts,
        ],
        out_specs=[plan.whole, plan.rows, plan.rows, plan.rows, plan.ends],
        out_shape=[
            plan.like(xbc.shape[2], xbc.dtype),
            rows, rows, rows,
            jax.ShapeDtypeStruct((*cum.shape[:4], _LANES), _f32),
        ],
        scratch_shapes=[plan.state, plan.dx],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
        name=SSD_BWD,
    )(xbc, xbc, xbc, dt, cum, d, dy, start)


def _rows_by_group(steps, groups, length):
    """``(batch, T, heads)`` float32 as the kernels read it, lane-dense rows:
    ``(batch, chunks, groups, heads a group, L)``."""
    batch, count, heads = steps.shape
    return steps.reshape(
        batch, count // length, length, groups, heads // groups
    ).transpose(0, 1, 3, 4, 2)


def _steps_by_head(rows):
    """:func:`_rows_by_group` undone."""
    batch, chunks, groups, heads, length = rows.shape
    return rows.transpose(0, 1, 4, 2, 3).reshape(
        batch, chunks * length, groups * heads
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _ssd_core(xbc, dt, cum, d, groups, states, chunk, interpret):
    """``xbc`` (batch, T, H P + 2 G N) and the result (batch, T, H P) in the
    layer's layout; ``dt``, ``cum`` (batch, T, heads) float32; ``d`` (heads,)
    float32.  The shape tiles (:func:`scan_tile`)."""
    return _ssd_core_fwd(xbc, dt, cum, d, groups, states, chunk, interpret)[0]


def _operands(xbc, dt, cum, d, groups, states, chunk):
    heads = dt.shape[2]
    width = (xbc.shape[2] - 2 * groups * states) // heads
    return (
        _rows_by_group(dt, groups, chunk), _rows_by_group(cum, groups, chunk),
        jnp.repeat(d, width).reshape(1, heads * width),
        scan_tile(heads, width, groups, states),
    )


def _ssd_core_fwd(xbc, dt, cum, d, groups, states, chunk, interpret):
    *rows, tile = _operands(xbc, dt, cum, d, groups, states, chunk)
    y, start = _forward(xbc, *rows, states, tile, interpret)
    return y, (xbc, dt, cum, d, start)


def _ssd_core_bwd(groups, states, chunk, interpret, residuals, dy):
    xbc, dt, cum, d, start = residuals
    *rows, tile = _operands(xbc, dt, cum, d, groups, states, chunk)
    dxbc, dcum, ddt, dd, ends = _backward(
        xbc, *rows, dy.astype(xbc.dtype), start, states, tile, interpret
    )
    # a chunk's last running sum also scales the state it hands on: the
    # kernel gives <H, dH> at each chunk's start, which is the chunk
    # before's <H', dH'>; the last chunk hands nothing on
    ends = ends[..., 0]
    ends = jnp.concatenate([ends[:, 1:], jnp.zeros_like(ends[:, :1])], axis=1)
    dcum = dcum.at[..., -1].add(ends)
    return (
        dxbc, _steps_by_head(ddt), _steps_by_head(dcum),
        jnp.sum(dd, axis=(0, 1, 4)).reshape(d.shape),
    )


_ssd_core.defvjp(_ssd_core_fwd, _ssd_core_bwd)


def _chunked_plain(xbc, dt, cum, d, groups, states, chunk):
    """:func:`_ssd_core` in ``jax.numpy`` with the kernels' precisions and
    rounding points, for the shapes they do not tile; JAX differentiates
    it."""
    batch, steps, _ = xbc.shape
    heads = dt.shape[2]
    inner = xbc.shape[2] - 2 * groups * states
    dtype = xbc.dtype

    def chunked(v, *shape):
        return v.reshape(batch, steps // chunk, chunk, *shape)

    x, b, c = (
        chunked(v, groups, -1) for v in jnp.split(
            xbc, [inner, inner + groups * states], axis=-1
        )
    )
    x = chunked(x, groups, heads // groups, -1).astype(_f32)
    dt, cum = (chunked(v, groups, heads // groups) for v in (dt, cum))
    product = functools.partial(jnp.einsum, preferred_element_type=_f32)
    xd = (x * dt[..., None]).astype(dtype)
    # [chunk, group, head, t, s]: exp(cum_t - cum_s) at s <= t
    ahead = cum.transpose(0, 1, 3, 4, 2)
    ahead = ahead[..., :, None] - ahead[..., None, :]
    decay = jnp.where(
        jnp.tril(jnp.ones((chunk, chunk), bool)),
        jnp.exp(jnp.minimum(ahead, 0.0)), 0.0,
    )
    masked = product("bktgn,bksgn->bkgts", c, b)[:, :, :, None] * decay
    y = product("bkghts,bksghp->bktghp", masked.astype(dtype), xd)
    weight = jnp.exp(cum[:, :, -1:] - cum)
    handed = product(
        "bksghn,bksghp->bkghnp",
        (b[..., None, :] * weight[..., None]).astype(dtype), xd,
    )

    def carry(h, chunk_of):
        handed, last = chunk_of
        return jnp.exp(last)[..., None, None] * h + handed, h

    _, start = jax.lax.scan(
        carry, jnp.zeros_like(handed[:, 0]),
        (jnp.moveaxis(handed, 1, 0), jnp.moveaxis(cum[:, :, -1], 1, 0)),
    )
    y += jnp.exp(cum)[..., None] * product(
        "bktgn,bkghnp->bktghp", c, jnp.moveaxis(start, 0, 1).astype(dtype)
    )
    y += d.reshape(groups, heads // groups, 1) * x
    return y.astype(dtype).reshape(batch, steps, inner)


def ssd_chunked(
    xbc, dt, a, d, *, groups: int, states: int, chunk: int,
    interpret: bool | None = None,
):
    """The scan on one device, in the layer's layout.  ``xbc`` (batch, T,
    H P + 2 G N): ``x`` as H heads of P channels, then ``B`` and ``C`` as G
    groups of N states, a head reading group ``head // (H // G)``; ``dt``
    (batch, T, H) float32, positive; ``a`` (H,) negative; ``d`` (H,).
    Returns ``y`` (batch, T, H P).  ``T`` that is no whole number of chunks
    is padded with steps of ``dt = 0``, which decay nothing and add nothing.
    Differentiable in every argument.  ``interpret=None`` follows the
    default backend."""
    if interpret is None:
        interpret = on_mesh.default_interpret()
    heads = dt.shape[2]
    inner = xbc.shape[2] - 2 * groups * states
    if heads % groups or inner <= 0 or inner % heads:
        raise ValueError(
            f"{xbc.shape[2]} channels are not {heads} heads in {groups} "
            f"groups of {states} states"
        )
    steps = xbc.shape[1]
    pad = -steps % chunk
    if pad:
        xbc, dt = (jnp.pad(v, [(0, 0), (0, pad), (0, 0)]) for v in (xbc, dt))
    dt = dt.astype(_f32)
    decay = dt * a.astype(_f32)
    # the running sums inside each chunk as a product with a triangle of
    # ones: XLA's cumsum over 128 steps is a reduce-window that takes 1.9 ms
    # for these 2 MB on the chip, and its transpose as long again
    cum = jnp.einsum(
        "ts,bcsh->bcth", jnp.tril(jnp.ones((chunk, chunk), _f32)),
        decay.reshape(xbc.shape[0], -1, chunk, heads),
        precision=jax.lax.Precision.HIGHEST,
    ).reshape(decay.shape)
    d = d.astype(_f32)
    if scan_tile(heads, inner // heads, groups, states):
        y = _ssd_core(xbc, dt, cum, d, groups, states, chunk, interpret)
    else:
        y = _chunked_plain(xbc, dt, cum, d, groups, states, chunk)
    return y[:, :steps]


def ssd_scan(xbc, dt, a, d, *, groups: int, states: int, chunk: int):
    """:func:`ssd_chunked` under the registered mesh, mapped over the batch
    (``ops/on_mesh.py::over_batch``)."""
    return on_mesh.over_batch(
        lambda xbc, dt, a, d, interpret: ssd_chunked(
            xbc, dt, a, d, groups=groups, states=states, chunk=chunk,
            interpret=interpret,
        ),
        (xbc, dt), (a, d),
    )
