"""Grouped matmul over ragged row groups: the expert layer's hot op.

``rows`` of a buffer belong to ``num_groups`` groups (experts), each with a
weight matrix of its own; group sizes are data (the router's choice) while
every shape is static.  The layout (:func:`group_layout`) starts each group
on a multiple of ``tile_rows`` and gives every group at least one tile, so a
row tile belongs to exactly one group: the three Pallas kernels are plain
tiled matmuls whose weight (or weight-gradient) block is picked per row tile
by a scalar-prefetched ``tile_group`` table — no masking inside a tile, no
tile that straddles two groups.  Tiles past the last group carry the value
``num_groups``: they are skipped and read back as zeros.

Row tiles run innermost with the whole contraction in one block, so an
expert's matrix stays in VMEM across its consecutive tiles and is read from
HBM once; what streams is the row buffer.  Padding costs at most one tile a
group: ``rows = ceil(pairs / tile_rows) + num_groups`` tiles
(:func:`num_rows`) hold any routing, every pair to one group included.

**The ladder.**  That size is what *all* pairs need.  Where the groups laid
out here are fewer than the groups the pairs were routed over (a device that
holds a share of a layer's experts, a rank of an ``ep`` mesh), a balanced
routing sends here ``share = num_groups / routed_groups`` of the pairs, and a
buffer for all of them is walked almost empty by every pass between the
router and the combine.  :func:`ladder` therefore gives a short ascending
list of static buffer sizes derived from the shapes alone: a low rung that
holds ``LOW_RUNG_SHARES`` times the balanced share (plus the tile a group may
waste), one of twice its rows, then the full size.  The caller (``layers/moe.py``)
sorts the pairs
once (:func:`group_order`), reads off the device how many row tiles this
step's routing needs (:func:`tiles_needed`) and takes, on the device, the
smallest rung that holds them; :func:`group_layout` lays the pairs out at
the rows it is given.  "Nothing is ever dropped" rests on the last rung,
which is always ``num_rows`` of all pairs; where the groups here are all
the groups, it is the only rung and nothing is chosen.

Each kernel has a name the device trace's op line shows (as the flash
kernels do, ``ops/attention.py``): ``perf/`` reads them by it.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from elasticdl_tpu.ops import on_mesh

GMM_FWD = "expert_gmm_fwd"
GMM_DX = "expert_gmm_dx"
GMM_DW = "expert_gmm_dw"

# rows of a tile: what a group is padded to.  On the chip 128, 256 and 512
# run the kernels at the same rate (a group's matrix stays resident, so a
# small tile costs no weight traffic) and 128 pads least: +0.7% tokens/s
# and 0.15 GB less than 256 in olmoe_1b7b_seq4096 (PERF.md, PR 27).  A
# weight block is bounded to _MAX_BLOCK_BYTES
TILE_ROWS = 128
# the low rung of the ladder holds this many times the pairs a balanced
# routing sends to the groups laid out here.  Twice: a load-balanced router
# keeps a device's share of the pairs (the sum over its experts, steadier
# than any one expert's) well inside it, and a buffer twice the needed size
# costs what an empty tile costs, a skipped grid step.  One rung of twice
# its rows stands between it and the full size (PR 34): a router that
# collapses onto one held expert, as routers without a warm-up do in their
# first dozens of steps, overflows the low rung by a few thousand rows, and
# with nothing between that step walked the full size, 6.6 times the rows
# and 22 ms a layer in ``joyai_flash_seq8192``, so that a window's rate
# followed the routing of its seed (PERF.md section 6).  Past four times
# its share a device is the straggler of its mesh whatever its buffer, and
# every rung is one more compiled copy of each expert layer's routed part
# (forward and backward); the full rung costs what every step cost before
# the ladder
LOW_RUNG_SHARES = 2
_MAX_BLOCK_BYTES = 4 * 1024 * 1024
_VMEM_LIMIT_BYTES = 64 * 1024 * 1024
_LANES = 128


class GroupOrder(NamedTuple):
    """The pairs sorted by group: what every rung's layout starts from."""

    # (pairs,) the pairs in group order; a stable sort, so a group's pairs
    # stay in token order
    order: jax.Array
    # (num_groups,) pairs of each group; pairs of no group are not counted
    sizes: jax.Array


class GroupLayout(NamedTuple):
    """Where each (token, slot) pair sits in the grouped row buffer."""

    # (rows,) the pair held by each row; ``pairs`` (out of range) on padding
    row_pair: jax.Array
    # (pairs,) the row of each pair; 0 for a pair of no group (masked by
    # the caller: its weight is zero).  None in a layout made ``by_rows``
    pair_row: jax.Array | None
    # (rows // tile_rows,) the group of each row tile; ``num_groups`` = none
    tile_group: jax.Array


def num_rows(pairs: int, num_groups: int, tile_rows: int) -> int:
    """Rows that hold any assignment of ``pairs`` to ``num_groups``."""
    return (-(-pairs // tile_rows) + num_groups) * tile_rows


def ladder(
    pairs: int, num_groups: int, routed_groups: int, tile_rows: int
) -> tuple[int, ...]:
    """The static buffer sizes (rows, ascending) for ``pairs`` routed over
    ``routed_groups`` groups of which ``num_groups`` are laid out here.  The
    last is ``num_rows(pairs, ...)``, which holds any routing; before it, a
    rung for ``LOW_RUNG_SHARES`` times the balanced share of the pairs and
    one of twice its rows, where they are smaller."""
    full = num_rows(pairs, num_groups, tile_rows)
    balanced = -(-LOW_RUNG_SHARES * pairs * num_groups // routed_groups)
    low = num_rows(balanced, num_groups, tile_rows)
    return tuple(rows for rows in (low, 2 * low) if rows < full) + (full,)


def group_order(group_ids, num_groups: int) -> GroupOrder:
    """``group_ids``: (pairs,) int32 in ``[0, num_groups]``; ``num_groups``
    marks a pair that belongs to none of these groups (another rank's
    expert)."""
    order = jnp.argsort(group_ids, stable=True).astype(jnp.int32)
    sizes = jnp.bincount(group_ids, length=num_groups + 1)[:num_groups]
    return GroupOrder(order, sizes.astype(jnp.int32))


def tiles_needed(sizes, tile_rows: int):
    """Row tiles that hold groups of ``sizes``: every group at least one."""
    return jnp.sum(jnp.maximum(-(-sizes // tile_rows), 1))


def group_layout(
    group_ids, num_groups: int, tile_rows: int, rows: int,
    order: GroupOrder | None = None, by_rows: bool = False,
) -> GroupLayout:
    """The layout of ``group_ids`` (as :func:`group_order` takes them) in a
    buffer of ``rows`` rows, a rung of the :func:`ladder`: the caller has
    seen that ``tiles_needed`` fit (``num_rows`` always do).  A pair of no
    group gets no row.  ``by_rows``: for a caller that reads no row by its
    pair.  ``pair_row`` is None, and the rows are laid out from their own
    side, a gather of ``rows`` entries where the other form scatters
    ``pairs``: nothing of the layout is then sized by the pairs."""
    pairs = group_ids.shape[0]
    order, sizes = group_order(group_ids, num_groups) if order is None else order
    starts = jnp.cumsum(sizes) - sizes
    tiles = jnp.maximum(-(-sizes // tile_rows), 1)
    tile_ends = jnp.cumsum(tiles)
    row_starts = (tile_ends - tiles) * tile_rows
    tile = jnp.arange(rows // tile_rows, dtype=jnp.int32)
    tile_group = jnp.searchsorted(tile_ends, tile, side="right").astype(jnp.int32)
    if by_rows:
        # row ``i`` of a group's tiles holds the ``i``-th of the group's
        # pairs in sorted order, if the group has so many
        group = jnp.minimum(tile_group, num_groups - 1)
        in_group = (
            tile[:, None] * tile_rows - row_starts[group][:, None]
            + jnp.arange(tile_rows, dtype=jnp.int32)
        )
        held = (tile_group < num_groups)[:, None] & (
            in_group < sizes[group][:, None]
        )
        sorted_pair = jnp.minimum(starts[group][:, None] + in_group, pairs - 1)
        row_pair = jnp.where(held, order[sorted_pair], pairs).reshape(rows)
        return GroupLayout(row_pair, None, tile_group)
    sorted_ids = group_ids[order]
    grouped = sorted_ids < num_groups
    safe = jnp.minimum(sorted_ids, num_groups - 1)
    sorted_row = (
        row_starts[safe] + jnp.arange(pairs, dtype=jnp.int32) - starts[safe]
    )
    # a pair of no group is dropped from the rows (index out of range)
    sorted_row = jnp.where(grouped, sorted_row, rows)
    row_pair = jnp.full((rows,), pairs, jnp.int32).at[sorted_row].set(
        order, mode="drop", unique_indices=True
    )
    pair_row = jnp.zeros((pairs,), jnp.int32).at[order].set(
        jnp.where(grouped, sorted_row, 0), unique_indices=True
    )
    return GroupLayout(row_pair, pair_row, tile_group)


def _pick_cols(rows_of_block: int, cols: int, itemsize: int) -> int:
    """Columns of a weight block: all of them where the block stays under
    ``_MAX_BLOCK_BYTES``, else the largest lane-aligned divisor that does."""
    if rows_of_block * cols * itemsize <= _MAX_BLOCK_BYTES:
        return cols
    best = cols
    for candidate in range(_LANES, cols, _LANES):
        if cols % candidate == 0 and (
            rows_of_block * candidate * itemsize <= _MAX_BLOCK_BYTES
        ):
            best = candidate
    return best


def _compiler_params(grid_rank: int):
    return pltpu.CompilerParams(
        dimension_semantics=("parallel",) * (grid_rank - 1) + ("arbitrary",),
        vmem_limit_bytes=_VMEM_LIMIT_BYTES,
    )


def _group_of(tile, table, num_groups):
    """The block index of a tile's group; a tile of no group (skipped by the
    kernel) points at the last group's block so nothing new is fetched."""
    return jnp.minimum(table[tile], num_groups - 1)


def _gmm_kernel(tile_group, lhs, rhs, out, *, num_groups, transpose_rhs):
    active = tile_group[pl.program_id(1)] < num_groups
    contract = (((1,), (1 if transpose_rhs else 0,)), ((), ()))

    @pl.when(active)
    def _():
        out[...] = jax.lax.dot_general(
            lhs[...], rhs[...], contract, preferred_element_type=jnp.float32
        ).astype(out.dtype)

    @pl.when(jnp.logical_not(active))
    def _():
        out[...] = jnp.zeros_like(out)


def _gmm(lhs, rhs, tile_group, *, transpose_rhs, tile_rows, interpret, name):
    """``out[tile] = lhs[tile] @ rhs[group of tile]`` (or ``@ rhs[g].T``)."""
    rows, inner = lhs.shape
    num_groups = rhs.shape[0]
    cols = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    block_cols = _pick_cols(inner, cols, rhs.dtype.itemsize)

    if transpose_rhs:
        rhs_spec = pl.BlockSpec(
            (None, block_cols, inner),
            lambda j, i, t: (_group_of(i, t, num_groups), j, 0),
        )
    else:
        rhs_spec = pl.BlockSpec(
            (None, inner, block_cols),
            lambda j, i, t: (_group_of(i, t, num_groups), 0, j),
        )
    return pl.pallas_call(
        functools.partial(
            _gmm_kernel, num_groups=num_groups, transpose_rhs=transpose_rhs
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(cols // block_cols, rows // tile_rows),
            in_specs=[
                pl.BlockSpec((tile_rows, inner), lambda j, i, t: (i, 0)),
                rhs_spec,
            ],
            out_specs=pl.BlockSpec(
                (tile_rows, block_cols), lambda j, i, t: (i, j)
            ),
        ),
        out_shape=jax.ShapeDtypeStruct((rows, cols), lhs.dtype),
        compiler_params=_compiler_params(2),
        interpret=interpret,
        name=name,
    )(tile_group, lhs, rhs)


def _tgmm_kernel(tile_group, lhs, grad, out, *, num_groups):
    tile = pl.program_id(2)
    group = tile_group[tile]
    previous = tile_group[jnp.maximum(tile - 1, 0)]
    first = jnp.logical_or(tile == 0, group != previous)
    active = group < num_groups

    def product():
        return jax.lax.dot_general(
            lhs[...], grad[...], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(jnp.logical_and(active, first))
    def _():
        out[...] = product()

    @pl.when(jnp.logical_and(active, jnp.logical_not(first)))
    def _():
        out[...] += product()


def _tgmm(lhs, grad, tile_group, num_groups, *, tile_rows, interpret):
    """``out[g] = sum over g's tiles of lhs[tile].T @ grad[tile]`` in
    float32 (the parameters' own dtype: no cast of a 403M-element tree).
    Every group has a tile, so every block of ``out`` is written."""
    rows, inner = lhs.shape
    cols = grad.shape[1]
    block_cols = _pick_cols(inner, cols, 4)
    block_inner = _pick_cols(block_cols, inner, 4)
    return pl.pallas_call(
        functools.partial(_tgmm_kernel, num_groups=num_groups),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(inner // block_inner, cols // block_cols, rows // tile_rows),
            in_specs=[
                pl.BlockSpec((tile_rows, block_inner), lambda k, j, i, t: (i, k)),
                pl.BlockSpec((tile_rows, block_cols), lambda k, j, i, t: (i, j)),
            ],
            out_specs=pl.BlockSpec(
                (None, block_inner, block_cols),
                lambda k, j, i, t: (_group_of(i, t, num_groups), k, j),
            ),
        ),
        out_shape=jax.ShapeDtypeStruct((num_groups, inner, cols), jnp.float32),
        compiler_params=_compiler_params(3),
        interpret=interpret,
        name=GMM_DW,
    )(tile_group, lhs, grad)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _grouped_matmul(lhs, rhs, tile_group, tile_rows, interpret):
    # the weights are cast here, inside the rule, so that their gradient
    # leaves the weight-gradient kernel in their own dtype (float32
    # parameters: no bfloat16 round trip of the accumulated sum)
    return _gmm(
        lhs, rhs.astype(lhs.dtype), tile_group, transpose_rhs=False,
        tile_rows=tile_rows, interpret=interpret, name=GMM_FWD,
    )


def _grouped_matmul_fwd(lhs, rhs, tile_group, tile_rows, interpret):
    out = _grouped_matmul(lhs, rhs, tile_group, tile_rows, interpret)
    return out, (lhs, rhs, tile_group)


def _grouped_matmul_bwd(tile_rows, interpret, residuals, grad):
    lhs, rhs, tile_group = residuals
    d_lhs = _gmm(
        grad, rhs.astype(grad.dtype), tile_group, transpose_rhs=True,
        tile_rows=tile_rows, interpret=interpret, name=GMM_DX,
    )
    d_rhs = _tgmm(
        lhs, grad, tile_group, rhs.shape[0], tile_rows=tile_rows,
        interpret=interpret,
    )
    return d_lhs, d_rhs.astype(rhs.dtype), None


_grouped_matmul.defvjp(_grouped_matmul_fwd, _grouped_matmul_bwd)


def grouped_matmul(
    lhs, rhs, tile_group, *, tile_rows: int = TILE_ROWS,
    interpret: bool | None = None,
):
    """``lhs``: (rows, k) laid out by :func:`group_layout`; ``rhs``:
    (num_groups, k, n); returns (rows, n) in ``lhs``'s dtype with float32
    accumulation.  Rows of a tile of no group come back zero.
    Differentiable in ``lhs`` and ``rhs``: the input gradient is the same
    kernel against ``rhs`` transposed in place, the weight gradient
    accumulates each group's tiles in float32.  ``interpret=None`` follows
    the default backend (interpreted on the CPU, compiled on a TPU)."""
    if interpret is None:
        interpret = on_mesh.default_interpret()
    if lhs.shape[0] != tile_group.shape[0] * tile_rows:
        raise ValueError(
            f"{lhs.shape[0]} rows are not {tile_group.shape[0]} tiles of "
            f"{tile_rows}"
        )
    return _grouped_matmul(lhs, rhs, tile_group, tile_rows, interpret)
