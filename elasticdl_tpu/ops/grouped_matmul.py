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

**The way back.**  On the full rung each pair has one row and the
permutations are gathers both ways.  Below it the rows of a buffer are added
into their tokens (``layers/moe.py``: the combine, the dispatch's transpose)
by a fourth kernel, :func:`sum_by_token`: a tile of tokens fetches its rows,
a contiguous span a group (:func:`token_spans`), and adds them as a one-hot
product; XLA's scatter-add, which it replaced, took a row at a time.

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
# the rows of a buffer added into their tokens (:func:`sum_by_token`): a
# kernel of the expert layer but no grouped matmul, so a name the three
# above (and ``perf/expert_rooflines.py``'s pattern for them) do not match
ROWS_SUM = "expert_rows_sum"

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
# :func:`sum_by_token`: tokens of an output tile (a grid step), rows fetched
# a group and round, and the rows a fetch may start at a multiple of (a
# bfloat16 tile's).  A round multiplies the staged rows of every block of
# groups that has a row in it into the tile, whatever else the block holds,
# so the matrix unit's work grows with both; a balanced load sends a tile
# ~8 rows a group in the cells (rows a rung / 2 / tiles / groups), and with
# the 0-15 rows before a span's start 32 hold most spans in one round.
# Swept on the chip at the four low rungs of the cells (PERF.md section 6,
# PR 49): 16 rows take more rounds (+19%); 48 and 64 stage rows for nothing
# (+36%, +75%, read before the fetches overlapped the products); 256 tokens
# halve the steps and double a round's product (+52% at 34,816 rows, -10%
# at 10,240)
_SUM_TOKENS = 128
_SPAN_ROWS = 32
_SPAN_ALIGN = 16


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


# ---- the rows of a buffer added into their tokens ---------------------------


def token_spans(group_ids, sizes, tokens: int, tile_rows: int):
    """``(lo, hi)``, each (token tiles, groups): the rows ``[lo, hi)`` of a
    buffer laid out by :func:`group_layout` that hold the pairs of each
    group whose tokens lie in each tile of ``_SUM_TOKENS`` tokens
    (``group_ids`` and ``sizes`` as :func:`group_order` takes and gives
    them).  A group's rows are in token order (the stable sort), so a
    tile's are one contiguous span of them, whatever the rung."""
    pairs = group_ids.shape[0]
    slots = pairs // tokens
    groups = sizes.shape[0]
    tiles = -(-tokens // _SUM_TOKENS)
    ids = jnp.pad(
        group_ids, (0, tiles * _SUM_TOKENS * slots - pairs),
        constant_values=groups,
    )
    counts = jnp.sum(
        ids.reshape(tiles, -1, 1) == jnp.arange(groups, dtype=jnp.int32),
        axis=1, dtype=jnp.int32,
    )
    group_tiles = jnp.maximum(-(-sizes // tile_rows), 1)
    row_starts = (jnp.cumsum(group_tiles) - group_tiles) * tile_rows
    lo = row_starts + jnp.cumsum(counts, axis=0) - counts
    return lo.astype(jnp.int32), (lo + counts).astype(jnp.int32)


def _rows_sum_kernel(
    lo, hi, rounds, rows, onehot_rows, out, stage, hot_stage, acc, sems,
    fetched, *, groups, span, count, weighted
):
    tile = pl.program_id(0)
    tiles = pl.num_programs(0)
    # the staged rows are multiplied a block of whole lanes' worth at a
    # time, and a block none of whose groups has a row in a round is neither
    # fetched nor multiplied in it: past the first round that is all but the
    # blocks of the groups a skewed routing sends most of a tile's tokens to
    blocks = [
        range(g, min(g + _LANES // span, groups))
        for g in range(0, groups, _LANES // span)
    ]

    def window(tile, group, k):
        # the k-th window of the group's span in the tile, the rows of it
        # the span holds, and where its fetch starts (inside the buffer)
        low = lo[tile * groups + group]
        first = low // _SPAN_ALIGN * _SPAN_ALIGN + k * span
        return (
            jnp.maximum(low, first),
            jnp.minimum(hi[tile * groups + group], first + span),
            pl.multiple_of(jnp.minimum(first, count - span), _SPAN_ALIGN),
        )

    def active(tile, block, k):
        held = False
        for group in block:
            low, high, _ = window(tile, group, k)
            held = jnp.logical_or(held, low < high)
        return held

    def copies(tile, group, k, slot):
        start = window(tile, group, k)[2]
        at = pl.ds(group * span, span)
        return (
            pltpu.make_async_copy(
                rows.at[pl.ds(start, span)], stage.at[slot, at],
                sems.at[slot, 0],
            ),
            pltpu.make_async_copy(
                onehot_rows.at[pl.ds(start, span)], hot_stage.at[slot, at],
                sems.at[slot, 1],
            ),
        )

    def fetch(tile, k, slot):
        for block in blocks:
            @pl.when(active(tile, block, k))
            def _():
                for group in block:
                    for copy in copies(tile, group, k, slot):
                        copy.start()

    # the rounds of all tiles are one sequence, each fetched into the slot
    # the one before it does not hold while that one is multiplied
    @pl.when(tile == 0)
    def _():
        fetched[0] = 0
        fetch(0, 0, 0)

    acc[...] = jnp.zeros_like(acc)

    def one_round(k, carry):
        slot = fetched[0] % 2
        more = k + 1 < rounds[tile]
        next_tile = jnp.where(more, tile, tile + 1)

        @pl.when(next_tile < tiles)
        def _():
            fetch(next_tile, jnp.where(more, k + 1, 0), 1 - slot)

        for block in blocks:
            @pl.when(active(tile, block, k))
            def _():
                lines = []
                for group in block:
                    for copy in copies(tile, group, k, slot):
                        copy.wait()
                    low, high, start = window(tile, group, k)
                    at = pl.ds(group * span, span)
                    row = start + jax.lax.broadcasted_iota(
                        jnp.int32, (span, hot_stage.shape[2]), 0
                    )
                    lines.append(jnp.where(
                        jnp.logical_and(row >= low, row < high),
                        hot_stage[slot, at, :], 0.0,
                    ))
                rest = jnp.concatenate(lines, axis=0)
                staged = stage[slot, pl.ds(block[0] * span, len(block) * span), :]
                contract = (((0,), (0,)), ((), ()))
                if staged.dtype != jnp.bfloat16:
                    acc[...] += jax.lax.dot_general(
                        rest, staged.astype(jnp.float32), contract,
                        precision=jax.lax.Precision.HIGHEST,
                        preferred_element_type=jnp.float32,
                    )
                    return
                for _ in range(3 if weighted else 1):
                    piece = rest.astype(jnp.bfloat16)
                    acc[...] += jax.lax.dot_general(
                        piece, staged, contract,
                        preferred_element_type=jnp.float32,
                    )
                    rest = rest - piece.astype(jnp.float32)

        fetched[0] += 1
        return carry

    jax.lax.fori_loop(0, rounds[tile], one_round, 0)
    out[...] = acc[...].astype(out.dtype)


def sum_by_token(
    rows, row_token, tokens: int, spans, row_weight=None, *,
    interpret: bool | None = None,
):
    """``out[t] = sum of row_weight[r] * rows[r] over the r with
    row_token[r] == t``: (tokens, d) in ``rows``' dtype, float32 products
    summed in float32 and rounded once.  A row whose token is ``tokens`` (a
    padding row) adds nothing, a token with no row reads zero;
    ``row_weight=None`` weighs every row 1.  ``spans`` =
    :func:`token_spans` of the layout the rows lie in.

    What XLA does as a scatter-add, a row at a time whatever the row's
    width (0.12 us a row on a v5e: 4.1 ms for a rung of 34,816 rows), as one
    kernel, ``expert_rows_sum``, with no (rows, d) or (tokens, d) float32
    array in HBM.  A grid step owns a tile of tokens.  The tile's rows of
    one group are contiguous in the buffer, so a round fetches a window of
    ``span`` rows of each group's span (``make_async_copy`` from the
    16-row boundary before its start; the next round's, or the next tile's
    first, is in flight while this one is multiplied), masks the rows of the
    window outside the span, and adds the staged rows into the tile as a
    product on the matrix unit with their lines of the tile's one-hot
    matrix: ``weight`` at the row's token, which XLA writes once as a
    (rows, 128) float32 array and the same windows fetch.  The
    groups are taken 128 staged rows at a time, and a block with no row in
    a round is neither fetched nor multiplied: a router that sends most of
    a tile to one group pays the later rounds for that group's block alone,
    a tile with no row pays nothing.  bfloat16
    rows are multiplied as they are, by the three bfloat16 pieces (8 + 8 + 8
    bits) a float32 weight is the sum of: each product is exact in float32,
    so no weight is rounded and what is summed are float32 products (one
    pass for weight 1).  Rows of another dtype take one float32 product at
    the highest precision.  Rounds go on while a group's span has rows
    left, so any routing is summed whole; ``lo``, ``hi`` and the rounds
    ride in scalar memory, 4 bytes a (tile, group) each.  (Zero times a row
    that is not finite is not zero: such a row spoils every token of the
    tiles whose windows hold it, where the scatter-add spoilt its own.)"""
    if interpret is None:
        interpret = on_mesh.default_interpret()
    token_tile, span = _SUM_TOKENS, _SPAN_ROWS
    lo, hi = spans
    tiles, groups = lo.shape
    count, width = rows.shape
    weight = (row_token < tokens).astype(jnp.float32)
    if row_weight is not None:
        weight = weight * row_weight
    # a row's line of the one-hot matrix of its tile: its weight at its token
    onehot_rows = jnp.where(
        (row_token % token_tile)[:, None]
        == jnp.arange(token_tile, dtype=jnp.int32),
        weight[:, None], 0.0,
    )
    if count < span:
        rows = jnp.pad(rows, ((0, span - count), (0, 0)))
        onehot_rows = jnp.pad(onehot_rows, ((0, span - count), (0, 0)))
        count = span
    first = lo // _SPAN_ALIGN * _SPAN_ALIGN
    rounds = jnp.maximum(jnp.max(-(-(hi - first) // span), axis=1), 1)
    staged = groups * span
    return pl.pallas_call(
        functools.partial(
            _rows_sum_kernel, groups=groups, span=span, count=count,
            weighted=row_weight is not None,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(tiles,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * 2,
            out_specs=pl.BlockSpec(
                (token_tile, width), lambda i, lo, hi, rounds: (i, 0)
            ),
            scratch_shapes=[
                pltpu.VMEM((2, staged, width), rows.dtype),
                pltpu.VMEM((2, staged, token_tile), jnp.float32),
                pltpu.VMEM((token_tile, width), jnp.float32),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.SMEM((1,), jnp.int32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((tokens, width), rows.dtype),
        compiler_params=_compiler_params(1),
        interpret=interpret,
        name=ROWS_SUM,
    )(
        lo.reshape(-1), hi.reshape(-1), rounds.astype(jnp.int32),
        rows, onehot_rows,
    )
