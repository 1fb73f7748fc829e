"""Learned sparse attention (DeepSeek sparse attention, DeepSeek-V3.2-Exp
technical report): the indexer's scores, the exact selection, and the
indexer's loss, as Pallas kernels beside the flash kernels.

A small *indexer* scores every visible key for every query,
``I[t, s] = sum_j w[t, j] * relu(qi[t, j] . ki[s])`` (eq. 1: a few narrow
heads against ONE key head, no softmax), each query keeps its ``topk``
best keys, the main attention runs over that set alone (eq. 2:
``ops/attention.py::selected_flash_attention``), and the indexer is trained
to the main attention's own distribution over the set (eq. 4:
:func:`indexer_kl`).  docs/designs/sparse_attention.md says why this form.

What crosses HBM is the selection as an int8 mask by key blocks,
``mask[b, s // block, t, s % block]`` (the block a grid cell of the flash
kernels wants is then a leading index and two tile-aligned dimensions), and
never a score: :func:`index_select` keeps one block of queries' scores in
VMEM, finds each row's ``topk``-th value EXACTLY by a radix select over the
float32 bits (32 counting passes; a tie at the last place goes to the lower
key index by a second search over the index bits, which runs in a block of
queries that has such a tie), and writes the mask.  ``lax.top_k`` would sort
16,384 rows of 16,384 scores a layer out of a 1 GB array.  The whole answer
of the search is two int32 a query (the threshold and the tie cut):
:func:`index_select_threshold` hands them out and
:func:`index_select_hinted` checks them by ONE counting pass instead of
searching again, which is how a recomputed layer gets its mask back
(``layers/recompute.py``).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from elasticdl_tpu.ops.attention import (
    _LANES,
    _lanes_to,
    _loop,
    _pick_block,
    _row_to_lanes,
    _scores,
    validate_gqa_heads,
)
from elasticdl_tpu.ops import on_mesh

# each ``pallas_call``'s name, which the device's op line shows
INDEX_SELECT = "dsa_index"
INDEX_SELECT_HINTED = "dsa_index_hinted"  # no ``^dsa_index\b``: read apart
INDEXER_KL = "dsa_kl"

_INT_MIN = np.int32(-(2**31))
_SEQUENTIAL = pltpu.CompilerParams(
    dimension_semantics=("parallel", "arbitrary"),
    vmem_limit_bytes=96 << 20,
)
_KL_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "arbitrary", "arbitrary"),
    vmem_limit_bytes=96 << 20,
)


def _ordered(x):
    """float32 as int32 keys in the same order (``-0.0`` is made ``0.0``
    first: the two compare equal as floats)."""
    bits = jax.lax.bitcast_convert_type(
        jnp.where(x == 0.0, 0.0, x), jnp.int32
    )
    return bits ^ ((bits >> 31) & np.int32(0x7FFFFFFF))


def _unordered(key):
    """:func:`_ordered`'s inverse (the map is an involution on the bits)."""
    return jax.lax.bitcast_convert_type(
        key ^ ((key >> 31) & np.int32(0x7FFFFFFF)), jnp.float32
    )


def _index_scores(qi_ref, w_cols, kb, products=None):
    """``sum_j w_j relu(qi_j @ kb^T)`` for a block of keys (``qi_ref``: a
    block ``(1, heads, rows, width)``): float32 sums of exact products,
    ReLU, weights and their sum in float32.  ``products``: a scratch
    ``(heads, rows, keys)`` that is left each head's product as it was
    before its ReLU."""
    total = None
    for j, w in enumerate(w_cols):
        s = _scores(qi_ref[0, j], kb)
        if products is not None:
            products[j] = s
        s = jnp.maximum(s, 0.0) * _lanes_to(w, kb.shape[0])
        total = s if total is None else total + s
    return total


def _row_sum(x):
    """``x``'s sum along the lanes as a lane-replicated column."""
    return jnp.broadcast_to(
        jnp.sum(x, axis=1, keepdims=True), (x.shape[0], _LANES)
    )


def _column_row(col):
    """A lane-replicated column ``(n, <= _LANES)`` as the ``(1, n)`` row an
    output block of one value a query is."""
    return jnp.broadcast_to(col[:, 0:1], (col.shape[0], _LANES)).T[0:1]


def _index_kernel(
    qi_ref, ki_ref, w_ref, *refs, topk, block_q, block_k, seq, hinted,
):
    """One (batch, q-block) cell: the block's index scores against every
    visible key into VMEM as ordered int32 keys, the per-row threshold
    ``kth`` and tie cut ``cut``, then the mask (``key > kth or (key == kth
    and s <= cut)``), the logsumexp of the selected scores and the two
    counters a row.

    A pass of the select runs only where its answer is not known.  The tie
    search runs in a block that has a tie to cut (some row holds more
    entries at ``kth`` than it still needs); elsewhere every tied entry is
    chosen and ``cut`` is the last index.  ``hinted``: the cell is handed a
    ``(kth, cut)`` a row and counts what it selects; where every row counts
    exactly ``min(t + 1, topk)`` the hint IS the selection (``k`` entries
    none of which a left-out entry precedes in the order (score down, index
    up) are the top ``k``, whatever search found them) and no search runs;
    where any row counts otherwise the block is searched as if unhinted."""
    if hinted:
        kth_in_ref, cut_in_ref, *refs = refs
    mask_ref, lse_ref, count_ref, tie_ref, flag_ref, *refs = refs
    if not hinted:
        kth_ref, cut_ref, *refs = refs
    key_scr, pick_scr = refs
    i = pl.program_id(1)
    heads = qi_ref.shape[1]
    num_kb = seq // block_k
    row0 = i * block_q
    live = (row0 + block_q + block_k - 1) // block_k  # blocks a row can see
    shape = (block_q, block_k)
    rows = row0 + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    # the passes over the keys in VMEM work a group of lanes at a time, so
    # what a pass accumulates a row stays a column of registers
    width = min(block_k, _LANES)
    column = (block_q, width)
    row_col = row0 + jax.lax.broadcasted_iota(jnp.int32, column, 0)
    lane_col = jax.lax.broadcasted_iota(jnp.int32, column, 1)
    w_cols = [_row_to_lanes(w_ref[j]) for j in range(heads)]

    def groups(jb):
        """A key block's lane groups: ``(lanes, first key index)``."""
        for g in range(block_k // width):
            yield slice(g * width, (g + 1) * width), jb * block_k + g * width

    def score(jb, top):
        start = pl.multiple_of(jb * block_k, block_k)
        total = _index_scores(
            qi_ref, w_cols, ki_ref[0, pl.ds(start, block_k), :]
        )
        key = jnp.where(start + lane <= rows, _ordered(total), _INT_MIN)
        key_scr[jb] = key
        for lanes, _ in groups(jb):
            top = jnp.maximum(top, key[:, lanes])
        return top

    top = jax.lax.fori_loop(
        0, live, score, jnp.full(column, _INT_MIN, jnp.int32)
    )

    def along(reduce, x):  # a column's lanes reduced, as a column again
        return jnp.broadcast_to(reduce(x, axis=1, keepdims=True), column)

    top = _unordered(along(jnp.max, top))

    def count(*preds):
        """Per row, over the live blocks: how many entries each of
        ``preds(keys, columns)`` holds for, as int32 columns."""
        def body(jb, accs):
            for lanes, first in groups(jb):
                key, col = key_scr[jb, :, lanes], first + lane_col
                accs = tuple(
                    acc + pred(key, col).astype(jnp.int32)
                    for acc, pred in zip(accs, preds)
                )
            return accs
        accs = jax.lax.fori_loop(
            0, live, body, (jnp.zeros(column, jnp.int32),) * len(preds)
        )
        return [along(jnp.sum, acc) for acc in accs]

    want = jnp.minimum(row_col + 1, topk)

    def selects(key, col, kth, cut):
        """Where ``(kth, cut)`` keeps a visible key."""
        return (col <= row_col) & (
            (key > kth) | ((key == kth) & (col <= cut))
        )

    def search():
        """``pick_scr``: the rows' ``kth``, ``cut`` and whether a tie was
        cut.  Returns whether the block's tie search ran."""
        # the k-th largest key a row, k = min(t + 1, topk): the largest
        # value with at least k entries at or above it, built from its top
        # bit down (keys offset to unsigned order: INT_MIN is 0)
        kth = jnp.full(column, _INT_MIN, jnp.int32)
        for bit in range(31, -1, -1):
            cand = kth ^ _INT_MIN if bit == 31 else kth + np.int32(1 << bit)
            (enough,) = count(lambda key, col, c=cand: key >= c)
            kth = jnp.where(enough >= want, cand, kth)
        above, tied = count(
            lambda key, col: key > kth, lambda key, col: key == kth
        )
        need = want - above  # of the tied entries, from the lowest index up
        pick_scr[0] = kth
        pick_scr[1] = jnp.full(column, seq - 1, jnp.int32)
        pick_scr[2] = (tied > need).astype(jnp.int32)
        cuts = jnp.max(tied - need) > 0

        @pl.when(cuts)
        def _cut():
            # the largest index d with fewer than ``need`` tied entries
            # before it
            cut = jnp.zeros(column, jnp.int32)
            for bit in range(max(seq - 1, 1).bit_length() - 1, -1, -1):
                cand = cut + np.int32(1 << bit)
                (before,) = count(
                    lambda key, col, c=cand: (key == kth) & (col < c)
                )
                cut = jnp.where(before <= need - 1, cand, cut)
            pick_scr[1] = cut

        return cuts

    if hinted:
        kth, cut = (
            _row_to_lanes(ref[0])[:, :width] for ref in (kth_in_ref, cut_in_ref)
        )
        chosen, beyond = count(
            lambda key, col: selects(key, col, kth, cut),
            lambda key, col: (col <= row_col) & (key == kth) & (col > cut),
        )
        pick_scr[0] = kth
        pick_scr[1] = cut
        pick_scr[2] = (beyond > 0).astype(jnp.int32)
        flag = jnp.min((chosen == want).astype(jnp.int32)) > 0  # it held

        @pl.when(jnp.logical_not(flag))
        def _fall_back():
            search()
    else:
        flag = search()
    kth, cut = pick_scr[0], pick_scr[1]

    def write(jb, carry):
        total, kept = carry
        for lanes, first in groups(jb):
            key, col = key_scr[jb, :, lanes], first + lane_col
            chosen = selects(key, col, kth, cut)
            mask_ref[0, jb, :, lanes] = jnp.where(chosen, 1, 0).astype(
                mask_ref.dtype
            )
            total = total + jnp.where(
                chosen, jnp.exp(_unordered(key) - top), 0.0
            )
            kept = kept + chosen.astype(jnp.int32)
        return total, kept

    total, kept = jax.lax.fori_loop(
        0, live, write,
        (jnp.zeros(column, jnp.float32), jnp.zeros(column, jnp.int32)),
    )

    def blank(jb):
        mask_ref[0, jb] = jnp.zeros(shape, mask_ref.dtype)

    _loop(live, num_kb, blank)

    lse_ref[0] = _column_row(top + jnp.log(along(jnp.sum, total)))
    count_ref[0] = _column_row(along(jnp.sum, kept).astype(jnp.float32))
    tie_ref[0] = _column_row(pick_scr[2].astype(jnp.float32))
    flag_ref[0] = jnp.full((1, block_q), flag.astype(jnp.float32))
    if not hinted:
        kth_ref[0] = _column_row(kth)
        cut_ref[0] = _column_row(cut)


def _index_call(qi, ki, w, topk, block_k, block_q, interpret, threshold=None):
    """``dsa_index``, or ``dsa_index_hinted`` where a ``threshold`` ``(kth,
    cut)`` is handed in: ``(mask, lse, kept, ties, flag)`` and, unhinted,
    ``(kth, cut)``."""
    qi, ki, w = (
        jax.lax.stop_gradient(jnp.asarray(x)) for x in (qi, ki, w)
    )
    batch, seq, heads, width = qi.shape
    if interpret is None:
        interpret = on_mesh.default_interpret()
    block_k = _pick_block(seq, block_k)
    block_q = _pick_block(seq, block_q)
    num_kb = seq // block_k
    hinted = threshold is not None
    rows = jax.ShapeDtypeStruct((batch, 1, seq), jnp.float32)
    picks = jax.ShapeDtypeStruct((batch, 1, seq), jnp.int32)
    row_spec = pl.BlockSpec((1, 1, block_q), lambda b, i: (b, 0, i))
    made = pl.pallas_call(
        functools.partial(
            _index_kernel, topk=topk, block_q=block_q, block_k=block_k,
            seq=seq, hinted=hinted,
        ),
        grid=(batch, seq // block_q),
        in_specs=[
            pl.BlockSpec(
                (1, heads, block_q, width), lambda b, i: (b, 0, i, 0)
            ),
            pl.BlockSpec((1, seq, width), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((heads, 1, block_q), lambda b, i: (b, 0, i)),
        ] + [row_spec] * (2 * hinted),
        out_specs=[
            pl.BlockSpec(
                (1, num_kb, block_q, block_k), lambda b, i: (b, 0, i, 0)
            ),
        ] + [row_spec] * (4 if hinted else 6),
        out_shape=[
            jax.ShapeDtypeStruct((batch, num_kb, seq, block_k), jnp.int8),
            rows, rows, rows, rows,
        ] + [picks] * (0 if hinted else 2),
        scratch_shapes=[
            pltpu.VMEM((num_kb, block_q, block_k), jnp.int32),
            pltpu.VMEM((3, block_q, min(block_k, _LANES)), jnp.int32),
        ],
        compiler_params=_SEQUENTIAL,
        interpret=interpret,
        name=INDEX_SELECT_HINTED if hinted else INDEX_SELECT,
    )(
        qi.transpose(0, 2, 1, 3),
        ki,
        w.astype(jnp.float32).transpose(0, 2, 1).reshape(
            batch * heads, 1, seq
        ),
        *(
            jax.lax.stop_gradient(x).astype(jnp.int32)[:, None, :]
            for x in (threshold or ())
        ),
    )
    mask, *per_row = made
    return (mask, *(x[:, 0] for x in per_row))


_STATIC = dict(static_argnums=(3, 4, 5, 6), inline=True)


@functools.partial(jax.jit, **_STATIC)
def index_select(
    qi, ki, w, topk: int, block_k: int = 512, block_q: int = 128,
    interpret: bool | None = None,
):
    """The indexer's scores and the exact selection.  ``qi`` ``(batch, seq,
    heads, width)`` the indexer's queries, ``ki`` ``(batch, seq, width)`` its
    one key head, ``w`` ``(batch, seq, heads)`` float32 head weights.
    Returns ``mask`` ``(batch, seq / block, seq, block)`` int8 (1 where query
    ``t`` keeps key ``s <= t``: the ``min(t + 1, topk)`` largest scores, a
    tie at the last place to the lower ``s``), ``lse`` ``(batch, seq)`` the
    logsumexp of a query's selected scores, and per query the keys it kept
    and whether a tie was broken at the last place (float32 0 / 1).
    No gradient passes through any of them."""
    return _index_call(qi, ki, w, topk, block_k, block_q, interpret)[:4]


@functools.partial(jax.jit, **_STATIC)
def index_select_threshold(
    qi, ki, w, topk: int, block_k: int = 512, block_q: int = 128,
    interpret: bool | None = None,
):
    """:func:`index_select` with what its search found: ``(mask, lse, kept,
    ties, searched, (kth, cut))``.  ``searched`` ``(batch, seq)`` is 1.0 for
    the queries of a block whose tie search ran; ``kth`` and ``cut`` are
    int32 a query, the whole answer of the select (a key is kept where its
    ordered score is above ``kth``, or at it with an index up to ``cut``):
    what :func:`index_select_hinted` takes."""
    *made, kth, cut = _index_call(qi, ki, w, topk, block_k, block_q, interpret)
    return (*made, (kth, cut))


@functools.partial(jax.jit, static_argnums=(4, 5, 6, 7), inline=True)
def index_select_hinted(
    qi, ki, w, threshold, topk: int, block_k: int = 512, block_q: int = 128,
    interpret: bool | None = None,
):
    """:func:`index_select`'s ``(mask, lse, kept, ties)`` and ``held``
    ``(batch, seq)``, from a ``threshold`` an earlier call on (nearly) the
    same operands found (``dsa_index_hinted``): a block of queries whose
    every row the threshold selects exactly ``min(t + 1, topk)`` keys for is
    written from it, ``held`` 1.0, after one counting pass; any other block
    is searched as :func:`index_select` searches it, ``held`` 0.0.  Either
    way the result is the exact selection of THESE operands' scores."""
    return _index_call(
        qi, ki, w, topk, block_k, block_q, interpret, tuple(threshold)
    )


def transpose_mask(mask, block_q: int = 512):
    """The mask by query blocks, ``[b, t // block_q, s, t % block_q]``: what
    dK/dV's transposed scores read.  One int8 transpose in XLA."""
    batch, num_kb, seq, block_k = mask.shape
    block_q = _pick_block(seq, block_q)
    return (
        mask.reshape(batch, num_kb, seq // block_q, block_q, block_k)
        .transpose(0, 2, 1, 4, 3)
        .reshape(batch, seq // block_q, num_kb * block_k, block_q)
    )


def dense_mask(mask):
    """``(batch, seq, seq)`` boolean ``[b, t, s]`` of the blocked mask: for
    tests and counters, never on the train path."""
    batch, num_kb, seq, block_k = mask.shape
    return mask.transpose(0, 2, 1, 3).reshape(batch, seq, seq) != 0


# ---- the indexer's loss ----------------------------------------------------


def _kl_kernel(
    q_ref, k_ref, lse_ref, mask_ref, qi_ref, ki_ref, w_ref, lsei_ref, *refs,
    sm_scale, block_q, block_k, num_kb, group, with_grads,
):
    """One (batch, q-block, k-block) cell of ``sum_t KL(p_t || softmax_S
    I_t)``: the main attention's probabilities rebuilt head by head from
    the saved logsumexp and averaged (``p``: sums to one over a row's set),
    the block's index scores again, the row's ``sum p (log p - log
    softmax I)`` accumulated along k; with ``with_grads`` also the
    gradient to the indexer's queries, weights (accumulated along k) and
    keys (a part a q-block, summed outside), ``dI = softmax_S I - p``; a
    head's ``ds`` needs every head's sum first, so the index products are
    kept in VMEM (``s_scr``) between the two and made once a cell."""
    s_scr = None
    if with_grads:
        kl_ref, dqi_ref, dw_ref, dki_ref, kl_scr, dqi_scr, dw_scr, s_scr = refs
    else:
        kl_ref, kl_scr = refs
    i, j = pl.program_id(1), pl.program_id(2)
    heads, index_heads = q_ref.shape[0], qi_ref.shape[1]

    @pl.when(j == 0)
    def _init():
        kl_scr[...] = jnp.zeros_like(kl_scr)
        if with_grads:
            dqi_scr[...] = jnp.zeros_like(dqi_scr)
            dw_scr[...] = jnp.zeros_like(dw_scr)

    @pl.when(j * block_k < (i + 1) * block_q)
    def _block():
        chosen = mask_ref[0, 0].astype(jnp.int32) != 0

        def head(h, total):
            s = _scores(q_ref[h], k_ref[h // group], sm_scale)
            return total + jnp.exp(
                s - _lanes_to(_row_to_lanes(lse_ref[h]), block_k)
            )

        p = jax.lax.fori_loop(
            0, heads, head, jnp.zeros((block_q, block_k), jnp.float32)
        )
        p = jnp.where(chosen, p * (1.0 / heads), 0.0)
        kib = ki_ref[0]
        w_cols = [_row_to_lanes(w_ref[h]) for h in range(index_heads)]
        log_q = _index_scores(qi_ref, w_cols, kib, s_scr) - _lanes_to(
            _row_to_lanes(lsei_ref[0]), block_k
        )
        kl = jnp.where(
            p > 0.0, p * (jnp.log(jnp.where(p > 0.0, p, 1.0)) - log_q), 0.0
        )
        kl_scr[...] = kl_scr[...] + _row_sum(kl)
        if not with_grads:
            return
        d_index = jnp.where(chosen, jnp.exp(log_q) - p, 0.0)
        dki = jnp.zeros(dki_ref.shape[2:], jnp.float32)
        for h in range(index_heads):
            qih = qi_ref[0, h]
            s = s_scr[h]
            dw_scr[h] = dw_scr[h] + _row_sum(d_index * jnp.maximum(s, 0.0))
            ds = jnp.where(
                s > 0.0, d_index * _lanes_to(w_cols[h], block_k), 0.0
            ).astype(kib.dtype)
            dqi_scr[h] = dqi_scr[h] + jax.lax.dot(
                ds, kib, preferred_element_type=jnp.float32
            )
            dki = dki + jax.lax.dot_general(
                ds, qih, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
        dki_ref[0, 0] = dki

    @pl.when(j == num_kb - 1)
    def _write():
        kl_ref[0] = kl_scr[...].T[0:1]
        if with_grads:
            dqi_ref[0] = dqi_scr[...]
            for h in range(index_heads):
                dw_ref[h] = dw_scr[h].T[0:1]


def _kl_call(q, k, lse, mask, qi, ki, w, lse_i, sm_scale, interpret, with_grads):
    batch, seq, heads, d = q.shape
    kv_heads = k.shape[2]
    index_heads, width = qi.shape[2:]
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    if interpret is None:
        interpret = on_mesh.default_interpret()
    block_k = mask.shape[3]
    block_q = _pick_block(seq, 256)
    num_kb, num_qb = seq // block_k, seq // block_q

    def last_live(i, j):  # the index maps stop where the kernel does
        return jnp.minimum(j, ((i + 1) * block_q - 1) // block_k)

    def folded(x):
        b, s, h, width = x.shape
        return x.transpose(0, 2, 1, 3).reshape(b * h, s, width)

    def q_rows(n):
        return pl.BlockSpec((n, 1, block_q), lambda b, i, j: (b, 0, i))

    out_specs = [q_rows(1)]
    out_shape = [jax.ShapeDtypeStruct((batch, 1, seq), jnp.float32)]
    scratch = [pltpu.VMEM((block_q, _LANES), jnp.float32)]
    if with_grads:
        out_specs += [
            pl.BlockSpec(
                (1, index_heads, block_q, width),
                lambda b, i, j: (b, 0, i, 0),
            ),
            q_rows(index_heads),
            pl.BlockSpec(
                (1, 1, block_k, width),
                lambda b, i, j: (b, i, last_live(i, j), 0),
            ),
        ]
        out_shape += [
            jax.ShapeDtypeStruct(
                (batch, index_heads, seq, width), jnp.float32
            ),
            jax.ShapeDtypeStruct((batch * index_heads, 1, seq), jnp.float32),
            jax.ShapeDtypeStruct((batch, num_qb, seq, width), jnp.float32),
        ]
        scratch += [
            pltpu.VMEM((index_heads, block_q, width), jnp.float32),
            pltpu.VMEM((index_heads, block_q, _LANES), jnp.float32),
            pltpu.VMEM((index_heads, block_q, block_k), jnp.float32),
        ]
    made = pl.pallas_call(
        functools.partial(
            _kl_kernel, sm_scale=sm_scale, block_q=block_q, block_k=block_k,
            num_kb=num_kb, group=heads // kv_heads, with_grads=with_grads,
        ),
        grid=(batch, num_qb, num_kb),
        in_specs=[
            pl.BlockSpec((heads, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec(
                (kv_heads, block_k, d),
                lambda b, i, j: (b, last_live(i, j), 0),
            ),
            q_rows(heads),
            pl.BlockSpec(
                (1, 1, block_q, block_k),
                lambda b, i, j: (b, last_live(i, j), i, 0),
            ),
            pl.BlockSpec(
                (1, index_heads, block_q, width),
                lambda b, i, j: (b, 0, i, 0),
            ),
            pl.BlockSpec(
                (1, block_k, width), lambda b, i, j: (b, last_live(i, j), 0)
            ),
            q_rows(index_heads),
            q_rows(1),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        compiler_params=_KL_PARAMS,
        interpret=interpret,
        name=INDEXER_KL,
    )(
        folded(q), folded(k), lse, mask, qi.transpose(0, 2, 1, 3), ki,
        w.astype(jnp.float32).transpose(0, 2, 1).reshape(
            batch * index_heads, 1, seq
        ),
        lse_i[:, None, :],
    )
    if not with_grads:
        return jnp.sum(made[0]), None
    kl, dqi, dw, dki_parts = made
    # a q-block's part of dki exists for the key blocks it sees
    seen = (
        jnp.arange(num_kb)[None, :] * block_k
        < (jnp.arange(num_qb)[:, None] + 1) * block_q
    )
    dki = jnp.sum(
        jnp.where(
            jnp.repeat(seen, block_k, axis=1)[None, :, :, None],
            dki_parts, 0.0,
        ),
        axis=1,
    )
    grads = (
        dqi.transpose(0, 2, 1, 3),
        dki,
        dw.reshape(batch, index_heads, seq).transpose(0, 2, 1),
    )
    return jnp.sum(kl), grads


@functools.partial(jax.custom_vjp, nondiff_argnums=(8, 9))
def indexer_kl(
    q, k, lse, mask, qi, ki, w, lse_i, sm_scale: float | None = None,
    interpret: bool | None = None,
):
    """``sum over batch and queries of KL(p_t || softmax_{S_t} I_t)``
    (DeepSeek-V3.2-Exp eq. 4, the sparse training stage): ``p`` the main
    attention's probabilities over the selected set averaged over its
    heads, rebuilt from ``q``, ``k`` ``(batch, seq, heads, d)`` and the
    ``lse`` ``selected_flash_attention`` returned; ``I`` from the indexer's
    ``qi``, ``ki``, ``w`` with ``lse_i`` its logsumexp over the set
    (:func:`index_select`).  Differentiable in ``qi``, ``ki`` and ``w``
    alone: the target and the selection are constants."""
    return _kl_call(
        q, k, lse, mask, qi, ki, w, lse_i, sm_scale, interpret, False
    )[0]


def _kl_fwd_rule(q, k, lse, mask, qi, ki, w, lse_i, sm_scale, interpret):
    # the value alone: a forward pass that is not differentiated (the first
    # one under ``remat_layers``) pays for no gradient, and the recomputed
    # one needs nothing of this call but its operands
    total, _ = _kl_call(
        q, k, lse, mask, qi, ki, w, lse_i, sm_scale, interpret, False
    )
    return total, (q, k, lse, mask, qi, ki, w, lse_i)


def _kl_bwd_rule(sm_scale, interpret, res, g):
    qi, ki, w = res[4:7]
    _, grads = _kl_call(*res, sm_scale, interpret, True)
    dqi, dki, dw = (
        (g * x).astype(z.dtype) for x, z in zip(grads, (qi, ki, w))
    )
    return None, None, None, None, dqi, dki, dw, None


indexer_kl.defvjp(_kl_fwd_rule, _kl_bwd_rule)


def indexer_kl_with_grads(
    q, k, lse, mask, qi, ki, w, lse_i, sm_scale: float | None = None,
    interpret: bool | None = None,
):
    """:func:`indexer_kl`'s value AND its gradients to ``qi``, ``ki`` and
    ``w`` (in their dtypes, before any cotangent) from ONE call of the
    gradient variant, which writes the value's rows too: ``(kl, (dqi, dki,
    dw))``.  The gradients depend on nothing downstream of the layer (the
    target and the selection are constants), so a first pass that knows it
    is being differentiated (``layers/recompute.py::offers_kept``) makes the
    loss's one call a layer and step here, and :func:`indexer_kl_found`
    hands the result to the backward pass.  Not itself differentiable."""
    kl, grads = _kl_call(
        q, k, lse, mask, qi, ki, w, lse_i, sm_scale, interpret, True
    )
    return kl, tuple(x.astype(z.dtype) for x, z in zip(grads, (qi, ki, w)))


@jax.custom_vjp
def indexer_kl_found(wrt, found):
    """The indexer's loss where an earlier pass already made its one call:
    ``found`` is ``(kl, grads)``, ``grads`` the loss's gradient to the tree
    ``wrt`` (:func:`indexer_kl_with_grads`'s three, or what they are to the
    parameters behind them).  Returns ``kl``; the backward rule is ``grads``
    times the cotangent.  No kernel."""
    return found[0]


def _kl_found_fwd_rule(wrt, found):
    return found


def _kl_found_bwd_rule(grads, g):
    # ``grads`` was rounded to its operand's dtype before the cotangent where
    # :func:`indexer_kl`'s rule rounds after it: the same bits where ``g`` is
    # a power of two, one rounding apart elsewhere
    return (
        jax.tree_util.tree_map(lambda x: (g * x).astype(x.dtype), grads), None
    )


indexer_kl_found.defvjp(_kl_found_fwd_rule, _kl_found_bwd_rule)


# ---- the materialised form (tests, chip_smoke.py) ----------------------------


def index_scores_reference(qi, ki, w):
    """``I`` whole, ``(batch, seq, seq)`` float32."""
    s = jnp.einsum(
        "bthd,bsd->bhts", qi.astype(jnp.float32), ki.astype(jnp.float32),
        precision="highest",
    )
    return jnp.einsum(
        "bhts,bth->bts", jnp.maximum(s, 0.0), w.astype(jnp.float32),
        precision="highest",
    )


def select_reference(scores, topk: int):
    """``(batch, seq, seq)`` boolean: per query the ``min(t + 1, topk)``
    visible keys with the largest scores, by ``lax.top_k`` (a tie goes to
    the lower index)."""
    seq = scores.shape[-1]
    visible = jnp.tril(jnp.ones((seq, seq), bool))
    _, index = jax.lax.top_k(
        jnp.where(visible, scores, -jnp.inf), min(topk, seq)
    )
    chosen = jnp.zeros(scores.shape, bool)
    chosen = jax.vmap(jax.vmap(lambda c, ix: c.at[ix].set(True)))(
        chosen, index
    )
    return chosen & visible


def selected_reference(q, k, v, chosen, sm_scale: float | None = None):
    """Materialised attention over ``chosen`` ``(batch, seq, seq)``:
    ``(out, probabilities (batch, heads, seq, seq))``."""
    group = validate_gqa_heads(q, k, v)
    k, v = (jnp.repeat(x, group, axis=2) for x in (k, v))
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    s = jnp.einsum(
        "bqhd,bkhd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32),
        precision="highest",
    ) * sm_scale
    p = jax.nn.softmax(jnp.where(chosen[:, None], s, -jnp.inf), axis=-1)
    out = jnp.einsum(
        "bhqk,bkhd->bqhd", p, v.astype(jnp.float32), precision="highest"
    )
    return out.astype(q.dtype), p


def indexer_kl_reference(p, scores, chosen):
    """``sum_t KL(mean_h p_t || softmax_S I_t)`` from the materialised
    probabilities and index scores."""
    target = jnp.mean(p, axis=1)
    log_q = jax.nn.log_softmax(jnp.where(chosen, scores, -jnp.inf), axis=-1)
    safe = jnp.where(target > 0, target, 1.0)
    return jnp.sum(
        jnp.where(
            chosen & (target > 0),
            target * (jnp.log(safe) - jnp.where(chosen, log_q, 0.0)),
            0.0,
        )
    )
