"""Attention kernels: pallas flash attention for the MXU + dispatch.

The reference framework has no attention anywhere (its models are
CNN/DNN/FM recommenders, SURVEY §2.10); long-context support is a
first-class requirement of the TPU build, so this module provides the
single-device half of it — a blockwise online-softmax (flash) kernel
that never materializes the (S, S) score matrix in HBM — and
:mod:`.ring_attention` provides the cross-device half over the ``sp``
mesh axis.

Layout convention everywhere: ``(batch, seq, heads, head_dim)`` — seq at
dim 1 matches ``parallel.sharding.batch_sharding(sp_dim=1)`` so the same
batch placement shards sequence over ``sp``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from elasticdl_tpu.ops import on_mesh
from elasticdl_tpu.ops.lane_heads import by_head, only_head
# the registry's names, for ``perf/`` and whoever has always found them here
from elasticdl_tpu.ops.on_mesh import (  # noqa: F401
    attention_mesh_scope,
    get_attention_mesh,
    kernel_interpret,
    set_attention_mesh,
)

# lane width of TPU vector registers: the m/l scratch accumulators keep
# this many (all-equal) columns so stores stay tile-aligned
_LANES = 128

# batch, head and q/k-block dims are independent programs; only the
# innermost (accumulation stream) dim is order-dependent — telling
# Mosaic lets it pipeline the outer dims across cores
_FLASH_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")
)

# the selected-set variants stage a chunk of the mask beside k and v
_SELECTED_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
    vmem_limit_bytes=64 << 20,
)

_NEG_INF = -1e30

# the three kernels' names: each ``pallas_call``'s ``name``, which the
# compiled custom-call carries, so a device trace tells forward, dQ and
# dK/dV apart by name (perf/layer_metrics/flash_*_roofline.lm.py)
FLASH_FWD = "flash_fwd"
FLASH_DQ = "flash_dq"
FLASH_DKV = "flash_dkv"
# the same three kernels over a selected set (a mask beside the causal
# structure, ops/sparse_attention.py): names of their own, so that a trace
# tells a step's dense attention from its sparse one
SELECTED_FWD = "dsa_fwd"
SELECTED_DQ = "dsa_dq"
SELECTED_DKV = "dsa_dkv"
# and under a window (a query reads its last ``window`` keys alone,
# docs/designs/window_attention.md): a stack that mixes window and full
# layers shows each kind's calls on the op line
WINDOW_FWD = "swa_fwd"
WINDOW_DQ = "swa_dq"
WINDOW_DKV = "swa_dkv"
# the XLA ops beside the kernel calls (reshapes and transposes to and from
# the kernels' layout) by telemetry/op_scopes.py's name; never around a
# ``pallas_call``, whose own name is what the device's op line shows
_FOLD = "fold"


# ---- reference (jnp) -------------------------------------------------------


def validate_gqa_heads(q, k, v) -> int:
    """The ONE place the grouped-query head constraint lives: K and V
    must agree, and q heads must be a multiple of kv heads.  Returns the
    group factor (1 = plain MHA)."""
    q_heads, kv_heads = q.shape[2], k.shape[2]
    if k.shape[-1] != q.shape[-1]:
        raise ValueError(
            f"q and k head widths differ: {q.shape[-1]} vs {k.shape[-1]}"
        )
    if v.shape[2] != kv_heads:
        raise ValueError(
            f"k and v head counts differ: {kv_heads} vs {v.shape[2]}"
        )
    if kv_heads <= 0 or q_heads % kv_heads:
        raise ValueError(
            f"GQA needs q heads ({q_heads}) divisible by kv heads "
            f"({kv_heads})"
        )
    return q_heads // kv_heads


def repeat_kv_heads(q, k, v):
    """Grouped-query attention support: when K/V carry fewer heads than
    Q, repeat each KV head over its query group so the caller can treat
    heads uniformly."""
    group = validate_gqa_heads(q, k, v)
    if group == 1:
        return k, v
    return (
        jnp.repeat(k, group, axis=2),
        jnp.repeat(v, group, axis=2),
    )


def mha_reference(
    q, k, v, causal: bool = False, sm_scale: float | None = None,
    window: int | None = None,
):
    """Plain multi-head attention, (B, S, H, D) layout (K/V may carry
    fewer heads — GQA) — the numerical oracle for the kernels and the
    CPU fallback.  ``window`` (causal only): query ``t`` reads key ``s``
    iff ``0 <= t - s < window``."""
    k, v = repeat_kv_heads(q, k, v)
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    scores = jnp.einsum(
        "bqhd,bkhd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32)
    )
    scores = scores * sm_scale
    if causal:
        s_q, s_k = scores.shape[-2], scores.shape[-1]
        row = jax.lax.broadcasted_iota(jnp.int32, (s_q, s_k), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (s_q, s_k), 1)
        seen = row >= col
        if window is not None:
            seen = seen & (row - col < window)
        scores = jnp.where(seen, scores, _NEG_INF)
    elif window is not None:
        raise ValueError("a window needs causal attention")
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v.astype(jnp.float32))
    return out.astype(q.dtype)


# ---- pallas flash kernel ---------------------------------------------------


# a @ b.T: the product the MXU takes natively (transposed right-hand side)
_NT = (((1,), (1,)), ((), ()))


def _clip(x, low, high):
    if isinstance(x, int):
        return min(max(x, low), high)
    return jnp.clip(x, low, high)


def _k_blocks_visible(row0, rows, col0, block_k, num_blocks):
    """Causal structure of ``num_blocks`` k-blocks of ``block_k`` columns
    from column ``col0`` on, against the q rows ``[row0, row0 + rows)``:
    ``(full, live)`` — blocks ``[0, full)`` lie wholly on the visible side
    of the diagonal (no mask), ``[full, live)`` are crossed by it, the
    rest see nothing.  Python ints or traced int32."""
    full = _clip((row0 + 1 - col0) // block_k, 0, num_blocks)
    live = _clip(
        (row0 + rows - col0 + block_k - 1) // block_k, 0, num_blocks
    )
    return full, live


def _q_blocks_visible(col0, cols, row0, block_q, num_blocks):
    """The same structure along the other axis, for dK/dV: q-blocks of
    ``block_q`` rows from row ``row0`` on, against the k columns
    ``[col0, col0 + cols)``: ``(first, full_from)`` — blocks
    ``[0, first)`` see nothing, ``[first, full_from)`` are crossed by the
    diagonal, ``[full_from, num_blocks)`` need no mask."""
    first = _clip((col0 - row0) // block_q, 0, num_blocks)
    full_from = _clip(
        (col0 + cols - 1 - row0 + block_q - 1) // block_q, 0, num_blocks
    )
    return first, full_from


def _k_blocks_in_window(row0, rows, col0, block_k, window, full, live):
    """The window's trailing edge over the same k-blocks: ``(behind, edge,
    full)`` — of the blocks ``[0, live)`` that :func:`_k_blocks_visible`
    leaves, ``[0, behind)`` lie wholly behind the edge (every pair has
    ``t - s >= window``: never visited), ``[behind, edge)`` are crossed by
    it, and ``full`` comes back no smaller than ``edge`` (where one block
    is crossed by both the edge and the diagonal the first range has it)."""
    behind = _clip((row0 - window - col0 + 1) // block_k, 0, live)
    edge = _clip(
        (row0 + rows - 1 - window - col0 + block_k) // block_k, behind, live
    )
    return behind, edge, _clip(full, edge, live)


def _q_blocks_in_window(
    col0, cols, row0, block_q, num_blocks, window, first, full_from
):
    """The trailing edge along the other axis, for dK/dV: ``(full_from,
    edge_from, end)`` — of the q-blocks ``[first, num_blocks)`` that
    :func:`_q_blocks_visible` leaves, ``[edge_from, end)`` are crossed by
    the edge and ``[end, num_blocks)`` lie wholly behind it; ``full_from``
    comes back no larger than ``end`` and ``edge_from`` no smaller than it."""
    end = _clip(
        (col0 + cols - 1 + window - row0 + block_q - 1) // block_q,
        first, num_blocks,
    )
    full_from = _clip(full_from, first, end)
    edge_from = _clip((col0 + window - row0) // block_q, full_from, end)
    return full_from, edge_from, end


# how a block is crossed: by the diagonal (``True``: what a dense causal
# kernel knows), by the window's trailing edge, or whole under both
# conditions
_EDGE = "edge"
_BOTH = "both"


def _crossings(block_q, block_k, window):
    """How the kernels mask a block the diagonal crosses and one the window's
    trailing edge crosses: ``(on_diagonal, on_edge)``.  Where one block can
    be crossed by both (``t - s`` spans ``block_q + block_k - 1`` values in a
    block), such blocks are computed whole under both conditions."""
    if window is not None and window < block_q + block_k - 1:
        return _BOTH, _BOTH
    return True, _EDGE


def flash_block_plan(seq_q, seq_k, block_q, block_k, causal, window=None):
    """``(live, masked, skipped)`` score blocks a head: the blocks the
    kernels compute, those of them the diagonal or the window's trailing
    edge crosses (the only ones that build a mask), and the ones never
    touched.  Counted with the kernels' own loop bounds."""
    num_q, num_k = seq_q // block_q, seq_k // block_k
    if not causal:
        return num_q * num_k, 0, 0
    live = masked = 0
    for i in range(num_q):
        full, upto = _k_blocks_visible(
            i * block_q, block_q, 0, block_k, num_k
        )
        behind = edge = 0
        if window is not None:
            behind, edge, full = _k_blocks_in_window(
                i * block_q, block_q, 0, block_k, window, full, upto
            )
        live += upto - behind
        masked += (edge - behind) + (upto - full)
    return live, masked, num_q * num_k - live


def _causal_mask(s, row0, col0, q_axis=0):
    """Mask scores above the causal diagonal for a tile whose first query
    row is ``row0`` and first key column ``col0``; queries run along
    ``q_axis`` of ``s`` (1 for dK/dV's transposed scores)."""
    ahead = jax.lax.broadcasted_iota(
        jnp.int32, s.shape, q_axis
    ) - jax.lax.broadcasted_iota(jnp.int32, s.shape, 1 - q_axis)
    return jnp.where(ahead >= col0 - row0, s, _NEG_INF)


def _visible(s, crossed, row0, col0, window, q_axis=0):
    """Scores a query does not read masked, for a tile whose first query
    row is ``row0`` and first key column ``col0``: above the diagonal,
    behind the window's trailing edge (``t - s >= window``), or both."""
    if crossed is True:
        return _causal_mask(s, row0, col0, q_axis)
    ahead = jax.lax.broadcasted_iota(
        jnp.int32, s.shape, q_axis
    ) - jax.lax.broadcasted_iota(jnp.int32, s.shape, 1 - q_axis)
    seen = ahead < window - (row0 - col0)
    if crossed == _BOTH:
        seen = seen & (ahead >= col0 - row0)
    return jnp.where(seen, s, _NEG_INF)


def _selected(s, chosen):
    """Scores outside the selected set masked: ``chosen`` is the tile of
    the selection's int8 mask (1 where the query reads the key, causality
    included; transposed like ``s`` for dK/dV)."""
    return jnp.where(chosen.astype(jnp.int32) != 0, s, _NEG_INF)


def _scores(a, b, sm_scale=None):
    """``a @ b.T`` in float32, times the softmax scale where one is given
    (applied to the float32 scores: 1/sqrt(128) is not a bf16 number, so
    it never rides a rounded q)."""
    s = jax.lax.dot_general(a, b, _NT, preferred_element_type=jnp.float32)
    return s if sm_scale is None else s * sm_scale


def _mxu(a, b):
    """``a`` (float32 probabilities, or their gradient) rounded once to
    ``b``'s dtype, times ``b``, accumulated in float32."""
    return jax.lax.dot(
        a.astype(b.dtype), b, preferred_element_type=jnp.float32
    )


def _lanes_to(x, width):
    """A lane-replicated ``(rows, _LANES)`` column as ``(rows, width)``."""
    if width % _LANES == 0:
        return jnp.tile(x, (1, width // _LANES))
    if width < _LANES:
        return x[:, :width]
    return jnp.broadcast_to(x[:, 0:1], (x.shape[0], width))


def _row_to_lanes(row):
    """A ``(1, n)`` row as an ``(n, _LANES)`` lane-replicated column: a
    transpose of the row repeated over 128 sublanes.  (Transposing 8
    sublanes and broadcasting the column across lanes costs dQ 20% at
    1,024 tokens: the lane broadcast is the dear part.)"""
    return jnp.broadcast_to(row, (_LANES, row.shape[1])).T


def _columns_by_head(cols, width):
    """Lane-replicated ``(rows, _LANES)`` columns, one a head, as
    ``(rows, width)`` with each head's column across that head's lanes."""
    return by_head([_lanes_to(col, width) for col in cols])


def _loop(start, stop, body):
    if isinstance(start, int) and isinstance(stop, int) and start >= stop:
        return
    jax.lax.fori_loop(start, stop, lambda i, _: body(i) or 0, 0)


def _diagonal_half(block_q, block_k):
    """Equal blocks are crossed by the diagonal only ON it (first row ==
    first column), where the upper-right quarter sees nothing: the
    kernels then work such a block in halves and skip that quarter.
    Returns the half, or 0 where blocks are not halved (unequal, or a half
    the sublanes do not tile)."""
    half = block_q // 2
    return half if block_q == block_k and half and half % 8 == 0 else 0


def _block_pieces(block_q, block_k, crossed, along_q, window=None):
    """The parts of a score block worth computing, as static
    ``(q_from, q_to, k_from, k_to, masked)`` ranges inside it: one piece
    for a block the diagonal does not cross; for one it does, the three
    visible quarters (:func:`_diagonal_half`) — as two pieces split along
    q for the kernels that accumulate by q row, as three for dK/dV, whose
    pieces each read one saved half-row — or the whole block, masked.  A
    block the window's trailing edge crosses is the diagonal block's
    complement where the window is a whole number of such blocks (its
    lower-left quarter sees nothing), else the whole block, masked."""
    half = _diagonal_half(block_q, block_k)
    if crossed == _EDGE and half and window % block_k == 0:
        if along_q:
            return [
                (0, half, 0, block_k, _EDGE),
                (half, block_q, half, block_k, _EDGE),
            ]
        return [
            (0, half, 0, half, _EDGE),
            (0, half, half, block_k, False),
            (half, block_q, half, block_k, _EDGE),
        ]
    if crossed is not True or not half:
        return [(0, block_q, 0, block_k, crossed)]
    if along_q:
        return [(0, half, 0, half, True), (half, block_q, 0, block_k, True)]
    return [
        (0, half, 0, half, True),
        (half, block_q, 0, half, False),
        (half, block_q, half, block_k, True),
    ]


def _flash_kernel(
    q_ref, k_ref, v_ref, *refs,
    sm_scale, causal, block_q, block_k, chunk_k, num_ck, selected=False,
    window=None,
):
    """One (batch, head, q-block, k-chunk) grid cell of the online-softmax
    forward: loop block_k sub-blocks of the staged (1, chunk_k, d) K/V
    chunk through the online softmax.  m/l/acc persist across the chunk
    stream in VMEM scratch; the output and the per-row logsumexp (of the
    SCALED scores — the backward rebuilds probabilities from it) are
    written once at the last chunk.

    Where the blocks hold two heads side by side in their lanes
    (:func:`_heads_per_block`; ``m_scr`` has a plane a head) the cell does
    both: each head's scores from a q with the other's lanes zeroed, its
    ``p @ v`` taken over the whole block and its own lanes selected into
    the one lane-dense accumulator.

    ``selected``: ``refs`` starts with the chunk of the selection's mask,
    ``(1, blocks of the chunk, block_q, block_k)`` int8, applied to every
    live block in the place of the diagonal's iota mask (it holds the
    causal structure too).  A row may then meet blocks that hold none of
    its keys before one that does: what ``exp(-1e30 - -1e30) = 1`` adds to
    ``l`` and ``acc`` there is wiped by ``alpha = 0`` at the first real
    key, and every row has one (itself or an earlier key).

    ``window``: a query reads its last ``window`` keys alone.  The grid's
    chunk stream then starts at the q-block's first live chunk
    (:func:`_first_live_chunk`) and is ``num_ck`` chunks long, the blocks of
    a chunk wholly behind the trailing edge are outside the loops' bounds,
    and the ones the edge crosses, which come first, build a second mask
    (a row may find no key in them: the same ``alpha = 0`` wipes it)."""
    if selected:
        mask_ref, *refs = refs
    o_ref, lse_ref, m_scr, l_scr, acc_scr = refs
    i = pl.program_id(2)
    c = pl.program_id(3)
    heads = m_scr.shape[0]
    chunk = c
    if window is not None:
        chunk = c + _first_live_chunk(i, block_q, chunk_k, window)

    @pl.when(c == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    nb = chunk_k // block_k
    row0, col0 = i * block_q, chunk * chunk_k
    if causal:
        full, live = _k_blocks_visible(row0, block_q, col0, block_k, nb)
    else:
        full = live = nb
    behind = edge = 0
    if window is not None:
        behind, edge, full = _k_blocks_in_window(
            row0, block_q, col0, block_k, window, full, live
        )
    on_diagonal, on_edge = _crossings(block_q, block_k, window)
    d = acc_scr.shape[1]

    def _chunk():
        q = q_ref[0]  # (block_q, heads * D)
        q_of = [only_head(q, h, heads) for h in range(heads)]

        def body(jj, crossed):
            start = pl.multiple_of(jj * block_k, block_k)
            for q0, q1, k0, k1, masked in _block_pieces(
                block_q, block_k, crossed, along_q=True, window=window
            ):
                rows = slice(q0, q1)
                kb = k_ref[0, pl.ds(start + k0, k1 - k0), :]
                vb = v_ref[0, pl.ds(start + k0, k1 - k0), :]
                alphas, pvs = [], []
                for h in range(heads):
                    s = _scores(q_of[h][rows], kb, sm_scale)
                    if selected:
                        s = _selected(s, mask_ref[0, jj, q0:q1, k0:k1])
                    elif masked:
                        s = _visible(
                            s, masked, row0 + q0, col0 + start + k0, window
                        )
                    m_prev = m_scr[h, rows]  # (rows, _LANES), columns equal
                    m_next = jnp.maximum(
                        m_prev, jnp.max(s, axis=1, keepdims=True)
                    )
                    alpha = jnp.exp(m_prev - m_next)
                    p = jnp.exp(s - _lanes_to(m_next, k1 - k0))
                    l_scr[h, rows] = alpha * l_scr[h, rows] + p.sum(
                        axis=1, keepdims=True
                    )
                    m_scr[h, rows] = m_next
                    alphas.append(alpha)
                    pvs.append(_mxu(p, vb))
                acc_scr[rows] = acc_scr[rows] * _columns_by_head(
                    alphas, d
                ) + by_head(pvs)

        _loop(behind, edge, functools.partial(body, crossed=on_edge))
        _loop(edge, full, functools.partial(body, crossed=False))
        _loop(full, live, functools.partial(body, crossed=on_diagonal))

    if causal:
        # chunks above the diagonal, or behind the window, add nothing
        pl.when(live > behind)(_chunk)
    else:
        _chunk()

    @pl.when(c == num_ck - 1)
    def _write():
        ls = [l_scr[h] for h in range(heads)]
        o_ref[0] = (acc_scr[...] / _columns_by_head(ls, d)).astype(
            o_ref.dtype
        )
        # one dense (1, block_q) row a block and head: lanes hold the
        # sequence
        for h in range(heads):
            lse_ref[h] = (m_scr[h] + jnp.log(ls[h])).T[0:1]


def _flash_dq_kernel(
    q_ref,
    k_ref,
    v_ref,
    do_ref,
    lse_ref,
    *refs,
    sm_scale,
    causal,
    block_q,
    block_k,
    chunk_k,
    num_ck,
    makes_delta,
    selected=False,
    window=None,
):
    """dQ cell per (batch, head, q-block, k-chunk): rebuild p from the
    saved logsumexp, accumulate dq = sm_scale * ds @ K into VMEM scratch
    across the chunk stream (same structure as the forward, two heads a
    cell where the blocks hold two).

    ``delta_r = rowsum(dO * O)``, the softmax-jacobian correction term,
    arrives as rows in the lse's layout (``refs``: ``delta_ref, dq_ref,
    acc_scr``), or, with ``makes_delta``, the cell makes it for its rows
    from the block of ``out`` (``refs``: ``out_ref, dq_ref, delta_ref,
    acc_scr, delta_scr``): a head at a time from the two blocks as they
    lie, kept as lane-replicated columns for its own use and written once
    in the lse's layout for dK/dV.  The kernels that read heads out of
    lanes make it: outside them the same sum over a 64-wide minor
    dimension has XLA turn a float32 (batch, tokens, heads * 64) array
    tokens-minor first, 100 MB through HBM a layer at 8 x 1,024 x 12 x 64."""
    if selected:  # the mask's chunk, as the forward takes it
        mask_ref, *refs = refs
    if makes_delta:
        out_ref, dq_ref, delta_ref, acc_scr, delta_scr = refs
    else:
        delta_ref, dq_ref, acc_scr = refs
    i = pl.program_id(2)
    c = pl.program_id(3)
    heads = lse_ref.shape[0]
    chunk = c
    if window is not None:  # the forward's chunk stream and block ranges
        chunk = c + _first_live_chunk(i, block_q, chunk_k, window)

    @pl.when(c == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)
        if makes_delta:
            do_o = do_ref[0].astype(jnp.float32) * out_ref[0].astype(
                jnp.float32
            )
            for h in range(heads):
                delta_scr[h] = jnp.broadcast_to(
                    jnp.sum(
                        only_head(do_o, h, heads), axis=1, keepdims=True
                    ),
                    delta_scr.shape[1:],
                )

    nb = chunk_k // block_k
    row0, col0 = i * block_q, chunk * chunk_k
    if causal:
        full, live = _k_blocks_visible(row0, block_q, col0, block_k, nb)
    else:
        full = live = nb
    behind = edge = 0
    if window is not None:
        behind, edge, full = _k_blocks_in_window(
            row0, block_q, col0, block_k, window, full, live
        )
    on_diagonal, on_edge = _crossings(block_q, block_k, window)

    def _chunk():
        q = q_ref[0]
        do = do_ref[0]  # (block_q, heads * D)
        q_of = [only_head(q, h, heads) for h in range(heads)]
        do_of = [only_head(do, h, heads) for h in range(heads)]
        # the saved rows, turned once a chunk into lane-replicated columns
        lse = [_row_to_lanes(lse_ref[h]) for h in range(heads)]
        delta = [
            delta_scr[h] if makes_delta else _row_to_lanes(delta_ref[h])
            for h in range(heads)
        ]

        def body(jj, crossed):
            start = pl.multiple_of(jj * block_k, block_k)
            for q0, q1, k0, k1, masked in _block_pieces(
                block_q, block_k, crossed, along_q=True, window=window
            ):
                rows = slice(q0, q1)
                kb = k_ref[0, pl.ds(start + k0, k1 - k0), :]
                vb = v_ref[0, pl.ds(start + k0, k1 - k0), :]
                dqs = []
                for h in range(heads):
                    s = _scores(q_of[h][rows], kb, sm_scale)
                    if selected:
                        s = _selected(s, mask_ref[0, jj, q0:q1, k0:k1])
                    elif masked:
                        s = _visible(
                            s, masked, row0 + q0, col0 + start + k0, window
                        )
                    p = jnp.exp(s - _lanes_to(lse[h][rows], k1 - k0))
                    dp = _scores(do_of[h][rows], vb)
                    ds = p * (dp - _lanes_to(delta[h][rows], k1 - k0))
                    dqs.append(_mxu(ds, kb))
                acc_scr[rows] = acc_scr[rows] + by_head(dqs)

        _loop(behind, edge, functools.partial(body, crossed=on_edge))
        _loop(edge, full, functools.partial(body, crossed=False))
        _loop(full, live, functools.partial(body, crossed=on_diagonal))

    if causal:
        pl.when(live > behind)(_chunk)
    else:
        _chunk()

    @pl.when(c == num_ck - 1)
    def _write():
        dq_ref[0] = (acc_scr[...] * sm_scale).astype(dq_ref.dtype)
        if makes_delta:
            for h in range(heads):
                delta_ref[h] = delta_scr[h].T[0:1]


def _flash_dkv_kernel(
    q_ref,
    k_ref,
    v_ref,
    do_ref,
    lse_ref,
    delta_ref,
    *refs,
    sm_scale,
    causal,
    block_q,
    block_k,
    chunk_q,
    num_cq,
    selected=False,
    window=None,
    all_cq=None,
):
    """dK/dV cell per (batch, head, k-block, q-chunk): loop block_q
    sub-blocks of the staged (1, chunk_q, d) Q/dO chunk over TRANSPOSED
    scores ``s^T = k @ q^T`` (block_k, block_q), so ``p^T`` and ``ds^T``
    come out as the left-hand sides ``dv += p^T @ dO`` and
    ``dk += ds^T @ q`` want, and ``lse``/``delta`` are read as rows along
    the lanes (``lse_ref``: a head's whole (1, seq_q / n, n), a row a
    q-block or half of one).  ``sm_scale`` meets dk once, at the write.
    Two heads a cell where the blocks hold two: k and v with the other
    head's lanes zeroed make the scores, and each head's lanes of the two
    products are selected into the accumulators.  ``selected``: ``refs``
    starts with the chunk of the TRANSPOSED mask, ``(1, blocks of the
    chunk, block_k, block_q)``.  ``window``: the chunk stream starts at the
    first q-chunk whose rows reach this k-block's columns and is ``num_cq``
    chunks long (``all_cq``: the sequence's, past which a late k-block's
    stream finds nothing); q-blocks wholly behind the trailing edge are
    outside the loops' bounds and the ones it crosses, which come last,
    build a second mask."""
    if selected:
        mask_ref, *refs = refs
    dk_ref, dv_ref, dk_scr, dv_scr = refs
    j = pl.program_id(2)
    c = pl.program_id(3)
    heads = lse_ref.shape[0]
    chunk = c
    if window is not None:
        chunk = c + (j * block_k) // chunk_q

    @pl.when(c == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    nb = chunk_q // block_q
    col0, row0 = j * block_k, chunk * chunk_q
    if causal:
        first, full_from = _q_blocks_visible(
            col0, block_k, row0, block_q, nb
        )
    else:
        first = full_from = 0
    edge_from = end = nb
    if window is not None:
        full_from, edge_from, end = _q_blocks_in_window(
            col0, block_k, row0, block_q, _clip((all_cq - chunk) * nb, 0, nb),
            window, first, full_from,
        )
    on_diagonal, on_edge = _crossings(block_q, block_k, window)

    saved = lse_ref.shape[2]  # block_q, or its half (_diagonal_half)

    def saved_rows(ref, h, ii, q0, q1):
        """Rows ``[q0, q1)`` of q-block ``ii`` of the chunk, along lanes."""
        first = (chunk * nb + ii) * (block_q // saved)
        return jnp.concatenate(
            [
                ref[h, pl.ds(first + part, 1), :]
                for part in range(q0 // saved, q1 // saved)
            ],
            axis=1,
        )

    def _chunk():
        k = k_ref[0]  # (block_k, heads * D)
        v = v_ref[0]
        k_of = [only_head(k, h, heads) for h in range(heads)]
        v_of = [only_head(v, h, heads) for h in range(heads)]

        def body(ii, crossed):
            start = pl.multiple_of(ii * block_q, block_q)
            for q0, q1, k0, k1, masked in _block_pieces(
                block_q, block_k, crossed, along_q=False, window=window
            ):
                cols = slice(k0, k1)
                qi = q_ref[0, pl.ds(start + q0, q1 - q0), :]
                doi = do_ref[0, pl.ds(start + q0, q1 - q0), :]
                dvs, dks = [], []
                for h in range(heads):
                    lse = saved_rows(lse_ref, h, ii, q0, q1)  # (1, q rows)
                    delta = saved_rows(delta_ref, h, ii, q0, q1)
                    # (k rows, q rows)
                    st = _scores(k_of[h][cols], qi, sm_scale)
                    if selected:
                        st = _selected(st, mask_ref[0, ii, k0:k1, q0:q1])
                    elif masked:
                        st = _visible(
                            st, masked, row0 + start + q0, col0 + k0,
                            window, q_axis=1,
                        )
                    pt = jnp.exp(st - lse)
                    dvs.append(_mxu(pt, doi))
                    dpt = _scores(v_of[h][cols], doi)
                    dst = pt * (dpt - delta)
                    dks.append(_mxu(dst, qi))
                dv_scr[cols] = dv_scr[cols] + by_head(dvs)
                dk_scr[cols] = dk_scr[cols] + by_head(dks)

        _loop(first, full_from, functools.partial(body, crossed=on_diagonal))
        _loop(full_from, edge_from, functools.partial(body, crossed=False))
        _loop(edge_from, end, functools.partial(body, crossed=on_edge))

    if causal:
        # chunks above the diagonal, or behind the window, see nothing
        pl.when(first < end)(_chunk)
    else:
        _chunk()

    @pl.when(c == num_cq - 1)
    def _write():
        dk_ref[0] = (dk_scr[...] * sm_scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _pick_block(size: int, preferred: int) -> int:
    block = min(preferred, size)
    while size % block:
        block //= 2
    return max(block, 1)


# the grid streams the opposite sequence in chunks; inside a chunk the
# in-kernel block loop runs.  A chunk is the whole sequence up to this many
# bytes an array (8,192 rows of 64 bf16), which bounds scoped VMEM at any
# sequence length and head width.  Measured on a TPU v5e, kernels alone,
# bf16 causal forward + gradients at (1, 8192, 12, 64): 6.81 ms with
# chunks of 2,048 rows, 6.24 with 4,096, 6.00 with the whole 8,192
# (benchmarks/attention_sweep.py; PERF.md section 6, PR 28)
_CHUNK_BYTES = 1 << 20


def _pick_chunk(seq: int, block: int, preferred: int) -> int:
    """Chunk rows for the grid stream: a multiple of ``block`` (the
    in-chunk loop runs ``chunk // block`` sub-blocks — a chunk smaller
    than the block would run ZERO and silently emit garbage) that
    divides ``seq``, as close to ``preferred`` as those constraints
    allow."""
    num_blocks = seq // block  # block always divides seq (_pick_block)
    return block * _pick_block(num_blocks, max(1, preferred // block))


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8)
)
def flash_attention(
    q,
    k,
    v,
    causal: bool = False,
    sm_scale: float | None = None,
    # at most (_pick_block shrinks them to divide a sequence).  512x512
    # measured on a TPU v5e at the benchmark cells' three shapes, kernels
    # alone, ms a call against 256 / 512 / 1,024-square blocks:
    # (1, 8192, 12, 64) 12.09 / 6.81 / 6.50, (8, 1024, 12, 64)
    # 1.92 / 1.30 / 1.52, (2, 4096, 16, 128) 8.29 / 4.80 / 4.88; once a
    # block on the diagonal skips its unseen quarter 1,024-blocks no
    # longer fit VMEM at 8,192 and win 1% and 3% at the other two
    # (PERF.md section 6, PR 28)
    block_q: int = 512,
    block_k: int = 512,
    interpret: bool | None = None,
    window: int | None = None,
):
    """Blockwise flash attention, (B, S, H, D) layout.  ``q`` and ``k``
    share a width (the scores', which sets the default scale); ``v`` and the
    output may have another (latent attention: 192 beside 128).

    ``window`` (causal self-attention only): query ``t`` reads key ``s`` iff
    ``0 <= t - s < window``.  Blocks wholly behind that trailing edge are in
    no loop and chunks wholly behind it in no grid cell, in all three
    kernels, which then run as ``swa_fwd`` / ``swa_dq`` / ``swa_dkv``; a
    window that holds the whole sequence is no window
    (docs/designs/window_attention.md).

    ``interpret=None`` follows the default backend through
    :func:`kernel_interpret` (interpreted on CPU, compiled on TPU).

    Differentiable via custom_vjp with pallas kernels in BOTH directions
    (FlashAttention-2 structure): the forward saves (q, k, v, out, lse);
    the backward reconstructs probabilities blockwise from the saved
    logsumexp — one kernel for dQ, one for dK/dV — so neither direction
    ever materializes an (S, S) score matrix in HBM.
    """
    out, _lse = _flash_forward(
        q, k, v, causal, sm_scale, block_q, block_k, interpret, window=window
    )
    return out


def _heads_per_block(q, k, v) -> int:
    """How the kernels can address a head, from the three ``(batch,
    tokens, heads, width)`` shapes alone: 2 where a block of 128 lanes of
    the layer's own layout, ``(batch, tokens, heads * width)``, is two
    heads and a grid cell does both (64-wide heads, an even number of
    them, ungrouped); 0 where only the folded ``(batch * heads, tokens,
    width)`` form reaches a head (scores of 192 beside values of 128, an
    odd head count, grouping at 64), **and at widths of 128**, where the
    index maps could pick a head's lane block but nothing is won: a
    128-wide minor dimension is not padded, XLA writes a projection
    straight into the folded layout, and what sits between a projection
    and the kernels (rotary positions on a 4-D array) wants that layout
    too.  Measured on a TPU v5e, lanes against folded at 128:
    ``olmoe_1b7b_seq4096`` 72,380 against 74,340 tokens/s (73,660 with the
    rotary positions rewritten over merged rows),
    ``nemotron_twotower_seq8192`` 29,520 against 29,709 (PERF.md section
    6, PR 36)."""
    (heads, d), (kv_heads, d_v) = q.shape[2:], v.shape[2:]
    if 2 * d == 2 * d_v == _LANES and heads == kv_heads and heads % 2 == 0:
        return 2
    return 0


def flash_layout(q, k, v) -> str:
    """``"lanes"`` where the flash kernels read heads out of q, k and v as
    the layer holds them (no copy on either side of a kernel), ``"folded"``
    where the operands are turned to ``(batch * heads, tokens, width)``
    first.  A pure function of the three shapes, decided at trace time."""
    return "lanes" if _heads_per_block(q, k, v) else "folded"


def _flash_geometry(q, k, v, sm_scale, block_q, block_k, interpret):
    """Defaults filled in, blocks made to divide the sequences, and the
    rows of a staged chunk of q (for dK/dV) and of k (for the forward and
    dQ), from what the call can see: lengths, head widths, dtype.  A chunk
    of blocks that hold two 64-wide heads keeps a 64-wide chunk's rows: it
    fills the 128 lanes the narrow one was padded to in VMEM, and measured
    on a TPU v5e, kernels alone, (1, 8192, 12, 64): 6.15 / 5.99 / 5.72 ms
    with chunks of 2,048 / 4,096 / 8,192 rows, 6.01 for the folded form
    (PERF.md section 6, PR 36).  Where the scores are wider than the values
    (latent attention: 192 beside 128) the narrower width sizes the chunk:
    measured on a TPU v5e, kernels alone, (1, 8192, 32, 192 | 128): 28.04 / 26.27 / 24.64 ms with chunks of
    1,024 (what 192 alone would give) / 2,048 / 4,096 rows; 8,192 do not
    fit VMEM (PERF.md section 6, PR 34)."""
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    if interpret is None:
        interpret = on_mesh.default_interpret()
    block_q = _pick_block(q.shape[1], block_q)
    block_k = _pick_block(k.shape[1], block_k)
    width = min(q.shape[-1], v.shape[-1])
    rows = _CHUNK_BYTES // (width * q.dtype.itemsize)
    chunk_q = _pick_chunk(q.shape[1], block_q, rows)
    chunk_k = _pick_chunk(k.shape[1], block_k, rows)
    return sm_scale, block_q, block_k, chunk_q, chunk_k, interpret


class _HeadAddressing:
    """How the three ``pallas_call``s reach a head of a ``(batch, tokens,
    heads, width)`` array, chosen by :func:`_heads_per_block`: which array
    the call is handed, the grid's head dimension, and the block a grid
    cell ``(b, h, ...)`` reads.  One algorithm; the shapes say which
    addressing it can use."""

    def __init__(self, q, k, v):
        self.lanes = _heads_per_block(q, k, v)
        # heads a grid cell works, and the grid's head dimension
        self.per_cell = max(1, self.lanes)
        self.cells = q.shape[2] // self.per_cell
        # GQA without materializing repeated K/V: the q-head program reads
        # its group's single kv head.  THE one definition of the grouping
        # used by every kernel spec (the subtlest index math in these
        # kernels must not be copy-pasted)
        self.group = validate_gqa_heads(q, k, v)

    def operand(self, x):
        """``(B, S, H, D)`` as the kernels take it: its two minor
        dimensions merged (what the projections wrote, no copy), or folded
        to ``(B*H, S, D)`` (a copy through HBM)."""
        b, s, h, d = x.shape
        if self.lanes:
            return x.reshape(b, s, h * d)
        return x.transpose(0, 2, 1, 3).reshape(b * h, s, d)

    def shape(self, batch, seq, heads, d):
        """Of a kernel's result for ``(batch, seq, heads, d)``."""
        if self.lanes:
            return (batch, seq, heads * d)
        return (batch * heads, seq, d)

    def result(self, x, batch, heads):
        """A kernel's result back as ``(B, S, H, D)``."""
        if self.lanes:
            return x.reshape(*x.shape[:2], heads, x.shape[2] // heads)
        bh, s, d = x.shape
        return x.reshape(batch, heads, s, d).transpose(0, 2, 1, 3)

    def spec(self, rows, d, rows_index, kv_heads=0):
        """The block of ``rows`` tokens by one cell's heads of width ``d``,
        at the row block ``rows_index(h_cell, i, c)``; ``kv_heads``: of a
        key/value operand with that many heads, whose head is the query
        head's group's (folded form only: the lanes form is ungrouped)."""
        heads = kv_heads or self.cells * self.per_cell
        group = self.group if kv_heads else 1

        def index(b, h, i, c):
            if self.lanes:
                return (b, rows_index(h, i, c), h)
            # per_cell is 1: cell h is query head h
            return (b * heads + h // group, rows_index(h, i, c), 0)

        return pl.BlockSpec((1, rows, d * self.per_cell), index)

    def row_spec(self, block, index):
        """Of ``lse`` and ``delta``, ``(batch * heads, ...)`` lane-major
        float32 rows in both forms: a cell's heads are consecutive rows."""
        return pl.BlockSpec(
            (self.per_cell,) + block,
            lambda b, h, i, c: (b * self.cells + h,) + index(i, c),
        )


def _mask_operand(mask, block, index):
    """``(operands, in_specs)`` a selected-set call adds: the mask and its
    block of ``block`` behind the batch at ``index(b, h, i, c)``; nothing
    for a dense call, whose program stays as it was."""
    if mask is None:
        return [], []
    return [mask], [pl.BlockSpec((1,) + block, index)]


def _compiler_params(mask):
    return _FLASH_COMPILER_PARAMS if mask is None else _SELECTED_COMPILER_PARAMS


def _last_live_chunk(i, block_q, chunk_k):
    """The last k-chunk a causal q-block sees: the index maps stop there,
    so the pipeline fetches no chunk the kernel would skip."""
    return (i * block_q + block_q - 1) // chunk_k


def _first_live_chunk(i, block_q, chunk_k, window):
    """The first k-chunk a q-block reads under a window: the one that holds
    the first key its first row sees.  Python ints or traced int32."""
    first_key = i * block_q - (window - 1)
    if isinstance(first_key, int):
        return max(first_key, 0) // chunk_k
    return jnp.maximum(first_key, 0) // chunk_k


def _last_live_q_chunk(j, block_k, chunk_q, num_cq, window):
    """The last q-chunk whose rows read a k-block under a window: the one
    that holds the last row that sees the block's last column."""
    last_row = j * block_k + block_k - 1 + window - 1
    return _clip(last_row // chunk_q, 0, num_cq - 1)


def _window_streams(window, seq_q, seq_k, bq, bk, chunk_q, chunk_k):
    """``(k-chunks, q-chunks)`` the grids' innermost dimensions run under a
    window: the most chunks any q-block (forward, dQ) or k-block (dK/dV)
    reads, counted from its own first live chunk.  The chunks of the
    sequence that lie outside are in no grid cell."""
    over_k = max(
        _last_live_chunk(i, bq, chunk_k)
        - _first_live_chunk(i, bq, chunk_k, window) + 1
        for i in range(seq_q // bq)
    )
    num_cq = seq_q // chunk_q
    over_q = max(
        _last_live_q_chunk(j, bk, chunk_q, num_cq, window)
        - (j * bk) // chunk_q + 1
        for j in range(seq_k // bk)
    )
    return over_k, over_q


def _effective_window(window, causal, mask, seq_q, seq_k):
    """``window`` as the kernels take it: None where there is none or it
    holds the whole sequence (the dense kernels then run, under their own
    names)."""
    if window is None:
        return None
    if not causal or mask is not None or seq_q != seq_k:
        raise ValueError(
            "a window is built for causal self-attention without a "
            "selected set"
        )
    if window < 1:
        raise ValueError(f"window must be at least 1, got {window}")
    return None if window >= seq_k else int(window)


_SELECTED_NAMES = {
    FLASH_FWD: SELECTED_FWD, FLASH_DQ: SELECTED_DQ, FLASH_DKV: SELECTED_DKV,
}
_WINDOW_NAMES = {
    FLASH_FWD: WINDOW_FWD, FLASH_DQ: WINDOW_DQ, FLASH_DKV: WINDOW_DKV,
}


def _kernel_name(dense, mask, window):
    """The name a kernel's call runs under: the dense one, or its
    selected-set or window sibling."""
    if mask is not None:
        return _SELECTED_NAMES[dense]
    if window is not None:
        return _WINDOW_NAMES[dense]
    return dense


# jitted so that a model's layers share ONE trace and one lowering of each
# kernel (the twelve layers of the benchmark's LM traced them 36 times: on
# the chip's host 15 s of a 50 s set-up, PERF.md section 6, PR 28)
@functools.partial(
    jax.jit, static_argnums=(3, 4, 5, 6, 7), static_argnames=("window",),
    inline=True,
)
def _flash_forward(
    q, k, v, causal, sm_scale, block_q, block_k, interpret, mask=None,
    window=None,
):
    """``mask``: the selection's, ``(batch, seq_k / block_k, seq_q,
    block_k)`` int8 (``ops/sparse_attention.py``): the kernel then runs
    under its selected-set name and reads a chunk of it beside k and v.
    ``window``: the kernel runs under its window name over the chunks and
    blocks the window leaves."""
    q, k, v = jnp.asarray(q), jnp.asarray(k), jnp.asarray(v)
    sm_scale, block_q, block_k, chunk_q, chunk_k, interpret = _flash_geometry(
        q, k, v, sm_scale, block_q, block_k, interpret
    )
    batch, seq_q, heads, d = q.shape
    d_v = v.shape[-1]
    kv_heads = k.shape[2]
    seq_k = k.shape[1]
    num_ck = seq_k // chunk_k
    window = _effective_window(window, causal, mask, seq_q, seq_k)
    windowed = {}
    if window is not None:
        num_ck, _ = _window_streams(
            window, seq_q, seq_k, block_q, block_k, chunk_q, chunk_k
        )
        windowed = {"window": window}
    at = _HeadAddressing(q, k, v)

    def _q_block(h, i, c):
        return i

    def _kv_chunk(h, i, c):
        if window is not None:
            c = c + _first_live_chunk(i, block_q, chunk_k, window)
        if causal:
            c = jnp.minimum(c, _last_live_chunk(i, block_q, chunk_k))
        return c

    kernel = functools.partial(
        _flash_kernel,
        sm_scale=sm_scale,
        causal=causal,
        block_q=block_q,
        block_k=block_k,
        chunk_k=chunk_k,
        num_ck=num_ck,
        **({} if mask is None else {"selected": True}),
        **windowed,
    )
    with jax.named_scope(_FOLD):
        operands = [at.operand(x) for x in (q, k, v)]
    masks, mask_specs = _mask_operand(
        mask, (chunk_k // block_k, block_q, block_k),
        lambda b, h, i, c: (b, _kv_chunk(h, i, c), i, 0),
    )
    out, lse = pl.pallas_call(
        kernel,
        grid=(batch, at.cells, seq_q // block_q, num_ck),
        in_specs=[
            at.spec(block_q, d, _q_block),
            at.spec(chunk_k, d, _kv_chunk, kv_heads),
            at.spec(chunk_k, d_v, _kv_chunk, kv_heads),
        ] + mask_specs,
        out_specs=[
            at.spec(block_q, d_v, _q_block),
            at.row_spec((1, block_q), lambda i, c: (0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(
                at.shape(batch, seq_q, heads, d_v), q.dtype
            ),
            # lane-major and compact: a row of seq_q float32 a head
            jax.ShapeDtypeStruct((batch * heads, 1, seq_q), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((at.per_cell, block_q, _LANES), jnp.float32),  # m
            pltpu.VMEM((at.per_cell, block_q, _LANES), jnp.float32),  # l
            pltpu.VMEM((block_q, at.per_cell * d_v), jnp.float32),  # acc
        ],
        compiler_params=_compiler_params(mask),
        interpret=interpret,
        name=_kernel_name(FLASH_FWD, mask, window),
    )(*operands, *masks)
    with jax.named_scope(_FOLD):
        return at.result(out, batch, heads), lse


@functools.partial(
    jax.jit, static_argnums=(6, 7, 8, 9, 10), static_argnames=("window",),
    inline=True,
)
def _flash_backward(
    q, k, v, out, lse, g, causal, sm_scale, block_q, block_k, interpret,
    mask=None, mask_t=None, window=None,
):
    """``mask`` as the forward takes it (for dQ), ``mask_t`` its transpose
    by blocks, ``(batch, seq_q / block_q, seq_k, block_q)`` (for dK/dV);
    ``window`` as the forward takes it."""
    q, k, v = jnp.asarray(q), jnp.asarray(k), jnp.asarray(v)
    out, lse, g = jnp.asarray(out), jnp.asarray(lse), jnp.asarray(g)
    sm_scale, bq, bk, chunk_q, chunk_k, interpret = _flash_geometry(
        q, k, v, sm_scale, block_q, block_k, interpret
    )
    batch, seq_q, heads, d = q.shape
    d_v = v.shape[-1]
    kv_heads = k.shape[2]
    seq_k = k.shape[1]
    num_ck = seq_k // chunk_k
    num_cq = seq_q // chunk_q
    all_cq = num_cq  # of the sequence; num_cq: of a k-block's stream
    window = _effective_window(window, causal, mask, seq_q, seq_k)
    windowed = {}
    if window is not None:
        num_ck, num_cq = _window_streams(
            window, seq_q, seq_k, bq, bk, chunk_q, chunk_k
        )
        windowed = {"window": window}
    at = _HeadAddressing(q, k, v)

    with jax.named_scope(_FOLD):
        qf, kf, vf, dof = (at.operand(x) for x in (q, k, v, g))

    def _q_block(h, i, c):
        return i

    def _kv_chunk(h, i, c):
        if window is not None:
            c = c + _first_live_chunk(i, bq, chunk_k, window)
        if causal:
            c = jnp.minimum(c, _last_live_chunk(i, bq, chunk_k))
        return c

    row_block = at.row_spec((1, bq), lambda i, c: (0, i))
    dq_spec = at.spec(bq, d, _q_block)
    dq_shape = jax.ShapeDtypeStruct(at.shape(batch, seq_q, heads, d), q.dtype)
    dq_scratch = pltpu.VMEM((bq, at.per_cell * d), jnp.float32)
    if at.lanes:
        # the kernel makes delta_r = rowsum(dO * O) from out's blocks
        with jax.named_scope(_FOLD):
            extra = at.operand(out)
        extra_spec = at.spec(bq, d_v, _q_block)
        out_specs = [dq_spec, row_block]
        out_shape = [dq_shape, jax.ShapeDtypeStruct(lse.shape, jnp.float32)]
        scratch = [
            dq_scratch,
            pltpu.VMEM((at.per_cell, bq, _LANES), jnp.float32),  # delta
        ]
    else:
        # ... or is handed it, in the lse's layout
        with jax.named_scope(_FOLD):
            extra = jnp.sum(
                dof.astype(jnp.float32) * at.operand(out).astype(jnp.float32),
                axis=-1,
            )[:, None, :]  # (B*H, 1, S_q)
        extra_spec, out_specs, out_shape = row_block, dq_spec, dq_shape
        scratch = [dq_scratch]
    selected = {} if mask is None else {"selected": True}
    masks, mask_specs = _mask_operand(
        mask, (chunk_k // bk, bq, bk),
        lambda b, h, i, c: (b, _kv_chunk(h, i, c), i, 0),
    )
    made = pl.pallas_call(
        functools.partial(
            _flash_dq_kernel, sm_scale=sm_scale, causal=causal,
            block_q=bq, block_k=bk, chunk_k=chunk_k, num_ck=num_ck,
            makes_delta=bool(at.lanes), **selected, **windowed,
        ),
        grid=(batch, at.cells, seq_q // bq, num_ck),
        in_specs=[
            dq_spec,
            at.spec(chunk_k, d, _kv_chunk, kv_heads),
            at.spec(chunk_k, d_v, _kv_chunk, kv_heads),
            at.spec(bq, d_v, _q_block),
            row_block,
        ] + mask_specs + [extra_spec],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        compiler_params=_compiler_params(mask),
        interpret=interpret,
        name=_kernel_name(FLASH_DQ, mask, window),
    )(qf, kf, vf, dof, lse, *masks, extra)
    dq, delta = made if at.lanes else (made, extra)

    # dK/dV are computed per q-head (the kernel never materializes
    # repeated K/V either); a GQA group then sums its q-heads' parts —
    # one pass over (B, S_k, H, D), the gradient analogue of the repeat.
    # Grid: k-block outer, q-CHUNK innermost (the accumulation stream).
    def _q_chunk(h, j, c):
        if window is not None:
            # from the first q-chunk whose rows reach this k-block's
            # columns to the last whose rows still read them
            return jnp.minimum(
                c + (j * bk) // chunk_q,
                _last_live_q_chunk(j, bk, chunk_q, all_cq, window),
            )
        if causal:
            # the first q-chunk whose rows reach this k-block's columns
            c = jnp.maximum(c, (j * bk) // chunk_q)
        return c

    def _k_block(h, j, c):
        return j

    # a head's whole row, one q-block (or half of one, where the kernel
    # halves the blocks on the diagonal) a sublane row: the kernel picks
    # its (1, n) by row index
    saved = _diagonal_half(bq, bk) or bq
    rows = (batch * heads, seq_q // saved, saved)
    row_spec = at.row_spec(rows[1:], lambda j, c: (0, 0))
    masks, mask_specs = _mask_operand(
        mask_t, (chunk_q // bq, bk, bq),
        lambda b, h, j, c: (b, _q_chunk(h, j, c), j, 0),
    )
    dk_per_q, dv_per_q = pl.pallas_call(
        functools.partial(
            _flash_dkv_kernel, sm_scale=sm_scale, causal=causal,
            block_q=bq, block_k=bk, chunk_q=chunk_q, num_cq=num_cq,
            **selected, **windowed,
            **({"all_cq": all_cq} if windowed else {}),
        ),
        grid=(batch, at.cells, seq_k // bk, num_cq),
        in_specs=[
            at.spec(chunk_q, d, _q_chunk),
            at.spec(bk, d, _k_block, kv_heads),
            at.spec(bk, d_v, _k_block, kv_heads),
            at.spec(chunk_q, d_v, _q_chunk),
            row_spec,
            row_spec,
        ] + mask_specs,
        out_specs=[at.spec(bk, d, _k_block), at.spec(bk, d_v, _k_block)],
        out_shape=[
            jax.ShapeDtypeStruct(at.shape(batch, seq_k, heads, d), k.dtype),
            jax.ShapeDtypeStruct(
                at.shape(batch, seq_k, heads, d_v), v.dtype
            ),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, at.per_cell * d), jnp.float32),  # dk
            pltpu.VMEM((bk, at.per_cell * d_v), jnp.float32),  # dv
        ],
        compiler_params=_compiler_params(mask),
        interpret=interpret,
        name=_kernel_name(FLASH_DKV, mask, window),
    )(qf, kf, vf, dof, lse.reshape(rows), delta.reshape(rows), *masks)

    with jax.named_scope(_FOLD):
        dq = at.result(dq, batch, heads)
        dk = at.result(dk_per_q, batch, heads)
        dv = at.result(dv_per_q, batch, heads)
        if at.group > 1:
            # sum each kv head's query group: (B, S, H, D) -> (B, S, KVH, D)
            dk = dk.reshape(batch, seq_k, kv_heads, at.group, d).sum(axis=3)
            dv = dv.reshape(batch, seq_k, kv_heads, at.group, d_v).sum(axis=3)
        return dq, dk.astype(k.dtype), dv.astype(v.dtype)


def _flash_fwd_rule(
    q, k, v, causal, sm_scale, block_q, block_k, interpret, window
):
    out, lse = _flash_forward(
        q, k, v, causal, sm_scale, block_q, block_k, interpret, window=window
    )
    return out, (q, k, v, out, lse)


def _flash_bwd_rule(
    causal, sm_scale, block_q, block_k, interpret, window, res, g
):
    q, k, v, out, lse = res
    return _flash_backward(
        q, k, v, out, lse, g, causal, sm_scale, block_q, block_k, interpret,
        window=window,
    )


flash_attention.defvjp(_flash_fwd_rule, _flash_bwd_rule)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def selected_flash_attention(
    q, k, v, mask, mask_t, sm_scale: float | None = None,
    interpret: bool | None = None,
):
    """Causal attention over a selected set of keys a query, through the
    flash kernels: ``mask`` ``(batch, seq / block, seq, block)`` int8 holds
    1 where query ``t`` reads key ``s`` (``[b, s // block, t, s % block]``,
    causality included) and ``mask_t`` the same by query blocks
    (``[b, t // block, s, t % block]``), as
    ``ops/sparse_attention.py::index_select`` and ``transpose_mask`` make
    them.  Returns ``(out, lse)``: the per-head logsumexp of the scaled
    scores over the selected set, ``(batch * heads, 1, seq)``, is what the
    indexer's loss rebuilds the probabilities from; it takes no gradient.
    No block is skipped for being unselected: the set is per token."""
    block_k, block_q = mask.shape[3], mask_t.shape[3]
    return _flash_forward(
        q, k, v, True, sm_scale, block_q, block_k, interpret, mask
    )


def _selected_fwd_rule(q, k, v, mask, mask_t, sm_scale, interpret):
    out, lse = selected_flash_attention(
        q, k, v, mask, mask_t, sm_scale, interpret
    )
    return (out, lse), (q, k, v, out, lse, mask, mask_t)


def _selected_bwd_rule(sm_scale, interpret, res, g):
    q, k, v, out, lse, mask, mask_t = res
    dq, dk, dv = _flash_backward(
        q, k, v, out, lse, g[0], True, sm_scale, mask_t.shape[3],
        mask.shape[3], interpret, mask, mask_t,
    )
    return dq, dk, dv, None, None


selected_flash_attention.defvjp(_selected_fwd_rule, _selected_bwd_rule)


# ---- dispatch --------------------------------------------------------------


def window_block_plan(q, k, v, window: int) -> tuple[int, int, int]:
    """:func:`flash_block_plan`'s ``(live, masked, skipped)`` for causal
    attention of these ``(batch, tokens, heads, width)`` operands under
    ``window``, at the blocks the kernels choose for them, over the batch
    and the heads: what a window layer counts of itself."""
    _, bq, bk, _, _, _ = _flash_geometry(q, k, v, None, 512, 512, False)
    seq = q.shape[1]
    plan = flash_block_plan(
        seq, seq, bq, bk, True,
        _effective_window(window, True, None, seq, k.shape[1]),
    )
    return tuple(q.shape[0] * q.shape[2] * n for n in plan)


def attention(
    q, k, v, causal: bool = False, sm_scale: float | None = None,
    window: int | None = None,
):
    """Self-attention entry point for layers: sequence-parallel attention
    (ring by default, ulysses when configured) when the registered mesh
    has an ``sp`` axis > 1, else the local flash kernel, mapped over the
    mesh's batch and head axes (``ops/on_mesh.py``)."""
    from elasticdl_tpu.ops.ring_attention import (
        ring_attention,
        sequence_shard_spec,
    )
    from elasticdl_tpu.ops.ulysses import ulysses_attention

    mesh, sp_axis, sp_impl = get_attention_mesh()
    if mesh is not None and (
        sp_axis in mesh.axis_names and mesh.shape[sp_axis] > 1
    ):
        if window is not None:
            raise NotImplementedError(
                "a window across the sp axis is not built: the ring and "
                "ulysses schedules read every key "
                "(docs/designs/window_attention.md)"
            )
        impl = (
            ulysses_attention if sp_impl == "ulysses" else ring_attention
        )
        return impl(
            q, k, v, mesh=mesh, axis_name=sp_axis, causal=causal,
            sm_scale=sm_scale,
        )
    lanes = flash_layout(q, k, v) == "lanes"

    def specs(mesh):
        # the layout the sp paths share, with no sequence axis: batch on the
        # data-parallel axes, heads on tp (not under GQA — query groups must
        # stay aligned)
        spec = sequence_shard_spec(mesh, None, q.shape[0], q.shape[2])
        if k.shape[2] != q.shape[2]:
            spec = jax.sharding.PartitionSpec(spec[0], None, None, None)
        if lanes:
            spec = jax.sharding.PartitionSpec(*spec[:3])
        return (spec, spec, spec), spec

    # with heads read out of lanes the per-device region is handed the
    # projections' merged rows and gives them back: a (..., heads, 64) array
    # at its boundary is a value XLA lays out tokens-minor, and every
    # operand of the kernels then crosses a copy again (13 a layer in the
    # compiled dp=4 step)
    def merged(x):
        return x.reshape(*x.shape[:2], -1)

    def split(x):
        return x.reshape(*x.shape[:2], -1, q.shape[-1])

    return on_mesh.mapped(
        functools.partial(
            flash_attention, causal=causal, sm_scale=sm_scale, window=window
        ),
        (q, k, v), specs, crossing=(merged, split) if lanes else None,
    )
