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

import contextlib
import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# lane width of TPU vector registers: the m/l scratch accumulators keep
# this many (all-equal) columns so stores stay tile-aligned
_LANES = 128

# batch*heads and q/k-block dims are independent programs; only the
# innermost (accumulation stream) dim is order-dependent — telling
# Mosaic lets it pipeline the outer dims across cores
_FLASH_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary")
)

_NEG_INF = -1e30

# the three kernels' names: each ``pallas_call``'s ``name``, which the
# compiled custom-call carries, so a device trace tells forward, dQ and
# dK/dV apart by name (perf/layer_metrics/flash_*_roofline.lm.py)
FLASH_FWD = "flash_fwd"
FLASH_DQ = "flash_dq"
FLASH_DKV = "flash_dkv"


# ---- mesh context (set by the trainer, read by layers) ---------------------

# process-global, NOT thread-local: one mesh per worker process (the SPMD
# model), and jit tracing may happen on a different thread than trainer
# construction
_mesh_context: list = [None, "sp", "ring"]


_SP_IMPLS = ("ring", "ulysses")


def set_attention_mesh(mesh, sp_axis: str = "sp", sp_impl: str = "ring"):
    """Register the mesh attention layers should use for sequence
    parallelism.  A ``None`` mesh (or an ``sp`` axis of size 1) makes
    :func:`attention` run the local kernel and lets GSPMD handle any
    sharding.  ``sp_impl`` picks the sequence-parallel algorithm:
    ``"ring"`` (K/V rotation; any head count) or ``"ulysses"``
    (head/sequence all-to-all; needs heads % sp == 0).  SPMDTrainer
    scopes this around every step call via :func:`attention_mesh_scope`
    — two trainers with different meshes in one process (bench, dryrun)
    must not see each other's mesh at (re)trace time."""
    if sp_impl not in _SP_IMPLS:
        # a typo must not silently fall back to ring
        raise ValueError(
            f"unknown sp_impl {sp_impl!r}; valid: {_SP_IMPLS}"
        )
    _mesh_context[0] = mesh
    _mesh_context[1] = sp_axis
    _mesh_context[2] = sp_impl


def get_attention_mesh():
    return _mesh_context[0], _mesh_context[1], _mesh_context[2]


@contextlib.contextmanager
def attention_mesh_scope(mesh, sp_axis: str = "sp", sp_impl: str | None = None):
    """Set-and-restore the attention mesh: tracing inside the scope (jit
    retraces on new shapes happen at call time) reads this mesh.
    ``sp_impl=None`` preserves the currently selected implementation —
    SPMDTrainer's step scopes must not clobber a global
    ``set_attention_mesh(..., sp_impl="ulysses")`` choice."""
    prev = tuple(_mesh_context)
    set_attention_mesh(
        mesh, sp_axis, _mesh_context[2] if sp_impl is None else sp_impl
    )
    try:
        yield
    finally:
        _mesh_context[:] = prev


def kernel_interpret(platform: str) -> bool:
    """Whether the pallas kernels run INTERPRETED on ``platform``: the
    CPU has no Mosaic, so it interprets (tests run the same kernel code
    the chip compiles); a TPU compiles; any other platform is an error —
    never a silent trip through the interpreter."""
    if platform == "cpu":
        return True
    if platform == "tpu":
        return False
    raise ValueError(
        f"pallas flash attention runs compiled on 'tpu' and interpreted "
        f"on 'cpu'; got platform {platform!r}"
    )


# ---- reference (jnp) -------------------------------------------------------


def validate_gqa_heads(q, k, v) -> int:
    """The ONE place the grouped-query head constraint lives: K and V
    must agree, and q heads must be a multiple of kv heads.  Returns the
    group factor (1 = plain MHA)."""
    q_heads, kv_heads = q.shape[2], k.shape[2]
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


def mha_reference(q, k, v, causal: bool = False, sm_scale: float | None = None):
    """Plain multi-head attention, (B, S, H, D) layout (K/V may carry
    fewer heads — GQA) — the numerical oracle for the kernels and the
    CPU fallback."""
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
        scores = jnp.where(row >= col, scores, _NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v.astype(jnp.float32))
    return out.astype(q.dtype)


# ---- pallas flash kernel ---------------------------------------------------


# the grid streams the opposite sequence in chunks of this many rows;
# inside a chunk the original in-kernel block loop runs.  Bounds scoped
# VMEM at any sequence length (full-seq refs OOM at 8k+) while keeping
# the ≤2048 fast path IDENTICAL to a single staged ref — measured: pure
# per-block grid streaming cost 13% tokens/sec on gpt2s@2048
_SEQ_CHUNK = 2048


def _causal_mask(s, row0, col0, block_q, block_k):
    """Mask scores below the causal diagonal for a (block_q, block_k)
    tile whose global top-left corner is (row0, col0)."""
    row = row0 + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0
    )
    col = col0 + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1
    )
    return jnp.where(row >= col, s, _NEG_INF)


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
    *, sm_scale, causal, block_q, block_k, chunk_k, num_ck,
):
    """One (batch*head, q-block, k-chunk) grid cell of the online-softmax
    forward: loop block_k sub-blocks of the staged (1, chunk_k, d) K/V
    chunk through the online softmax.  m/l/acc persist across the chunk
    stream in VMEM scratch; the output and the per-row logsumexp (of the
    SCALED scores — the backward rebuilds probabilities from it) are
    written once at the last chunk."""
    i = pl.program_id(1)
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    row_end = (i + 1) * block_q  # exclusive causal row bound
    # chunks fully above the causal diagonal contribute nothing
    chunk_live = c * chunk_k < row_end if causal else None

    def _chunk():
        q = q_ref[0].astype(jnp.float32) * sm_scale  # (block_q, D)
        nb = chunk_k // block_k
        if causal:
            # stop at the last sub-block intersecting this q-block's rows
            nb_live = jnp.clip(
                (row_end - c * chunk_k + block_k - 1) // block_k, 0, nb
            )
        else:
            nb_live = nb

        def body(jj, _):
            kb = k_ref[0, pl.ds(jj * block_k, block_k), :].astype(
                jnp.float32
            )
            vb = v_ref[0, pl.ds(jj * block_k, block_k), :].astype(
                jnp.float32
            )
            s = jax.lax.dot_general(
                q, kb, (((1,), (1,)), ((), ()))
            )  # (block_q, block_k)
            if causal:
                s = _causal_mask(
                    s, i * block_q, c * chunk_k + jj * block_k,
                    block_q, block_k,
                )
            m_prev = m_scr[...]  # (block_q, _LANES), columns all equal
            l_prev = l_scr[...]
            m_next = jnp.maximum(
                m_prev, jnp.max(s, axis=1, keepdims=True)
            )
            alpha = jnp.exp(m_prev - m_next)
            p = jnp.exp(s - m_next[:, 0:1])
            l_scr[...] = alpha * l_prev + p.sum(axis=1, keepdims=True)
            m_scr[...] = m_next
            acc_scr[...] = (
                acc_scr[...] * alpha[:, 0:1] + jax.lax.dot(p, vb)
            )
            return 0

        jax.lax.fori_loop(0, nb_live, body, 0)

    if causal:
        pl.when(chunk_live)(_chunk)
    else:
        _chunk()

    @pl.when(c == num_ck - 1)
    def _write():
        l = l_scr[...][:, 0:1]
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)
        # (block_q, 1) trailing unit dim: TPU block shapes must tile the
        # last two dims, and a 2-D (1, block_q) block would not
        lse_ref[0] = m_scr[...][:, 0:1] + jnp.log(l)


def _flash_dq_kernel(
    q_ref,
    k_ref,
    v_ref,
    do_ref,
    lse_ref,
    delta_ref,
    dq_ref,
    acc_scr,
    *,
    sm_scale,
    causal,
    block_q,
    block_k,
    chunk_k,
    num_ck,
):
    """dQ cell per (batch*head, q-block, k-chunk): rebuild p from the
    saved logsumexp, accumulate dq = sm_scale * ds @ K into VMEM scratch
    across the chunk stream (same structure as the forward)."""
    i = pl.program_id(1)
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    row_end = (i + 1) * block_q
    chunk_live = c * chunk_k < row_end if causal else None

    def _chunk():
        q = q_ref[0].astype(jnp.float32) * sm_scale
        do = do_ref[0].astype(jnp.float32)  # (block_q, D)
        lse = lse_ref[0]  # (block_q, 1)
        delta = delta_ref[0]  # (block_q, 1)
        nb = chunk_k // block_k
        if causal:
            nb_live = jnp.clip(
                (row_end - c * chunk_k + block_k - 1) // block_k, 0, nb
            )
        else:
            nb_live = nb

        def body(jj, _):
            kb = k_ref[0, pl.ds(jj * block_k, block_k), :].astype(
                jnp.float32
            )
            vb = v_ref[0, pl.ds(jj * block_k, block_k), :].astype(
                jnp.float32
            )
            s = jax.lax.dot_general(q, kb, (((1,), (1,)), ((), ())))
            if causal:
                s = _causal_mask(
                    s, i * block_q, c * chunk_k + jj * block_k,
                    block_q, block_k,
                )
            p = jnp.exp(s - lse)  # (block_q, block_k)
            dp = jax.lax.dot_general(do, vb, (((1,), (1,)), ((), ())))
            ds = p * (dp - delta)
            acc_scr[...] = acc_scr[...] + jax.lax.dot(ds, kb)
            return 0

        jax.lax.fori_loop(0, nb_live, body, 0)

    if causal:
        pl.when(chunk_live)(_chunk)
    else:
        _chunk()

    @pl.when(c == num_ck - 1)
    def _write():
        dq_ref[0] = (acc_scr[...] * sm_scale).astype(dq_ref.dtype)


def _flash_dkv_kernel(
    q_ref,
    k_ref,
    v_ref,
    do_ref,
    lse_ref,
    delta_ref,
    dk_ref,
    dv_ref,
    dk_scr,
    dv_scr,
    *,
    sm_scale,
    causal,
    block_q,
    block_k,
    chunk_q,
    num_cq,
):
    """dK/dV cell per (batch*head, k-block, q-chunk): loop block_q
    sub-blocks of the staged (1, chunk_q, d) Q/dO chunk, dv += p^T @ dO
    and dk += ds^T @ (sm_scale * q) accumulating in VMEM scratch."""
    j = pl.program_id(1)
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    col0 = j * block_k  # first causal-visible column of this k block
    # chunks whose LAST row is still above the diagonal see nothing
    chunk_live = (c + 1) * chunk_q > col0 if causal else None

    def _chunk():
        kb = k_ref[0].astype(jnp.float32)  # (block_k, D)
        vb = v_ref[0].astype(jnp.float32)
        nb = chunk_q // block_q
        if causal:
            # first sub-block whose rows reach this k block's columns
            ii0 = jnp.clip((col0 - c * chunk_q) // block_q, 0, nb)
        else:
            ii0 = 0

        def body(ii, _):
            qi = (
                q_ref[0, pl.ds(ii * block_q, block_q), :].astype(
                    jnp.float32
                )
                * sm_scale
            )
            doi = do_ref[0, pl.ds(ii * block_q, block_q), :].astype(
                jnp.float32
            )
            lse = lse_ref[0, pl.ds(ii * block_q, block_q), :]
            delta = delta_ref[0, pl.ds(ii * block_q, block_q), :]
            s = jax.lax.dot_general(qi, kb, (((1,), (1,)), ((), ())))
            if causal:
                s = _causal_mask(
                    s, c * chunk_q + ii * block_q, col0,
                    block_q, block_k,
                )
            p = jnp.exp(s - lse)  # (block_q, block_k)
            dv_scr[...] = dv_scr[...] + jax.lax.dot_general(
                p, doi, (((0,), (0,)), ((), ()))
            )
            dp = jax.lax.dot_general(doi, vb, (((1,), (1,)), ((), ())))
            ds = p * (dp - delta)
            dk_scr[...] = dk_scr[...] + jax.lax.dot_general(
                ds, qi, (((0,), (0,)), ((), ()))
            )
            return 0

        jax.lax.fori_loop(ii0, nb, body, 0)

    if causal:
        pl.when(chunk_live)(_chunk)
    else:
        _chunk()

    @pl.when(c == num_cq - 1)
    def _write():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _pick_block(size: int, preferred: int) -> int:
    block = min(preferred, size)
    while size % block:
        block //= 2
    return max(block, 1)


def _pick_chunk(seq: int, block: int) -> int:
    """Chunk rows for the grid stream: a multiple of ``block`` (the
    in-chunk loop runs ``chunk // block`` sub-blocks — a chunk smaller
    than the block would run ZERO and silently emit garbage) that
    divides ``seq``, as close to ``_SEQ_CHUNK`` as those constraints
    allow."""
    num_blocks = seq // block  # block always divides seq (_pick_block)
    return block * _pick_block(num_blocks, max(1, _SEQ_CHUNK // block))


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7)
)
def flash_attention(
    q,
    k,
    v,
    causal: bool = False,
    sm_scale: float | None = None,
    # 512x512 measured on v5e: 8-17x faster than 128x128 across seq
    # 2048-8192 / head_dim 64-128 (small blocks starve the mosaic
    # pipeline); _pick_block shrinks them for shorter sequences
    block_q: int = 512,
    block_k: int = 512,
    interpret: bool | None = None,
):
    """Blockwise flash attention, (B, S, H, D) layout.

    ``interpret=None`` follows the default backend through
    :func:`kernel_interpret` (interpreted on CPU, compiled on TPU).

    Differentiable via custom_vjp with pallas kernels in BOTH directions
    (FlashAttention-2 structure): the forward saves (q, k, v, out, lse);
    the backward reconstructs probabilities blockwise from the saved
    logsumexp — one kernel for dQ, one for dK/dV — so neither direction
    ever materializes an (S, S) score matrix in HBM.
    """
    out, _lse = _flash_forward(
        q, k, v, causal, sm_scale, block_q, block_k, interpret
    )
    return out


def _flash_geometry(q, k, sm_scale, block_q, block_k, interpret):
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    if interpret is None:
        interpret = kernel_interpret(jax.default_backend())
    block_q = _pick_block(q.shape[1], block_q)
    block_k = _pick_block(k.shape[1], block_k)
    return sm_scale, block_q, block_k, interpret


def _fold_heads(x):
    """(B, S, H, D) -> (B*H, S, D)."""
    b, s, h, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, s, d)


def _kv_head(bh, heads, kv_heads, group):
    """Folded-KV row for folded-Q row ``bh``: GQA without materializing
    repeated K/V — the q-head program reads its group's single kv head.
    THE one definition of the grouping used by every kernel spec (the
    subtlest index math in these kernels must not be copy-pasted)."""
    return (bh // heads) * kv_heads + (bh % heads) // group


def _unfold_heads(x, batch, heads):
    bh, s, d = x.shape
    return x.reshape(batch, heads, s, d).transpose(0, 2, 1, 3)


def _flash_forward(q, k, v, causal, sm_scale, block_q, block_k, interpret):
    q, k, v = jnp.asarray(q), jnp.asarray(k), jnp.asarray(v)
    sm_scale, block_q, block_k, interpret = _flash_geometry(
        q, k, sm_scale, block_q, block_k, interpret
    )
    batch, seq_q, heads, d = q.shape
    group = validate_gqa_heads(q, k, v)
    kv_heads = k.shape[2]
    seq_k = k.shape[1]

    chunk_k = _pick_chunk(seq_k, block_k)
    num_ck = seq_k // chunk_k

    def _kv_index(b, i, c):
        return (_kv_head(b, heads, kv_heads, group), c, 0)

    qf, kf, vf = _fold_heads(q), _fold_heads(k), _fold_heads(v)
    kernel = functools.partial(
        _flash_kernel,
        sm_scale=sm_scale,
        causal=causal,
        block_q=block_q,
        block_k=block_k,
        chunk_k=chunk_k,
        num_ck=num_ck,
    )
    out, lse = pl.pallas_call(
        kernel,
        grid=(batch * heads, seq_q // block_q, num_ck),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, c: (b, i, 0)),
            pl.BlockSpec((1, chunk_k, d), _kv_index),
            pl.BlockSpec((1, chunk_k, d), _kv_index),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, c: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, c: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((batch * heads, seq_q, d), q.dtype),
            jax.ShapeDtypeStruct((batch * heads, seq_q, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, _LANES), jnp.float32),  # m
            pltpu.VMEM((block_q, _LANES), jnp.float32),  # l
            pltpu.VMEM((block_q, d), jnp.float32),  # acc
        ],
        compiler_params=_FLASH_COMPILER_PARAMS,
        interpret=interpret,
        name=FLASH_FWD,
    )(qf, kf, vf)
    return _unfold_heads(out, batch, heads), lse


def _flash_backward(
    q, k, v, out, lse, g, causal, sm_scale, block_q, block_k, interpret
):
    q, k, v = jnp.asarray(q), jnp.asarray(k), jnp.asarray(v)
    out, lse, g = jnp.asarray(out), jnp.asarray(lse), jnp.asarray(g)
    sm_scale, block_q, block_k, interpret = _flash_geometry(
        q, k, sm_scale, block_q, block_k, interpret
    )
    batch, seq_q, heads, d = q.shape
    group = validate_gqa_heads(q, k, v)
    kv_heads = k.shape[2]
    seq_k = k.shape[1]

    chunk_k = _pick_chunk(seq_k, block_k)
    num_ck = seq_k // chunk_k
    chunk_q = _pick_chunk(seq_q, block_q)
    num_cq = seq_q // chunk_q

    def _kv_chunk_index(b, i, c):
        return (_kv_head(b, heads, kv_heads, group), c, 0)

    qf, kf, vf = _fold_heads(q), _fold_heads(k), _fold_heads(v)
    dof = _fold_heads(g)
    # delta_r = rowsum(dO * O): the softmax-jacobian correction term;
    # trailing unit dim matches the lse layout (TPU block tiling)
    delta = jnp.sum(
        dof.astype(jnp.float32)
        * _fold_heads(out).astype(jnp.float32),
        axis=-1,
        keepdims=True,
    )  # (B*H, S_q, 1)

    common = dict(
        sm_scale=sm_scale, causal=causal, block_q=block_q, block_k=block_k
    )
    dq = pl.pallas_call(
        functools.partial(
            _flash_dq_kernel, chunk_k=chunk_k, num_ck=num_ck, **common
        ),
        grid=(batch * heads, seq_q // block_q, num_ck),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, c: (b, i, 0)),
            pl.BlockSpec((1, chunk_k, d), _kv_chunk_index),
            pl.BlockSpec((1, chunk_k, d), _kv_chunk_index),
            pl.BlockSpec((1, block_q, d), lambda b, i, c: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, c: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, c: (b, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, c: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((batch * heads, seq_q, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=_FLASH_COMPILER_PARAMS,
        interpret=interpret,
        name=FLASH_DQ,
    )(qf, kf, vf, dof, lse, delta)

    # dK/dV are computed per q-head (the kernel never materializes
    # repeated K/V either); a GQA group then sums its q-heads' parts —
    # one (B, H, S_k, D) pass, the gradient analogue of the repeat.
    # Grid: k-block outer, q-CHUNK innermost (the accumulation stream).
    dk_per_q, dv_per_q = pl.pallas_call(
        functools.partial(
            _flash_dkv_kernel, chunk_q=chunk_q, num_cq=num_cq, **common
        ),
        grid=(batch * heads, seq_k // block_k, num_cq),
        in_specs=[
            pl.BlockSpec((1, chunk_q, d), lambda b, j, c: (b, c, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j, c: (
                _kv_head(b, heads, kv_heads, group), j, 0
            )),
            pl.BlockSpec((1, block_k, d), lambda b, j, c: (
                _kv_head(b, heads, kv_heads, group), j, 0
            )),
            pl.BlockSpec((1, chunk_q, d), lambda b, j, c: (b, c, 0)),
            pl.BlockSpec((1, chunk_q, 1), lambda b, j, c: (b, c, 0)),
            pl.BlockSpec((1, chunk_q, 1), lambda b, j, c: (b, c, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda b, j, c: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j, c: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((batch * heads, seq_k, d), k.dtype),
            jax.ShapeDtypeStruct((batch * heads, seq_k, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),  # dk
            pltpu.VMEM((block_k, d), jnp.float32),  # dv
        ],
        compiler_params=_FLASH_COMPILER_PARAMS,
        interpret=interpret,
        name=FLASH_DKV,
    )(qf, kf, vf, dof, lse, delta)

    dq = _unfold_heads(dq, batch, heads)
    dk = _unfold_heads(dk_per_q, batch, heads)
    dv = _unfold_heads(dv_per_q, batch, heads)
    if group > 1:
        # sum each kv head's query group: (B, S, H, D) -> (B, S, KVH, D)
        dk = dk.reshape(batch, seq_k, kv_heads, group, d).sum(axis=3)
        dv = dv.reshape(batch, seq_k, kv_heads, group, d).sum(axis=3)
    return dq, dk.astype(k.dtype), dv.astype(v.dtype)


def _flash_fwd_rule(q, k, v, causal, sm_scale, block_q, block_k, interpret):
    out, lse = _flash_forward(
        q, k, v, causal, sm_scale, block_q, block_k, interpret
    )
    return out, (q, k, v, out, lse)


def _flash_bwd_rule(causal, sm_scale, block_q, block_k, interpret, res, g):
    q, k, v, out, lse = res
    return _flash_backward(
        q, k, v, out, lse, g, causal, sm_scale, block_q, block_k, interpret
    )


flash_attention.defvjp(_flash_fwd_rule, _flash_bwd_rule)


# ---- dispatch --------------------------------------------------------------


def attention(q, k, v, causal: bool = False, sm_scale: float | None = None):
    """Self-attention entry point for layers: sequence-parallel attention
    (ring by default, ulysses when configured) when the registered mesh
    has an ``sp`` axis > 1, else the local flash kernel — mapped over the
    mesh's batch and head axes, because a compiled pallas kernel is an
    opaque custom call GSPMD cannot partition: JAX refuses to lower it
    bare inside a multi-device jitted program (interpreted, on CPU, it
    is ordinary HLO and partitions, which hid this from every test)."""
    from elasticdl_tpu.ops.ring_attention import (
        ring_attention,
        sequence_shard_spec,
    )
    from elasticdl_tpu.ops.ulysses import ulysses_attention

    mesh, sp_axis, sp_impl = get_attention_mesh()
    if mesh is None:
        return flash_attention(q, k, v, causal=causal, sm_scale=sm_scale)
    if sp_axis in mesh.axis_names and mesh.shape[sp_axis] > 1:
        impl = (
            ulysses_attention if sp_impl == "ulysses" else ring_attention
        )
        return impl(
            q, k, v, mesh=mesh, axis_name=sp_axis, causal=causal,
            sm_scale=sm_scale,
        )
    # interpret follows the MESH's platform, not the process default: a
    # CPU mesh on a TPU-default machine (virtual-device dryrun) compiles
    # for CPU, where pallas only runs interpreted
    local = functools.partial(
        flash_attention,
        causal=causal,
        sm_scale=sm_scale,
        interpret=kernel_interpret(mesh.devices.flat[0].platform),
    )
    if mesh.devices.size == 1 or jax.sharding.get_abstract_mesh().manual_axes:
        # one device, or already inside a caller's per-device region
        return local(q, k, v)
    # the layout the sp paths share, with no sequence axis: batch on the
    # data-parallel axes, heads on tp (not under GQA — query groups must
    # stay aligned)
    spec = sequence_shard_spec(mesh, None, q.shape[0], q.shape[2])
    if k.shape[2] != q.shape[2]:
        spec = jax.sharding.PartitionSpec(spec[0], None, None, None)
    return jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )(q, k, v)
