"""Ring attention: sequence-parallel attention over the ``sp`` mesh axis.

Long-context half of the attention stack (the single-device half is
:mod:`.attention`): Q, K, V are sharded along the sequence dimension over
``sp``; each device computes attention of its local Q chunk against every
K/V chunk by rotating K/V around the ring with ``lax.ppermute`` (ICI
neighbor hops — bandwidth-optimal, no all-gather materializing the full
sequence), merging per-chunk results with the same online-softmax update
the flash kernel uses blockwise.

The reference has nothing comparable (no sequence dimension anywhere,
SURVEY §5); this is a required capability of the TPU rebuild.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

_NEG_INF = -1e30


def _chunk_scores(q, k, sm_scale, causal, q_offset, k_offset):
    """(B, H, Sq, Sk) scores of the local Q against one K chunk, with the
    causal mask evaluated in GLOBAL positions."""
    s = jnp.einsum(
        "bqhd,bkhd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32)
    )
    s = s * sm_scale
    if causal:
        s_q, s_k = q.shape[1], k.shape[1]
        row = q_offset + jax.lax.broadcasted_iota(jnp.int32, (s_q, s_k), 0)
        col = k_offset + jax.lax.broadcasted_iota(jnp.int32, (s_q, s_k), 1)
        s = jnp.where(row >= col, s, _NEG_INF)
    return s


def _ring_attention_local(
    q, k, v, *, axis_name, axis_size, causal, sm_scale
):
    """Per-shard body (runs under shard_map): local seq chunks in
    (B, S/n, H, D) layout."""
    my_idx = jax.lax.axis_index(axis_name)
    chunk_q = q.shape[1]
    chunk_k = k.shape[1]
    batch, _, heads, d = q.shape
    perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]

    group = q.shape[2] // k.shape[2]  # GQA: rotate the SMALL kv tensors

    def step(s, carry):
        acc, m, l, k_cur, v_cur = carry
        # the chunk we hold at step s started on device (my_idx - s)
        src = (my_idx - s) % axis_size
        # expand grouped kv heads locally, AFTER the rotation — ppermute
        # traffic stays at kv_heads size
        k_exp = jnp.repeat(k_cur, group, axis=2) if group > 1 else k_cur
        v_exp = jnp.repeat(v_cur, group, axis=2) if group > 1 else v_cur
        scores = _chunk_scores(
            q, k_exp, sm_scale, causal, my_idx * chunk_q, src * chunk_k
        )  # (B, H, Sq, Sk)
        m_new = jnp.maximum(m, scores.max(axis=-1))
        p = jnp.exp(scores - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l_new = alpha * l + p.sum(axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", p, v_exp.astype(jnp.float32)
        )
        # rotate AFTER using the chunk; the final rotation restores the
        # original K/V residency (and XLA overlaps it with compute)
        k_next = jax.lax.ppermute(k_cur, axis_name, perm)
        v_next = jax.lax.ppermute(v_cur, axis_name, perm)
        return acc_new, m_new, l_new, k_next, v_next

    init = (
        jnp.zeros((batch, heads, chunk_q, d), jnp.float32),
        jnp.full((batch, heads, chunk_q), _NEG_INF, jnp.float32),
        jnp.zeros((batch, heads, chunk_q), jnp.float32),
        k,
        v,
    )
    acc, _m, l, _k, _v = jax.lax.fori_loop(0, axis_size, step, init)
    out = acc / l[..., None]
    return out.transpose(0, 2, 1, 3).astype(q.dtype)  # (B, Sq, H, D)


def sequence_shard_spec(
    mesh, axis_name: str | None, batch: int, heads: int, head_divisor: int = 1
) -> P:
    """The (B, S, H, D) PartitionSpec every mapped attention path shares
    (ring, ulysses, and the local kernel with ``axis_name=None``):
    batch on its data-parallel axes when divisible (replicated-batch
    fallback covers the 1-example init trace), sequence on ``axis_name``,
    heads on ``tp`` when it divides ``heads`` (and the per-device head
    group stays divisible by ``head_divisor`` — ulysses' all_to_all
    constraint; ring passes 1)."""
    from elasticdl_tpu.parallel.mesh import data_parallel_axes

    dp_axes = data_parallel_axes(mesh)
    dp_size = (
        int(np.prod([mesh.shape[a] for a in dp_axes])) if dp_axes else 1
    )
    batch_axes = dp_axes if dp_axes and batch % dp_size == 0 else None
    tp = "tp" if "tp" in mesh.axis_names else None
    head_axis = None
    if tp and mesh.shape[tp] > 1 and heads % mesh.shape[tp] == 0:
        if (heads // mesh.shape[tp]) % head_divisor == 0:
            head_axis = tp
    return P(batch_axes, axis_name, head_axis, None)


def ring_attention(
    q,
    k,
    v,
    mesh,
    axis_name: str = "sp",
    causal: bool = False,
    sm_scale: float | None = None,
):
    """Sequence-parallel attention, (B, S, H, D) layout with S sharded
    over ``mesh[axis_name]``.

    Callable from inside jit (GSPMD) — the shard_map nests; batch stays
    sharded however the surrounding program shards it (specs below only
    constrain the sequence dim).
    """
    from elasticdl_tpu.ops.attention import validate_gqa_heads

    validate_gqa_heads(q, k, v)
    q_heads, kv_heads = q.shape[2], k.shape[2]
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    axis_size = mesh.shape[axis_name]
    if axis_size <= 1:
        from elasticdl_tpu.ops.attention import flash_attention

        return flash_attention(q, k, v, causal=causal, sm_scale=sm_scale)

    if q.shape[1] % axis_size:
        raise ValueError(
            f"ring attention needs seq ({q.shape[1]}) divisible by "
            f"{axis_name}={axis_size}"
        )
    # batch on dp when divisible; heads stay tp-sharded through the ring
    # (embarrassingly parallel over heads).  Under GQA the small kv
    # tensors rotate un-repeated (expansion is chunk-local in the body)
    # and head sharding is disabled to keep query groups aligned.
    spec = sequence_shard_spec(mesh, axis_name, q.shape[0], q_heads)
    if kv_heads != q_heads and spec[2] is not None:
        spec = P(spec[0], axis_name, None, None)
    body = functools.partial(
        _ring_attention_local,
        axis_name=axis_name,
        axis_size=axis_size,
        causal=causal,
        sm_scale=sm_scale,
    )
    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )(q, k, v)
