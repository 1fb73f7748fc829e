"""Ulysses (DeepSpeed-style) all-to-all sequence parallelism.

The alternative to ring attention for the ``sp`` axis: instead of
rotating K/V chunks, one ``all_to_all`` reshards activations from
sequence-sharded to HEAD-sharded, each device runs ordinary (flash)
attention over its head group with the FULL sequence, and a second
``all_to_all`` reshards back.  Two collectives total per attention call
(vs ``sp`` ppermute hops for ring) — cheaper when ``sp`` divides the
head count and the full sequence fits one device's memory for its head
group; ring remains the choice when it does not.  Select globally with
``set_attention_mesh(mesh, sp_impl="ulysses")`` (layers dispatch through
``ops.attention.attention``), or call :func:`ulysses_attention`
directly.
"""

from __future__ import annotations

import functools
import math

import jax


def _ulysses_local(
    q, k, v, *, axis_name, causal, sm_scale, interpret, group, sp
):
    """Per-shard body (under shard_map): inputs are (B, S/n, H, D);
    all_to_all to (B, S, H/n, D), flash attention (GQA-aware: kv may
    still carry fewer heads after the reshard), and back."""
    import jax.numpy as jnp

    def seq_to_heads(x):
        # concat_dimension=1 gathers the sequence; split_dimension=2
        # scatters heads; tiled=True keeps the dims in place
        return jax.lax.all_to_all(
            x, axis_name, split_axis=2, concat_axis=1, tiled=True
        )

    def heads_to_seq(x):
        return jax.lax.all_to_all(
            x, axis_name, split_axis=1, concat_axis=2, tiled=True
        )

    from elasticdl_tpu.ops.attention import flash_attention

    if group > 1 and k.shape[2] % sp != 0:
        # kv heads don't split over sp: expand BEFORE the reshard (the
        # divisible case moves the SMALL kv through the all_to_all and
        # lets flash's GQA indexing handle the reduced head count)
        k = jnp.repeat(k, group, axis=2)
        v = jnp.repeat(v, group, axis=2)
    qh, kh, vh = seq_to_heads(q), seq_to_heads(k), seq_to_heads(v)
    out = flash_attention(
        qh, kh, vh, causal=causal, sm_scale=sm_scale, interpret=interpret
    )
    return heads_to_seq(out)


def ulysses_attention(
    q,
    k,
    v,
    mesh,
    axis_name: str = "sp",
    causal: bool = False,
    sm_scale: float | None = None,
):
    """Sequence-parallel attention via head/sequence all-to-all,
    (B, S, H, D) layout with S sharded over ``mesh[axis_name]``.

    Requires ``heads % sp == 0`` and ``seq % sp == 0``.
    """
    from elasticdl_tpu.ops.attention import validate_gqa_heads

    group = validate_gqa_heads(q, k, v)
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    sp = mesh.shape[axis_name]
    if sp <= 1:
        from elasticdl_tpu.ops.attention import flash_attention

        return flash_attention(q, k, v, causal=causal, sm_scale=sm_scale)
    if q.shape[1] % sp:
        raise ValueError(
            f"ulysses needs seq ({q.shape[1]}) divisible by "
            f"{axis_name}={sp}"
        )

    from elasticdl_tpu.ops.on_mesh import kernel_interpret
    from elasticdl_tpu.ops.ring_attention import sequence_shard_spec

    # shared layout with ring (batch on dp; head sharding over tp is
    # disabled under GQA — query groups must stay aligned); head_divisor
    # = sp because the inner all_to_all splits the head dim sp ways
    spec = sequence_shard_spec(
        mesh, axis_name, q.shape[0], q.shape[2], head_divisor=sp
    )
    if group > 1 and spec[2] is not None:
        from jax.sharding import PartitionSpec as P

        spec = P(spec[0], axis_name, None, None)
    local_heads = q.shape[2] // (
        mesh.shape["tp"] if spec[2] == "tp" else 1
    )
    if local_heads % sp:
        raise ValueError(
            f"ulysses needs the per-device head group ({local_heads}) "
            f"divisible by {axis_name}={sp}; use ring attention otherwise"
        )
    interpret = kernel_interpret(mesh.devices.flat[0].platform)
    body = functools.partial(
        _ulysses_local,
        axis_name=axis_name,
        causal=causal,
        sm_scale=sm_scale,
        interpret=interpret,
        group=group,
        sp=sp,
    )
    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )(q, k, v)
