"""Pipeline parallelism over the ``pp`` mesh axis (GPipe schedule).

The last of the five parallelism families (dp/fsdp, tp, sp, ep, pp): a
stack of identical stages is laid out one-stage-per-``pp``-shard, the
batch is split into microbatches, and activations flow stage-to-stage
with ``lax.ppermute`` neighbor hops — at steady state every stage
computes a different microbatch, hiding all but the S-1 bubble ticks.
Differentiating through the schedule gives the backward pipeline for
free (the transpose of ``ppermute`` is the reverse permute), so the same
op trains.

No reference counterpart (the reference is data-parallel only, SURVEY
§2.8); this exists because the TPU build's mesh must not preclude any
standard parallel dimension.

Layout contract: ``stacked_params`` is a pytree whose leaves all have a
leading ``num_stages`` dimension, sharded over ``pp``
(:func:`pipeline_sharding_rules`); ``stage_fn(params_slice, x) -> y``
maps one stage's parameter slice over activations of a fixed shape
(every stage must preserve the activation shape — the homogeneous-stack
restriction of GPipe-style scan pipelines).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def pipeline_sharding_rules(pattern: str = r"(^|/)stages[_/]"):
    """The canonical 'stage-stacked params shard dim 0 over pp' rule:
    matches a path SEGMENT named/prefixed ``stages`` (nested ``stages/x``
    or flat ``stages_x``), anchored so e.g. ``extra_stages_bias`` does
    not shard accidentally."""
    from elasticdl_tpu.parallel.sharding import Rule

    return [Rule(pattern, P("pp"))]


def _pipeline_local(params, x_mb, *, stage_fn, axis_name, num_stages):
    """Per-stage body (under shard_map).

    params: this stage's parameter slice (leading dim 1, squeezed).
    x_mb: (num_microbatches, microbatch, ...) — replicated over pp; only
    stage 0 reads it.

    Schedule: T = M + S - 1 ticks.  At tick t, stage 0 feeds microbatch
    t (while t < M); stage s computes what it received from s-1 last
    tick; stage S-1's results from ticks >= S-1 are collected.  The
    rotation also carries S-1 bubble slots — their results are masked
    out, never observed.
    """
    stage = jax.lax.axis_index(axis_name)
    num_mb = x_mb.shape[0]
    params = jax.tree_util.tree_map(lambda p: p[0], params)
    perm = [(i, (i + 1) % num_stages) for i in range(num_stages)]

    def tick(carry, t):
        prev_out, outputs = carry
        # what arrives from the previous stage this tick (stage 0's
        # recv is garbage — it is replaced by the fed microbatch)
        recv = jax.lax.ppermute(prev_out, axis_name, perm)
        feed = x_mb[jnp.minimum(t, num_mb - 1)]
        x_in = jnp.where(stage == 0, feed, recv)
        out = stage_fn(params, x_in)
        # collect the LAST stage's finished microbatch t - (S - 1)
        mb_index = t - (num_stages - 1)
        outputs = jax.lax.cond(
            jnp.logical_and(stage == num_stages - 1, mb_index >= 0),
            lambda o: jax.lax.dynamic_update_index_in_dim(
                o, out, jnp.maximum(mb_index, 0), 0
            ),
            lambda o: o,
            outputs,
        )
        return (out, outputs), None

    init = (
        jnp.zeros_like(x_mb[0]),
        jnp.zeros_like(x_mb),
    )
    (_, outputs), _ = jax.lax.scan(
        tick, init, jnp.arange(num_mb + num_stages - 1)
    )
    # only the last stage holds real outputs; replicate them over pp
    outputs = jnp.where(stage == num_stages - 1, outputs, 0.0)
    return jax.lax.psum(outputs, axis_name)


def pipeline_apply(
    stage_fn,
    stacked_params,
    x,
    mesh,
    num_microbatches: int,
    axis_name: str = "pp",
):
    """Run ``x`` through ``num_stages`` pipelined stages.

    x: (batch, ...) with batch divisible by ``num_microbatches``.
    Returns (batch, ...) outputs (replicated over ``pp``).
    """
    num_stages = mesh.shape[axis_name]
    for path, leaf in jax.tree_util.tree_flatten_with_path(stacked_params)[0]:
        if jnp.shape(leaf)[0] != max(num_stages, 1) and num_stages > 1:
            raise ValueError(
                f"stacked param {jax.tree_util.keystr(path)} has leading "
                f"dim {jnp.shape(leaf)[0]} but the {axis_name} axis has "
                f"{num_stages} stages — a divisible mismatch would "
                "silently drop stages"
            )
    if num_stages <= 1:
        # degenerate: sequential scan over the stage stack
        def body(h, p):
            return stage_fn(p, h), None

        out, _ = jax.lax.scan(body, x, stacked_params)
        return out

    batch = x.shape[0]
    if batch % num_microbatches:
        raise ValueError(
            f"batch {batch} not divisible by {num_microbatches} microbatches"
        )
    mb = batch // num_microbatches
    x_mb = x.reshape(num_microbatches, mb, *x.shape[1:])

    from elasticdl_tpu.parallel.mesh import batch_divisor, data_parallel_axes

    dp_axes = data_parallel_axes(mesh)
    batch_axes = (
        dp_axes if dp_axes and mb % batch_divisor(mesh) == 0 else None
    )
    x_spec = P(None, batch_axes, *([None] * (x.ndim - 1)))
    param_spec = jax.tree_util.tree_map(
        lambda _: P(axis_name), stacked_params
    )

    body = functools.partial(
        _pipeline_local,
        stage_fn=stage_fn,
        axis_name=axis_name,
        num_stages=num_stages,
    )
    out = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(param_spec, x_spec),
        out_specs=x_spec,
        check_vma=False,
    )(stacked_params, x_mb)
    return out.reshape(batch, *x.shape[1:])
