"""Pipeline-parallel transformer LM — the model-level consumer of
``ops.pipeline`` (GPipe schedule over the ``pp`` mesh axis).

The homogeneous middle of the network (``num_stages`` identical
transformer blocks) carries its parameters STACKED with a leading stage
dimension, sharded over ``pp`` (``sharding_rules``); the forward pass
streams microbatches through the stages with ``pipeline_apply`` (each
device computes one stage, activations hop neighbor-to-neighbor).  With
no ``pp`` axis (or no registered mesh) the same stacked parameters run
as a sequential ``lax.scan`` — one parameter layout, both execution
schedules.

Stage math is pure jnp (hand-rolled pre-LN block) rather than nested
flax modules: ``pipeline_apply``'s stage_fn runs under ``shard_map``
where a plain function over a parameter pytree is the natural shape.

Spec contract matches the model zoo (same dataset as
``long_seq_transformer``), so the standard CLI trains it:
``--model_def pipelined_transformer.pipelined_transformer.custom_model
--mesh_shape dp=2,pp=4``.
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp

from elasticdl_tpu.layers.attention import sinusoidal_positions
from elasticdl_tpu.models.long_seq_transformer import (  # noqa: F401
    VOCAB,
    dataset_fn,
    eval_metrics_fn,
    loss,
    optimizer,
)
from elasticdl_tpu.ops.attention import mha_reference
from elasticdl_tpu.ops.on_mesh import get_attention_mesh


def _layernorm(x, scale, bias, eps=1e-6):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * scale + bias


def _block(p, x):
    """One pre-LN transformer block as a pure function of (params, x);
    every shape comes from the param pytree."""
    h = _layernorm(x, p["ln1_scale"], p["ln1_bias"])
    q = jnp.einsum("bse,ehd->bshd", h, p["wq"])
    k = jnp.einsum("bse,ehd->bshd", h, p["wk"])
    v = jnp.einsum("bse,ehd->bshd", h, p["wv"])
    a = mha_reference(q, k, v, causal=True)
    x = x + jnp.einsum("bshd,hde->bse", a, p["wo"])
    h = _layernorm(x, p["ln2_scale"], p["ln2_bias"])
    h = jax.nn.gelu(h @ p["w_up"] + p["b_up"])
    return x + h @ p["w_down"] + p["b_down"]


# leading dim is the stage "batch": exclude it from fan computations.
# The 4-D attention weights need explicit fan axes so heads don't
# inflate fan_in (wq/wk/wv: embed -> (heads, head_dim); wo: the mirror).
_stacked_init = nn.initializers.variance_scaling(
    1.0, "fan_in", "truncated_normal", batch_axis=(0,)
)
_qkv_init = nn.initializers.variance_scaling(
    1.0,
    "fan_in",
    "truncated_normal",
    in_axis=-3,
    out_axis=(-2, -1),
    batch_axis=(0,),
)
_wo_init = nn.initializers.variance_scaling(
    1.0,
    "fan_in",
    "truncated_normal",
    in_axis=(-3, -2),
    out_axis=-1,
    batch_axis=(0,),
)


class PipelinedTransformerLM(nn.Module):
    vocab_size: int = VOCAB
    embed_dim: int = 128
    num_heads: int = 4
    num_stages: int = 4
    mlp_ratio: int = 4
    num_microbatches: int = 4

    @nn.compact
    def __call__(self, features, training: bool = False):
        tokens = (
            features["tokens"] if isinstance(features, dict) else features
        )
        tokens = jnp.asarray(tokens).astype(jnp.int32)
        x = nn.Embed(self.vocab_size, self.embed_dim, name="tok_embed")(
            tokens
        )
        x = x + sinusoidal_positions(tokens.shape[1], self.embed_dim)[
            None, :, :
        ].astype(x.dtype)

        embed, heads = self.embed_dim, self.num_heads
        head_dim = embed // heads
        hidden = embed * self.mlp_ratio
        s = self.num_stages

        def _p(name, shape, init=_stacked_init):
            return self.param(f"stages_{name}", init, (s, *shape))

        ones = nn.initializers.ones
        zeros = nn.initializers.zeros
        stages = {
            "ln1_scale": _p("ln1_scale", (embed,), ones),
            "ln1_bias": _p("ln1_bias", (embed,), zeros),
            "wq": _p("wq", (embed, heads, head_dim), _qkv_init),
            "wk": _p("wk", (embed, heads, head_dim), _qkv_init),
            "wv": _p("wv", (embed, heads, head_dim), _qkv_init),
            "wo": _p("wo", (heads, head_dim, embed), _wo_init),
            "ln2_scale": _p("ln2_scale", (embed,), ones),
            "ln2_bias": _p("ln2_bias", (embed,), zeros),
            "w_up": _p("w_up", (embed, hidden)),
            "b_up": _p("b_up", (hidden,), zeros),
            "w_down": _p("w_down", (hidden, embed)),
            "b_down": _p("b_down", (embed,), zeros),
        }
        mesh, _axis, _impl = get_attention_mesh()
        if (
            mesh is not None
            and "pp" in mesh.axis_names
            and mesh.shape["pp"] > 1
        ):
            from elasticdl_tpu.ops.pipeline import pipeline_apply

            if mesh.shape["pp"] != s:
                raise ValueError(
                    f"mesh pp={mesh.shape['pp']} != num_stages={s}"
                )
            # largest divisor of the batch (the 1-example init trace must
            # compile the same program structure)
            mb = min(self.num_microbatches, x.shape[0])
            while x.shape[0] % mb:
                mb -= 1
            x = pipeline_apply(
                _block, stages, x, mesh, num_microbatches=mb
            )
        else:
            # same stacked params, sequential schedule
            def body(h, p):
                return _block(p, h), None

            x, _ = jax.lax.scan(body, x, stages)

        x = _layernorm(
            x,
            self.param("final_ln_scale", ones, (embed,)),
            self.param("final_ln_bias", zeros, (embed,)),
        )
        return nn.Dense(self.vocab_size, name="lm_head")(x)


def custom_model(**kwargs):
    return PipelinedTransformerLM(**kwargs)


def sharding_rules(mesh):
    """Stage-stacked parameters shard their leading dim over pp."""
    from elasticdl_tpu.ops.pipeline import pipeline_sharding_rules

    if mesh.shape.get("pp", 1) <= 1:
        return ()
    return tuple(pipeline_sharding_rules())
