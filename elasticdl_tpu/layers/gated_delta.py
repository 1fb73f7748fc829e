"""The Gated DeltaNet mixer (Yang et al., arXiv:2412.06464) as the
``qwen3_next`` stack runs it (HF ``modeling_qwen3_next.py``,
``Qwen3NextGatedDeltaNet``; docs/designs/gated_delta_rule.md)::

    [q | k | v | z] = W_qkvz u              Hk x dk | Hk x dk | Hv x dv | Hv x dv
    [b | a] = W_ba u                        Hv | Hv
    [q | k | v] = silu(causal depthwise conv_4([q | k | v]))      no bias
    q, k = l2norm(q), l2norm(k) a head;  q = q / sqrt(dk)
    beta = sigmoid(b);  g = -exp(A_log) softplus(a + dt_bias)    float32
    S_t = exp(g_t) S_{t-1} + beta_t k_t (v_t - (exp(g_t) S_{t-1})^T k_t)^T
    o_t = S_t^T q_t                         value head h reads key head h // (Hv / Hk)
    y = RMSNorm_dv(o) * w * silu(z)         the norm BEFORE the gate, one scale
                                            of dv shared by the heads
    out = W_out y

The recurrence is ``ops/gated_delta.py``'s chunked scan; the convolution is
``ops/mamba_passes.py``'s pass (through ``layers/mamba.py::conv_silu``, a
call a part of the projection: nothing is split or joined), and the projection
is ``layers/mamba.py::SplitDense``, whose parts come out as arrays of their
own.  ``A_log`` and ``dt_bias`` take Mamba-2's initialisers, as the family's
code does.  The layer sows the step's mean ``exp(g)`` and mean ``beta`` into
the ``delta_state`` collection (``telemetry/router_load.py::
read_delta_state``): a state that has decayed to nothing, or a ``beta`` near
0, would leave a comparison of outputs blind to the scan.

No reference counterpart; listed in DEVIATIONS.md additions.
"""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from elasticdl_tpu.layers.mamba import (
    SplitDense,
    _a_log_init,
    _dt_bias_init,
    conv_silu,
)
from elasticdl_tpu.ops import gated_delta as gated_delta_ops
from elasticdl_tpu.telemetry.router_load import DELTA_STATE


def l2_normalised(x, eps: float = 1e-6):
    """``x / sqrt(sum x^2 + eps)`` over the last axis, float32 inside."""
    wide = x.astype(jnp.float32)
    return (
        wide * jax.lax.rsqrt(jnp.sum(jnp.square(wide), -1, keepdims=True) + eps)
    ).astype(x.dtype)


def normed_then_gated(o, z, scale, eps: float):
    """``RMSNorm(o) * scale * silu(z)`` over the last axis (a head's width),
    float32 inside."""
    wide = o.astype(jnp.float32)
    normed = wide * jax.lax.rsqrt(
        jnp.mean(jnp.square(wide), -1, keepdims=True) + eps
    )
    return (
        normed * scale.astype(jnp.float32) * nn.silu(z.astype(jnp.float32))
    ).astype(o.dtype)


class GatedDeltaNet(nn.Module):
    num_key_heads: int
    num_value_heads: int
    key_dim: int = 128
    value_dim: int = 128
    conv_kernel: int = 4
    chunk: int = 128  # steps a chunk: on the chip 128 beat 64 (PERF.md, PR 65)
    norm_eps: float = 1e-6
    dtype: Any = None  # compute dtype; params stay f32

    @nn.compact
    def __call__(self, u):
        """u: (batch, T, embed) -> (batch, T, embed)."""
        keys, values = self.num_key_heads, self.num_value_heads
        if values % keys:
            raise ValueError(f"{values} value heads over {keys} key heads")
        widths = (
            keys * self.key_dim, keys * self.key_dim,
            values * self.value_dim, values * self.value_dim,
        )
        *qkv, z = SplitDense(widths, dtype=self.dtype, name="in_proj_qkvz")(u)
        b, a = SplitDense(
            (values, values), dtype=self.dtype, name="in_proj_ba"
        )(u)
        taps = self.param(
            "conv_kernel", nn.initializers.lecun_normal(),
            (self.conv_kernel, sum(widths[:3])),
        )
        # the regions between the modules and the kernel, by
        # telemetry/op_scopes.py's names
        with jax.named_scope("delta_conv"):
            first, convolved = 0, []
            for part in qkv:
                width = part.shape[-1]
                convolved.append(
                    conv_silu(
                        part, taps[:, first:first + width],
                        jnp.zeros((width,), jnp.float32),
                    )
                )
                first += width
        q, k, v = (
            x.reshape(*x.shape[:2], heads, -1)
            for x, heads in zip(convolved, (keys, keys, values))
        )
        dt_bias = self.param(
            "dt_bias", _dt_bias_init(0.001, 0.1, 1e-4), (values,)
        )
        a_log = self.param("A_log", _a_log_init, (values,))
        with jax.named_scope("delta_rule"):
            q = l2_normalised(q) * jnp.asarray(self.key_dim**-0.5, q.dtype)
            k = l2_normalised(k)
            beta = jax.nn.sigmoid(b.astype(jnp.float32))
            g = -jnp.exp(a_log.astype(jnp.float32)) * nn.softplus(
                a.astype(jnp.float32) + dt_bias
            )
            o = gated_delta_ops.gated_delta_scan(
                q, k, v, g, beta, chunk=self.chunk
            )
            for name, value in (
                ("decay_mean", jnp.mean(jnp.exp(g))),
                ("beta_mean", jnp.mean(beta)),
            ):
                self.sow(
                    DELTA_STATE, name, jax.lax.stop_gradient(value),
                    init_fn=lambda: jnp.zeros((), jnp.float32),
                    reduce_fn=lambda _prev, new: new,
                )
        with jax.named_scope("norm_gate"):
            y = normed_then_gated(
                o, z.reshape(o.shape),
                self.param("norm_scale", nn.initializers.ones, (self.value_dim,)),
                self.norm_eps,
            )
        return nn.Dense(
            u.shape[-1], use_bias=False, dtype=self.dtype, name="out_proj"
        )(y.reshape(*y.shape[:2], -1))
