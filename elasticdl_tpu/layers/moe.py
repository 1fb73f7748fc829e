"""Routed expert MLP: top-k softmax routing that drops no token, SwiGLU
experts, a grouped matmul over the (token, slot) pairs sorted by expert.

The equations are OLMoE's (Muennighoff et al., arXiv:2409.02060; HF
``OlmoeSparseMoeBlock``): ``p = softmax(x W_r)`` in float32 over all
experts, the ``k`` largest as the token's experts, their probabilities as
combine weights (divided by their sum only with ``norm_topk_prob``),
``y = sum_k w_k * down_k(silu(gate_k(x)) * up_k(x))``.  Two auxiliary
losses join the training loss through the ``losses`` collection
(``trainer/step.py::forward_loss``): the load-balance loss
``E * sum_e f_e P_e`` and the router z-loss ``mean(logsumexp(logits)^2)``.

Dispatch has one path and static shapes at any imbalance
(``ops/grouped_matmul.py``): a stable sort of the pairs by expert, each
expert's rows padded to whole tiles, a gather into that order, the grouped
matmuls, and a gather back with the weights — the permutation's transpose
is a gather too (each pair has one row), so no scatter of activations runs
in either direction.  On a mesh the experts' leading dimension shards over
``ep`` (``moe_sharding_rules``): under ``shard_map`` each rank lays out only
the pairs of its own experts, the others weigh zero, and a ``psum`` over
``ep`` completes the combine.  The all-to-all that would move tokens
instead of replicating them over ``ep`` is ROADMAP B5's follow-up.

No reference counterpart; listed in DEVIATIONS.md additions.
"""

from __future__ import annotations

import functools
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from elasticdl_tpu.ops import grouped_matmul as gmm_ops
from elasticdl_tpu.ops.attention import get_attention_mesh, kernel_interpret
from elasticdl_tpu.telemetry.router_load import ROUTER_STATS

# fan_in must count only the per-expert receptive field: axis 0 is the
# expert "batch" dimension, not part of any one expert's fan
_expert_init = nn.initializers.variance_scaling(
    1.0, "fan_in", "truncated_normal", batch_axis=(0,)
)



@jax.custom_vjp
def _dispatch(x, row_token, pair_row, pair_grouped):
    """Rows of the grouped buffer from the tokens: ``x[row_token]``."""
    return x[row_token]


def _dispatch_fwd(x, row_token, pair_row, pair_grouped):
    return x[row_token], (pair_row, pair_grouped)


def _dispatch_bwd(residuals, d_rows):
    # each pair has one row: the transpose of the gather is a gather by the
    # inverse index, summed over a token's slots
    pair_row, pair_grouped = residuals
    d_pairs = jnp.where(pair_grouped[..., None], d_rows[pair_row], 0)
    d_x = d_pairs.sum(axis=1, dtype=jnp.float32).astype(d_rows.dtype)
    return d_x, None, None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _combine(rows, weights, pair_row, row_pair):
    """``y[n] = sum_k weights[n, k] * rows[pair_row[n, k]]``."""
    return jnp.einsum(
        "nkd,nk->nd", rows[pair_row], weights,
        preferred_element_type=jnp.float32,
    ).astype(rows.dtype)


def _combine_fwd(rows, weights, pair_row, row_pair):
    return _combine(rows, weights, pair_row, row_pair), (
        rows, weights, pair_row, row_pair,
    )


def _combine_bwd(residuals, d_y):
    rows, weights, pair_row, row_pair = residuals
    pairs = weights.size
    slots = weights.shape[1]
    held = row_pair < pairs  # padding rows hold no pair
    pair = jnp.minimum(row_pair, pairs - 1)
    row_weight = jnp.where(held, weights.reshape(-1)[pair], 0.0)
    d_rows = (
        d_y[pair // slots].astype(jnp.float32) * row_weight[:, None]
    ).astype(d_y.dtype)
    d_weights = jnp.einsum(
        "nkd,nd->nk", rows[pair_row], d_y,
        preferred_element_type=jnp.float32,
    )
    return d_rows, d_weights.astype(weights.dtype), None, None


_combine.defvjp(_combine_fwd, _combine_bwd)


def routed_experts(
    x, top_experts, weights, w_gate, w_up, w_down, *, first_expert=0,
    tile_rows: int = gmm_ops.TILE_ROWS, interpret: bool | None = None,
):
    """The experts' part on one device: ``x`` (tokens, d), ``top_experts``
    and ``weights`` (tokens, k), the three weight stacks of the
    ``w_gate.shape[0]`` experts that start at ``first_expert``.  A pair
    whose expert is not among them adds nothing here.  Returns the output
    and the number of pairs that were given a row."""
    tokens, slots = top_experts.shape
    experts = w_gate.shape[0]
    local = top_experts - first_expert
    grouped = (local >= 0) & (local < experts)
    layout = gmm_ops.group_layout(
        jnp.where(grouped, local, experts).reshape(-1).astype(jnp.int32),
        experts, tile_rows,
    )
    pair_row = layout.pair_row.reshape(tokens, slots)
    row_token = jnp.minimum(layout.row_pair, tokens * slots - 1) // slots
    matmul = functools.partial(
        gmm_ops.grouped_matmul, tile_group=layout.tile_group,
        tile_rows=tile_rows, interpret=interpret,
    )
    rows = _dispatch(x, row_token, pair_row, grouped)
    hidden = nn.silu(matmul(rows, w_gate)) * matmul(rows, w_up)
    out = matmul(hidden, w_down)
    y = _combine(
        out, jnp.where(grouped, weights, 0.0), pair_row, layout.row_pair
    )
    return y, jnp.sum(layout.row_pair < tokens * slots, dtype=jnp.int32)


def _experts_on_mesh(x, top_experts, weights, w_gate, w_up, w_down):
    """``routed_experts`` under the registered mesh: tokens stay on their
    batch (and sequence) axes, experts shard over ``ep``, and a compiled
    Pallas kernel, which GSPMD cannot partition, runs per device."""
    from jax.sharding import PartitionSpec as P

    from elasticdl_tpu.ops.ring_attention import sequence_shard_spec

    mesh, sp_axis, _ = get_attention_mesh()
    batch, seq, embed = x.shape
    slots = top_experts.shape[-1]

    def local(x, top_experts, weights, *stacks, **kw):
        y, held = routed_experts(
            x.reshape(-1, embed), top_experts.reshape(-1, slots),
            weights.reshape(-1, slots), *stacks, **kw,
        )
        return y.reshape(x.shape), held

    if mesh is None:
        return local(x, top_experts, weights, w_gate, w_up, w_down)
    interpret = kernel_interpret(mesh.devices.flat[0].platform)
    if mesh.devices.size == 1 or jax.sharding.get_abstract_mesh().manual_axes:
        return local(
            x, top_experts, weights, w_gate, w_up, w_down, interpret=interpret
        )
    sharded_seq = (
        sp_axis in mesh.axis_names
        and mesh.shape[sp_axis] > 1
        and seq % mesh.shape[sp_axis] == 0
    )
    tokens = sequence_shard_spec(
        mesh, sp_axis if sharded_seq else None, batch, 1
    )
    tokens = P(tokens[0], tokens[1], None)
    ep = "ep" if mesh.shape.get("ep", 1) > 1 else None
    if ep and w_gate.shape[0] % mesh.shape[ep]:
        raise ValueError(
            f"{w_gate.shape[0]} experts do not divide over ep={mesh.shape[ep]}"
        )
    experts = P(ep, None, None)

    # the axes a pair's row exists on once: its tokens' and its expert's
    # (a spec entry is None, one axis name, or a tuple of them)
    counted = tuple(
        axis
        for entry in (tokens[0], tokens[1], ep) if entry
        for axis in ((entry,) if isinstance(entry, str) else entry)
    )

    def per_device(x, top_experts, weights, w_gate, w_up, w_down):
        first = jax.lax.axis_index(ep) * w_gate.shape[0] if ep else 0
        y, held = local(
            x, top_experts, weights, w_gate, w_up, w_down,
            first_expert=first, interpret=interpret,
        )
        return (
            jax.lax.psum(y, ep) if ep else y,
            jax.lax.psum(held, counted) if counted else held,
        )

    return jax.shard_map(
        per_device,
        mesh=mesh,
        in_specs=(tokens, tokens, tokens, experts, experts, experts),
        out_specs=(tokens, P()),
        check_vma=False,
    )(x, top_experts, weights, w_gate, w_up, w_down)


class MoEMLP(nn.Module):
    """Drop-in MLP replacement: ``experts_per_token`` of ``num_experts``
    SwiGLU experts of width ``expert_width`` a token, none dropped."""

    num_experts: int
    experts_per_token: int = 2
    expert_width: int = 0  # 0: four times the embedding
    norm_topk_prob: bool = False
    aux_loss_weight: float = 0.01
    z_loss_weight: float = 0.001
    dtype: Any = None

    @nn.compact
    def __call__(self, x, training: bool = False):
        embed = x.shape[-1]
        width = self.expert_width or 4 * embed
        if not 0 < self.experts_per_token <= self.num_experts:
            raise ValueError(
                f"experts_per_token={self.experts_per_token} of "
                f"{self.num_experts} experts"
            )
        # the router in float32 at full precision: a near-tie between two
        # experts is decided as a float32 reference decides it
        logits = nn.Dense(
            self.num_experts, use_bias=False, name="router",
            precision=jax.lax.Precision.HIGHEST,
        )(x.astype(jnp.float32))
        probs = jax.nn.softmax(logits, axis=-1)
        weights, top_experts = jax.lax.top_k(probs, self.experts_per_token)
        if self.norm_topk_prob:
            weights = weights / jnp.sum(weights, axis=-1, keepdims=True)

        # pairs per expert over tokens (sum_e f_e = k, as HF counts it)
        chosen = jax.nn.one_hot(top_experts, self.num_experts, dtype=jnp.float32)
        counts = chosen.sum(axis=tuple(range(chosen.ndim - 1)))
        tokens = logits.size // self.num_experts
        router_prob = probs.reshape(-1, self.num_experts).mean(axis=0)
        balance = self.num_experts * jnp.sum(counts / tokens * router_prob)
        z = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))
        for name, value in (
            ("moe_load_balance", self.aux_loss_weight * balance),
            ("moe_router_z", self.z_loss_weight * z),
        ):
            self.sow(
                "losses", name, value,
                init_fn=lambda: jnp.zeros((), jnp.float32),
                reduce_fn=lambda _prev, new: new,
            )

        shape = (self.num_experts, embed, width)
        w_gate = self.param("w_gate", _expert_init, shape)
        w_up = self.param("w_up", _expert_init, shape)
        w_down = self.param(
            "w_down", _expert_init, (self.num_experts, width, embed)
        )
        if self.dtype is not None:
            x = x.astype(self.dtype)
        y, rows_held = _experts_on_mesh(
            x, top_experts, weights, w_gate, w_up, w_down
        )
        # what telemetry/router_load.py reads on demand; the dispatch's own
        # count of rows beside the router's says that no pair was dropped
        for name, value in (
            ("expert_counts", counts.astype(jnp.int32)),
            ("rows_held", rows_held),
        ):
            self.sow(
                ROUTER_STATS, name, jax.lax.stop_gradient(value),
                init_fn=lambda value=value: jnp.zeros_like(value),
                reduce_fn=lambda _prev, new: new,
            )
        return y


def moe_sharding_rules():
    """Expert-parallel rules: the leading expert dimension of every expert
    weight stack shards over ``ep``; composes with default_tp_rules
    (distinct path patterns)."""
    from jax.sharding import PartitionSpec as P

    from elasticdl_tpu.parallel.sharding import Rule

    return [
        Rule(r"moe/(w_gate|w_up|w_down)$", P("ep", None, None)),
    ]
