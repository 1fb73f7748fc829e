"""Routed expert MLP: top-k routing that drops no token, a grouped matmul
over the (token, slot) pairs sorted by expert.

The equations are OLMoE's (Muennighoff et al., arXiv:2409.02060; HF
``OlmoeSparseMoeBlock``): ``p = softmax(x W_r)`` in float32 over all
experts, the ``k`` largest as the token's experts, their probabilities as
combine weights (divided by their sum only with ``norm_topk_prob``),
``y = sum_k w_k * down_k(silu(gate_k(x)) * up_k(x))``.  Two auxiliary
losses join the training loss through the ``losses`` collection
(``trainer/step.py::forward_loss``): the load-balance loss
``E * sum_e f_e P_e`` and the router z-loss ``mean(logsumexp(logits)^2)``.

The fields also spell the DeepSeek-V3 / ``nemotron_h`` router (HF
``NemotronHTopkRouter``): ``scoring="sigmoid"`` scores each expert alone;
with ``selection_bias`` the ``k`` experts are the largest of ``score +
bias`` while their weights are the scores without it, and the bias, a buffer
outside the gradient (collection ``router_stats``), moves by
``selection_bias_rate`` a step towards the experts that got fewer pairs than
the mean (Wang et al., arXiv:2408.15664); ``routed_scaling`` multiplies the
weights; ``expert_kind="relu2"`` makes an expert ``down(relu(up(x))^2)``,
two stacks through the same grouped matmul; ``shared_width`` adds one such
expert that every token passes.

``experts_held`` of the ``num_experts`` the router scores, from
``first_expert`` on, are the ones this deployment holds: one chip's share of
a layer whose experts lie on several.  Their part of the result is computed
through the path ``ep`` uses (a pair of another expert gets no row); what the
absent experts would add is left out, and no code stands in for their chips.

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
    x, top_experts, weights, *stacks, first_expert=0,
    tile_rows: int = gmm_ops.TILE_ROWS, interpret: bool | None = None,
):
    """The experts' part on one device: ``x`` (tokens, d), ``top_experts``
    and ``weights`` (tokens, k), the weight ``stacks`` of the
    ``stacks[0].shape[0]`` experts that start at ``first_expert`` — three
    (gate, up, down) for SwiGLU experts, two (up, down) for relu^2 ones.  A
    pair whose expert is not among them adds nothing here.  Returns the
    output and the number of pairs that were given a row."""
    tokens, slots = top_experts.shape
    experts = stacks[0].shape[0]
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
    if len(stacks) == 3:
        w_gate, w_up, w_down = stacks
        hidden = nn.silu(matmul(rows, w_gate)) * matmul(rows, w_up)
    else:
        w_up, w_down = stacks
        hidden = jnp.square(nn.relu(matmul(rows, w_up)))
    out = matmul(hidden, w_down)
    y = _combine(
        out, jnp.where(grouped, weights, 0.0), pair_row, layout.row_pair
    )
    return y, jnp.sum(layout.row_pair < tokens * slots, dtype=jnp.int32)


def _experts_on_mesh(x, top_experts, weights, stacks, first_expert=0):
    """``routed_experts`` under the registered mesh: tokens stay on their
    batch (and sequence) axes, the held experts (``stacks``, from
    ``first_expert`` on) shard over ``ep``, and a compiled Pallas kernel,
    which GSPMD cannot partition, runs per device."""
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
        return local(
            x, top_experts, weights, *stacks, first_expert=first_expert
        )
    interpret = kernel_interpret(mesh.devices.flat[0].platform)
    if mesh.devices.size == 1 or jax.sharding.get_abstract_mesh().manual_axes:
        return local(
            x, top_experts, weights, *stacks, first_expert=first_expert,
            interpret=interpret,
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
    if ep and stacks[0].shape[0] % mesh.shape[ep]:
        raise ValueError(
            f"{stacks[0].shape[0]} experts do not divide over "
            f"ep={mesh.shape[ep]}"
        )
    experts = P(ep, None, None)

    # the axes a pair's row exists on once: its tokens' and its expert's
    # (a spec entry is None, one axis name, or a tuple of them)
    counted = tuple(
        axis
        for entry in (tokens[0], tokens[1], ep) if entry
        for axis in ((entry,) if isinstance(entry, str) else entry)
    )

    def per_device(x, top_experts, weights, *stacks):
        first = first_expert
        if ep:
            first += jax.lax.axis_index(ep) * stacks[0].shape[0]
        y, held = local(
            x, top_experts, weights, *stacks,
            first_expert=first, interpret=interpret,
        )
        return (
            jax.lax.psum(y, ep) if ep else y,
            jax.lax.psum(held, counted) if counted else held,
        )

    return jax.shard_map(
        per_device,
        mesh=mesh,
        in_specs=(tokens, tokens, tokens) + (experts,) * len(stacks),
        out_specs=(tokens, P()),
        check_vma=False,
    )(x, top_experts, weights, *stacks)


SCORINGS = ("softmax", "sigmoid")
EXPERT_KINDS = ("swiglu", "relu2")


class MoEMLP(nn.Module):
    """Drop-in MLP replacement: ``experts_per_token`` of ``num_experts``
    experts of width ``expert_width`` a token, none dropped."""

    num_experts: int
    experts_per_token: int = 2
    expert_width: int = 0  # 0: four times the embedding
    norm_topk_prob: bool = False
    aux_loss_weight: float = 0.01
    z_loss_weight: float = 0.001
    dtype: Any = None
    scoring: str = "softmax"  # | "sigmoid"
    selection_bias: bool = False
    selection_bias_rate: float = 0.001
    routed_scaling: float = 1.0
    expert_kind: str = "swiglu"  # | "relu2": down(relu(up(x))^2)
    shared_width: int = 0  # > 0: one expert of this width on every token
    experts_held: int = 0  # 0: all of them
    first_expert: int = 0

    @nn.compact
    def __call__(self, x, training: bool = False):
        embed = x.shape[-1]
        width = self.expert_width or 4 * embed
        held = self.experts_held or self.num_experts
        if not 0 < self.experts_per_token <= self.num_experts:
            raise ValueError(
                f"experts_per_token={self.experts_per_token} of "
                f"{self.num_experts} experts"
            )
        if self.scoring not in SCORINGS or self.expert_kind not in EXPERT_KINDS:
            raise ValueError(
                f"scoring {self.scoring!r} of {SCORINGS}, expert_kind "
                f"{self.expert_kind!r} of {EXPERT_KINDS}"
            )
        if not 0 <= self.first_expert <= self.num_experts - held:
            raise ValueError(
                f"experts {self.first_expert}..{self.first_expert + held} "
                f"of {self.num_experts}"
            )
        # the router in float32 at full precision: a near-tie between two
        # experts is decided as a float32 reference decides it
        logits = nn.Dense(
            self.num_experts, use_bias=False, name="router",
            precision=jax.lax.Precision.HIGHEST,
        )(x.astype(jnp.float32))
        if self.scoring == "sigmoid":
            scores = jax.nn.sigmoid(logits)
            probs = scores / jnp.sum(scores, axis=-1, keepdims=True)
        else:
            scores = probs = jax.nn.softmax(logits, axis=-1)
        if self.selection_bias:
            bias = self.variable(
                ROUTER_STATS, "selection_bias",
                lambda: jnp.zeros((self.num_experts,), jnp.float32),
            )
            _, top_experts = jax.lax.top_k(
                scores + bias.value, self.experts_per_token
            )
            weights = jnp.take_along_axis(scores, top_experts, axis=-1)
        else:
            weights, top_experts = jax.lax.top_k(scores, self.experts_per_token)
        if self.norm_topk_prob:
            weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
        if self.routed_scaling != 1.0:
            weights = weights * self.routed_scaling

        # pairs per expert over tokens (sum_e f_e = k, as HF counts it)
        chosen = jax.nn.one_hot(top_experts, self.num_experts, dtype=jnp.float32)
        counts = chosen.sum(axis=tuple(range(chosen.ndim - 1)))
        tokens = logits.size // self.num_experts
        router_prob = probs.reshape(-1, self.num_experts).mean(axis=0)
        balance = self.num_experts * jnp.sum(counts / tokens * router_prob)
        z = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))
        for name, weight, value in (
            ("moe_load_balance", self.aux_loss_weight, balance),
            ("moe_router_z", self.z_loss_weight, z),
        ):
            if weight:
                self.sow(
                    "losses", name, weight * value,
                    init_fn=lambda: jnp.zeros((), jnp.float32),
                    reduce_fn=lambda _prev, new: new,
                )
        if (
            self.selection_bias and training and not self.is_initializing()
            and self.is_mutable_collection(ROUTER_STATS)
        ):
            # outside the gradient: towards the experts under the mean load
            bias.value = bias.value + self.selection_bias_rate * jnp.sign(
                jnp.mean(counts) - counts
            )

        names = ("w_gate", "w_up") if self.expert_kind == "swiglu" else ("w_up",)
        stacks = [
            self.param(name, _expert_init, (held, embed, width)) for name in names
        ] + [self.param("w_down", _expert_init, (held, width, embed))]
        if self.dtype is not None:
            x = x.astype(self.dtype)
        y, rows_held = _experts_on_mesh(
            x, top_experts, weights, stacks, self.first_expert
        )
        if self.shared_width:
            y = y + self._shared_expert(x)
        # what telemetry/router_load.py reads on demand; the dispatch's own
        # count of rows beside the router's says that no pair was dropped
        stats = {"expert_counts": counts.astype(jnp.int32), "rows_held": rows_held}
        if held < self.num_experts:
            # pairs of experts that other chips hold: left out, not dropped
            last = self.first_expert + held
            stats["absent_pairs"] = jnp.sum(
                counts[: self.first_expert], dtype=jnp.int32
            ) + jnp.sum(counts[last:], dtype=jnp.int32)
        for name, value in stats.items():
            self.sow(
                ROUTER_STATS, name, jax.lax.stop_gradient(value),
                init_fn=lambda value=value: jnp.zeros_like(value),
                reduce_fn=lambda _prev, new: new,
            )
        return y

    def _shared_expert(self, x):
        def dense(features, name):
            return nn.Dense(
                features, use_bias=False, dtype=self.dtype, name=name
            )

        if self.expert_kind == "swiglu":
            hidden = nn.silu(dense(self.shared_width, "shared_gate")(x)) * dense(
                self.shared_width, "shared_up"
            )(x)
        else:
            hidden = jnp.square(nn.relu(dense(self.shared_width, "shared_up")(x)))
        return dense(x.shape[-1], "shared_down")(hidden)


def moe_sharding_rules():
    """Expert-parallel rules: the leading expert dimension of every expert
    weight stack shards over ``ep``; composes with default_tp_rules
    (distinct path patterns)."""
    from jax.sharding import PartitionSpec as P

    from elasticdl_tpu.parallel.sharding import Rule

    return [
        Rule(r"moe/(w_gate|w_up|w_down)$", P("ep", None, None)),
    ]
