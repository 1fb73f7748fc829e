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
The gradient of the router's logits is then a partial sum too: only the held
experts' pairs add to it, so a step taken along it sends the pairs to the
held experts, at a speed Adam's rate alone sets (PERF.md section 7, From PR
61 (c)).  A router with a selection bias is held to the mean load all the
same; one without takes ``router_trains=False``: the logits enter the step
as constants, the router's weights get a zero gradient, and the routing
stays where the seeded weights put it.

Dispatch has static shapes at any imbalance
(``ops/grouped_matmul.py``): a stable sort of the pairs by expert, each
expert's rows padded to whole tiles, a gather into that order, the grouped
matmuls, and a gather back with the weights — the permutation's transpose
is a gather too (each pair has one row), so no scatter of activations runs
in either direction.  (On a low rung, below, the rows are added into their
tokens: by a kernel, ``grouped_matmul.sum_by_token``, not by a scatter.)  On
a mesh the experts' leading dimension shards over
``ep`` (``moe_sharding_rules``): under ``shard_map`` each rank lays out only
the pairs of its own experts, the others weigh zero, and a ``psum`` over
``ep`` completes the combine.  The all-to-all that would move tokens
instead of replicating them over ``ep`` is ROADMAP B5's follow-up.

**The ladder of row buffers.**  A buffer that no routing can overflow holds
every pair the router made (``grouped_matmul.num_rows``).  Where the
experts laid out on a device are fewer than the experts the router scores
— ``experts_held < num_experts``, or the stacks sharded over ``ep`` — the
device is sent ``share = local experts / routed experts`` of the pairs at a
balanced load, and every pass between router and combine would walk a
buffer sized for all of them.  There the buffer's size is chosen each step,
on the device, from ``grouped_matmul.ladder``'s static sizes: a low rung
for twice the balanced share (7,168 rows for 8 of 128 experts and 49,152
pairs, where the full size is 50,176), one of twice its rows (14,336
there), then the full size, derived from the shapes alone.  The pairs are sorted and counted once, outside the choice;
``lax.switch`` on the tiles the counts need takes the smallest rung that
holds them (no host readback), and inside the branch the layout, the three
kernels' grids, the activation and both permutations run at the rung's
size.  Below the full rung the permutations move rows and not pairs
(``_combine_by_rows``, ``_dispatch_by_rows``: the rows added into their
tokens by the kernel ``expert_rows_sum``, each tile of tokens fetching its
rows' spans from the buffer, ``grouped_matmul.sum_by_token``; the weights'
gradient a dot product a row).  "Nothing is ever
dropped" now rests on the last rung, which is always the full size, at the
cost every step paid before; a routing that outgrows the low rung by a few
thousand rows (a router collapsing onto one held expert) takes the next.  The choice sits inside one ``custom_vjp``
(``_experts_on_ladder``) whose residuals are its inputs and whose backward
chooses again and recomputes the taken rung's forward inside the branch, so
nothing shaped by a rung crosses the choice (plain autodiff through it
hands out every branch's residuals, zero-filled where not taken).  A layer
that holds every expert it routes over has one rung: no choice, no wrapper,
the program it had.  Each layer sows the rows of the rung it took
(``router_stats``: ``buffer_rows``).

No reference counterpart; listed in DEVIATIONS.md additions.
"""

from __future__ import annotations

import functools
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from elasticdl_tpu.ops import grouped_matmul as gmm_ops
from elasticdl_tpu.ops import on_mesh
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


def _rows_of(weights, row_pair):
    """Of each row of the buffer: its pair's weight (0 on a padding row)
    and its token (``tokens``, out of range, on a padding row)."""
    tokens, slots = weights.shape
    held = row_pair < tokens * slots  # padding rows hold no pair
    pair = jnp.minimum(row_pair, tokens * slots - 1)
    return (
        jnp.where(held, weights.reshape(-1)[pair], 0.0),
        jnp.where(held, pair // slots, tokens),
    )


# The same two permutations in the form a rung below the full one takes:
# what moves is rows x d, not pairs x d.  A buffer that holds an eighth of
# the pairs makes the pair-indexed gathers above (an absent pair reads row
# 0) the layer's largest passes; here the rows are added into their tokens
# instead, by ``grouped_matmul.sum_by_token``: the combine's forward and the
# dispatch's transpose.  Up to PR 48 that was XLA's float32 scatter-add,
# chosen at a low rung's 7,168 rows of 2,688 (1.18 ms against the gather's
# 2.99, PERF.md section 6, PR 33) and a row at a time whatever the row's
# width: 4.1 ms a call at the 34,816 rows of a 16,384-token step, where the
# kernel takes 0.86 (weights) and 0.53 (weight 1; PERF.md section 6, PR 49).
# The weights' gradient is a dot product a row (0.51 ms against 3.26 by
# pairs).  A token's rows are summed in the order the kernel meets them
# here and in slot order above: the results differ by a float32 rounding of
# a sum of at most ``slots`` terms.


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _dispatch_by_rows(x, row_token, spans, tokens, interpret):
    """``x[row_token]``; a padding row reads the last token.  ``spans``:
    ``grouped_matmul.token_spans`` of the layout, for the way back."""
    return x[jnp.minimum(row_token, tokens - 1)]


def _dispatch_by_rows_fwd(x, row_token, spans, tokens, interpret):
    return _dispatch_by_rows(x, row_token, spans, tokens, interpret), (
        row_token, spans,
    )


def _dispatch_by_rows_bwd(tokens, interpret, residuals, d_rows):
    row_token, spans = residuals
    d_x = gmm_ops.sum_by_token(
        d_rows, row_token, tokens, spans, interpret=interpret
    )
    return d_x, None, None


_dispatch_by_rows.defvjp(_dispatch_by_rows_fwd, _dispatch_by_rows_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _combine_by_rows(rows, weights, row_pair, spans, interpret):
    """:func:`_combine` as ``y[token of r] += weight of r * rows[r]``."""
    row_weight, row_token = _rows_of(weights, row_pair)
    return gmm_ops.sum_by_token(
        rows, row_token, weights.shape[0], spans, row_weight,
        interpret=interpret,
    )


def _combine_by_rows_fwd(rows, weights, row_pair, spans, interpret):
    return _combine_by_rows(rows, weights, row_pair, spans, interpret), (
        rows, weights, row_pair,
    )


def _combine_by_rows_bwd(interpret, residuals, d_y):
    rows, weights, row_pair = residuals
    row_weight, row_token = _rows_of(weights, row_pair)
    d_y_rows = d_y[jnp.minimum(row_token, weights.shape[0] - 1)].astype(
        jnp.float32
    )
    d_rows = (d_y_rows * row_weight[:, None]).astype(d_y.dtype)
    # a pair's weight moves the loss by <its row, its token's d_y>, put at
    # the pair (each has one row; a padding row's falls out)
    row_dot = jnp.sum(rows.astype(jnp.float32) * d_y_rows, axis=-1)
    d_weights = jnp.zeros((weights.size,), weights.dtype).at[row_pair].set(
        row_dot.astype(weights.dtype), mode="drop", unique_indices=True
    )
    return d_rows, d_weights.reshape(weights.shape), None, None


_combine_by_rows.defvjp(_combine_by_rows_fwd, _combine_by_rows_bwd)


def _experts_at(
    rows, by_rows, tile_rows, interpret, x, weights, group_ids, order, stacks
):
    """Dispatch, grouped matmuls, activation and combine in a buffer of
    ``rows`` rows that holds this routing (``order`` =
    ``group_order(group_ids)``); ``by_rows`` picks the permutations' form.
    Returns the output and the number of pairs that were given a row."""
    tokens, slots = weights.shape
    experts = stacks[0].shape[0]
    # the three regions by telemetry/op_scopes.py's names; the kernels
    # keep theirs (a scope around a call changes no custom-call's name)
    with jax.named_scope("dispatch"):
        layout = gmm_ops.group_layout(
            group_ids, experts, tile_rows, rows, order, by_rows
        )
        held = layout.row_pair < tokens * slots
        if by_rows:
            _, row_token = _rows_of(weights, layout.row_pair)
            spans = gmm_ops.token_spans(
                group_ids, order.sizes, tokens, tile_rows
            )
            buffer = _dispatch_by_rows(x, row_token, spans, tokens, interpret)
        else:
            pair_row = layout.pair_row.reshape(tokens, slots)
            row_token = (
                jnp.minimum(layout.row_pair, tokens * slots - 1) // slots
            )
            buffer = _dispatch(
                x, row_token, pair_row,
                (group_ids < experts).reshape(tokens, slots),
            )
    matmul = functools.partial(
        gmm_ops.grouped_matmul, tile_group=layout.tile_group,
        tile_rows=tile_rows, interpret=interpret,
    )
    with jax.named_scope("experts"):
        if len(stacks) == 3:
            w_gate, w_up, w_down = stacks
            hidden = nn.silu(matmul(buffer, w_gate)) * matmul(buffer, w_up)
        else:
            w_up, w_down = stacks
            hidden = jnp.square(nn.relu(matmul(buffer, w_up)))
        out = matmul(hidden, w_down)
    with jax.named_scope("combine"):
        if by_rows:
            y = _combine_by_rows(
                out, weights, layout.row_pair, spans, interpret
            )
        else:
            y = _combine(out, weights, pair_row, layout.row_pair)
    with jax.named_scope("dispatch"):
        return y, jnp.sum(held, dtype=jnp.int32)


def _rung_of(rungs, tile_rows, sizes):
    """Index of the smallest rung that holds groups of ``sizes``: a device
    scalar, read by no host."""
    tiles = jnp.asarray([rows // tile_rows for rows in rungs[:-1]], jnp.int32)
    return jnp.sum(gmm_ops.tiles_needed(sizes, tile_rows) > tiles)


def _branches(rungs, tile_rows, interpret):
    # every rung below the last moves rows, the last (today's size) pairs
    return [
        functools.partial(
            _experts_at, rows, rows < rungs[-1], tile_rows, interpret
        )
        for rows in rungs
    ]


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _experts_on_ladder(
    rungs, tile_rows, interpret, x, weights, group_ids, order, stacks
):
    """:func:`_experts_at` the smallest of ``rungs`` that holds this step's
    routing, chosen on the device.  Nothing whose shape follows the rung
    leaves the choice: plain autodiff through it would have every branch
    hand out every branch's residuals, zero-filled where not taken (the
    full-size buffers written for nothing, the low rung's added to the
    peak).  So the residuals are the inputs, and the backward pass makes the
    same choice again and runs, inside the branch, that rung's forward up
    to the combine's input and its backward.  Returns the output, the pairs
    given a row and the rows of the rung taken."""
    # (the choice and what XLA puts in its branches, by op_scopes's name)
    with jax.named_scope("rung"):
        rung = _rung_of(rungs, tile_rows, order.sizes)
        y, held = jax.lax.switch(
            rung, _branches(rungs, tile_rows, interpret),
            x, weights, group_ids, order, stacks,
        )
        return y, held, jnp.asarray(rungs, jnp.int32)[rung]


def _experts_on_ladder_fwd(
    rungs, tile_rows, interpret, x, weights, group_ids, order, stacks
):
    out = _experts_on_ladder(
        rungs, tile_rows, interpret, x, weights, group_ids, order, stacks
    )
    return out, (x, weights, group_ids, order, stacks)


def _experts_on_ladder_bwd(rungs, tile_rows, interpret, residuals, cotangents):
    x, weights, group_ids, order, stacks = residuals

    def backward(experts_at):
        def forward(x, weights, stacks):
            # under a scope of its own a kernel keeps the op name it gives
            # itself (``expert_gmm_dw``, which ``perf/`` reads it by); a
            # differentiation with nothing named inside names the ops
            # ``jvp(expert_gmm_dw)``
            with jax.named_scope("rung"):
                return experts_at(x, weights, group_ids, order, stacks)[0]

        def run(d_y, x, weights, stacks):
            return jax.vjp(forward, x, weights, stacks)[1](d_y)

        return run

    with jax.named_scope("rung"):
        d_x, d_weights, d_stacks = jax.lax.switch(
            _rung_of(rungs, tile_rows, order.sizes),
            [backward(b) for b in _branches(rungs, tile_rows, interpret)],
            cotangents[0], x, weights, stacks,
        )
    return d_x, d_weights, None, None, d_stacks


_experts_on_ladder.defvjp(_experts_on_ladder_fwd, _experts_on_ladder_bwd)


def routed_experts(
    x, top_experts, weights, *stacks, first_expert=0, num_experts=None,
    tile_rows: int = gmm_ops.TILE_ROWS, interpret: bool | None = None,
):
    """The experts' part on one device: ``x`` (tokens, d), ``top_experts``
    and ``weights`` (tokens, k), the weight ``stacks`` of the
    ``stacks[0].shape[0]`` experts that start at ``first_expert`` — three
    (gate, up, down) for SwiGLU experts, two (up, down) for relu^2 ones —
    of the ``num_experts`` that ``top_experts`` ranges over (default: these
    are all).  A pair whose expert is not among them adds nothing here.
    Returns the output, the number of pairs that were given a row, and the
    rows of the buffer they were laid out in beside the rows of the full
    one (int32[2])."""
    tokens, slots = top_experts.shape
    experts = stacks[0].shape[0]
    with jax.named_scope("dispatch"):
        local = top_experts - first_expert
        grouped = (local >= 0) & (local < experts)
        group_ids = (
            jnp.where(grouped, local, experts).reshape(-1).astype(jnp.int32)
        )
        order = gmm_ops.group_order(group_ids, experts)
        weights = jnp.where(grouped, weights, 0.0)
    rungs = gmm_ops.ladder(
        tokens * slots, experts, num_experts or experts, tile_rows
    )
    if len(rungs) == 1:
        # every routed expert is here: all pairs are held, nothing to choose
        y, held = _experts_at(
            rungs[0], False, tile_rows, interpret,
            x, weights, group_ids, order, stacks,
        )
        rows = jnp.int32(rungs[0])
    else:
        y, held, rows = _experts_on_ladder(
            rungs, tile_rows, interpret, x, weights, group_ids, order, stacks
        )
    return y, held, jnp.stack([rows, jnp.int32(rungs[-1])])


def _experts_on_mesh(
    x, top_experts, weights, stacks, first_expert=0, num_experts=None
):
    """``routed_experts`` under the registered mesh: tokens stay on their
    batch (and sequence) axes, the held experts (``stacks``, from
    ``first_expert`` on, of the ``num_experts`` routed over) shard over
    ``ep``, and a compiled Pallas kernel, which GSPMD cannot partition, runs
    per device.  The counts come back summed over the devices.

    The decision is ``ops/on_mesh.py``'s; the mapped region is this
    function's own, because what runs in it is not what runs without it:
    a device's first expert follows its place on ``ep``, and the output and
    the counts are summed over the axes of the specs below."""
    from jax.sharding import PartitionSpec as P

    from elasticdl_tpu.ops.ring_attention import sequence_shard_spec

    batch, seq, embed = x.shape
    slots = top_experts.shape[-1]

    def local(x, top_experts, weights, *stacks, **kw):
        with jax.named_scope("dispatch"):
            flat = (
                x.reshape(-1, embed), top_experts.reshape(-1, slots),
                weights.reshape(-1, slots),
            )
        y, held, buffer_rows = routed_experts(
            *flat, *stacks, num_experts=num_experts, **kw
        )
        with jax.named_scope("combine"):
            return y.reshape(x.shape), held, buffer_rows

    interpret, mesh = on_mesh.resolve()
    if mesh is None:
        return local(
            x, top_experts, weights, *stacks, first_expert=first_expert,
            interpret=interpret,
        )
    sp_axis = on_mesh.get_attention_mesh()[1]
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
        y, *counts = local(
            x, top_experts, weights, *stacks,
            first_expert=first, interpret=interpret,
        )
        return (
            jax.lax.psum(y, ep) if ep else y,
            *(jax.lax.psum(counts, counted) if counted else counts),
        )

    return jax.shard_map(
        per_device,
        mesh=mesh,
        in_specs=(tokens, tokens, tokens) + (experts,) * len(stacks),
        out_specs=(tokens, P(), P()),
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
    # the shared expert's output times sigmoid(x w_g), w_g: embed -> 1
    shared_gated: bool = False
    experts_held: int = 0  # 0: all of them
    first_expert: int = 0
    router_trains: bool = True  # False: the logits are constants of the step

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
        with jax.named_scope("route"):
            top_experts, weights, counts = self._route(x, training)
        names = ("w_gate", "w_up") if self.expert_kind == "swiglu" else ("w_up",)
        stacks = [
            self.param(name, _expert_init, (held, embed, width)) for name in names
        ] + [self.param("w_down", _expert_init, (held, width, embed))]
        if self.dtype is not None:
            x = x.astype(self.dtype)
        y, rows_held, buffer_rows = _experts_on_mesh(
            x, top_experts, weights, stacks, self.first_expert, self.num_experts
        )
        if self.shared_width:
            with jax.named_scope("shared"):
                y = y + self._shared_expert(x)
        with jax.named_scope("route"):
            self._sow_stats(counts, rows_held, buffer_rows, held)
        return y

    def _route(self, x, training):
        """Scores, the ``k`` experts a token and their weights, the
        auxiliary losses and the selection bias's step; returns the experts,
        the weights and the pairs routed to each expert."""
        # the router in float32 at full precision: a near-tie between two
        # experts is decided as a float32 reference decides it
        logits = nn.Dense(
            self.num_experts, use_bias=False, name="router",
            precision=jax.lax.Precision.HIGHEST,
        )(x.astype(jnp.float32))
        if not self.router_trains:
            logits = jax.lax.stop_gradient(logits)
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

        return top_experts, weights, counts

    def _sow_stats(self, counts, rows_held, buffer_rows, held):
        # what telemetry/router_load.py reads on demand; the dispatch's own
        # count of rows beside the router's says that no pair was dropped,
        # the rows of the rung its buffer took beside the full rung's how
        # much of the static size this step walked
        stats = {
            "expert_counts": counts.astype(jnp.int32),
            "rows_held": rows_held,
            "buffer_rows": buffer_rows,
        }
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
        y = dense(x.shape[-1], "shared_down")(hidden)
        if self.shared_gated:
            y = y * jax.nn.sigmoid(dense(1, "shared_expert_gate")(x))
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
