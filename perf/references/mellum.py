"""Plain float32 reference of Mellum 2 (``JetBrains/Mellum2-12B-A2.5B-Instruct``
``config.json``, ``model_type: mellum``), as
``configs/mellum2_12b_a2p5b.json`` describes it: loss and gradient of one
batch.

A layer is ``h' = h + Attn(RMSNorm(h))`` then ``h'' = h' + MoE(RMSNorm(h'))``;
the zoo model writes it as two one-part blocks (``w`` or ``*`` then ``E`` of
its ``layer_pattern``), each ``x + part(norm(x))``, so a block here is read by
the key its parameters carry: ``attn`` (grouped-query attention with an
RMSNorm a head on q and k, rotary positions, and by the layer's type a window
of ``SLIDING_WINDOW`` keys, the query's own among them, or every earlier key)
or ``moe`` (softmax-routed SwiGLU experts, no shared expert).  Which attention
part is a window part is ``LAYER_TYPES`` (``config.json``'s ``layer_types``,
cut as the configuration cuts it): the parameter tree does not carry it.  Then
a final RMSNorm, an untied head without bias, the mean next-token
cross-entropy.

The rotary rule is the layer type's (``ROPE_PARAMETERS``, ``config.json``'s
``rope_parameters``), written here from the formulas of HF
``_compute_default_rope_parameters`` and ``_compute_yarn_parameters`` and not
taken from the program: ``default`` turns pair ``i`` by ``t * theta^(-2i/d)``;
``yarn`` keeps that frequency for the pairs that turn more than ``beta_fast``
times over the original length, divides it by ``factor`` for those that turn
less than ``beta_slow`` times, blends the two linearly over the pairs between,
and multiplies cos and sin by ``attention_factor``.

Everything is ``jax.numpy`` in float32 under
``default_matmul_precision("highest")``; the scores are materialised with the
window as a mask, the routers select with ``lax.top_k``; no kernel, no sort,
no grouped matmul; nothing of the program is imported: the parameter tree is
read by its leaf names.  What the tree does not carry, the numbers below, is
the published configuration's.

The chip's share.  The expert stacks hold ``w_up.shape[0]`` of the router's
experts, those from ``FIRST_EXPERT`` on; the router is as wide as published,
the ``EXPERTS_PER_TOKEN`` largest are renormalised over themselves, and a
pair routed to an expert that is not held adds nothing, here as in the
program: that partial sum goes on.  The head's rows are the vocabulary
slice's.  The routing is a constant of the step (``ROUTER_TRAINS``, the
configuration's ``router_trains`` false, a departure it states): the
gradient of the router's logits would be a partial sum as well, the held
experts' pairs alone, so the logits are not differentiated, the routers'
weights get a zero gradient and nothing reaches the layer's input through
them.

Memory, not mathematics: attention is materialised over blocks of
``QUERY_BLOCK`` query rows against the whole context, the head and its loss
run over the same blocks, the experts run as a loop over the held ones, each
applied to every row and masked to the rows that chose it, and each block of
rows, each expert and each layer is recomputed in the backward pass
(``jax.checkpoint``)."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

QUERY_BLOCK = 256
# config.json: rms_norm_eps, sliding_window, layer_types (its first four
# entries: the cut), rope_parameters, num_experts_per_tok, norm_topk_prob
RMS_NORM_EPS = 1e-6
SLIDING_WINDOW = 1024
LAYER_TYPES = (
    "sliding_attention", "sliding_attention", "sliding_attention",
    "full_attention",
)
ROPE_PARAMETERS = {
    "full_attention": {
        "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
        "original_max_position_embeddings": 8192, "beta_fast": 32,
        "beta_slow": 1, "attention_factor": 1.2772588722239782,
    },
    "sliding_attention": {"rope_type": "default", "rope_theta": 500000},
}
EXPERTS_PER_TOKEN = 8
NORM_TOPK_PROB = True
# the configuration's router_trains: this cut does not differentiate its routing
ROUTER_TRAINS = False
# the first expert this chip holds (``deployment`` in the configuration)
FIRST_EXPERT = 0


def rms_norm(x, p):
    variance = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(variance + RMS_NORM_EPS) * p["scale"]


# ---- rotary positions by the layer's type -----------------------------------------


def yarn_ramp(rope, d):
    """The share of the interpolated frequency in each of the ``d / 2``
    pairs: 0 up to the pair that turns ``beta_fast`` times over the original
    length (rounded down), 1 from the pair that turns ``beta_slow`` times
    (rounded up), linear between; both ends clipped to ``[0, d - 1]``."""
    def pair_turning(turns):
        length = rope["original_max_position_embeddings"]
        return d * math.log(length / (turns * 2 * math.pi)) / (
            2 * math.log(rope["rope_theta"])
        )

    low = max(math.floor(pair_turning(rope["beta_fast"])), 0)
    high = min(math.ceil(pair_turning(rope["beta_slow"])), d - 1)
    if low == high:
        high += 0.001
    return jnp.clip(
        (jnp.arange(d // 2, dtype=jnp.float32) - low) / (high - low), 0.0, 1.0
    )


def inv_freq(rope, d):
    """``(frequencies, attention factor)`` of a ``rope_parameters`` group."""
    base = rope["rope_theta"] ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    if rope["rope_type"] == "default":
        return 1.0 / base, 1.0
    ramp = yarn_ramp(rope, d)
    blended = (1.0 / (rope["factor"] * base)) * ramp + (1.0 / base) * (1.0 - ramp)
    factor = rope.get("attention_factor") or 0.1 * math.log(rope["factor"]) + 1.0
    return blended, factor


def rotate_half(x):
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([-x2, x1], axis=-1)


def rotary(x, layer_type):
    """HF ``apply_rotary_pos_emb`` on ``x`` (batch, T, heads, d): frequency
    ``i`` of the ``d / 2`` turns the pair ``(x_i, x_{i + d/2})`` of position
    ``t`` by ``t * inv_freq_i``; cos and sin carry the attention factor."""
    steps, d = x.shape[1], x.shape[-1]
    frequencies, factor = inv_freq(ROPE_PARAMETERS[layer_type], d)
    freqs = jnp.arange(steps, dtype=jnp.float32)[:, None] * frequencies[None, :]
    emb = jnp.concatenate([freqs, freqs], axis=-1)[None, :, None, :]
    return x * (jnp.cos(emb) * factor) + rotate_half(x) * (jnp.sin(emb) * factor)


# ---- attention: a window of keys, or every earlier one ---------------------------


def block_rows(seq: int) -> int:
    """Rows of a block: ``QUERY_BLOCK`` where it divides the context, else
    the whole context at once."""
    return QUERY_BLOCK if seq % QUERY_BLOCK == 0 else seq


def visible(rows, columns, window):
    """Query ``t`` reads key ``s`` iff ``0 <= t - s`` and, under a window,
    ``t - s < window``: the query's own key and the ``window - 1`` before."""
    ahead = rows[:, None] - columns[None, :]
    seen = ahead >= 0
    return seen if window is None else seen & (ahead < window)


def masked_attention(q, k, v, window):
    """``softmax(q k^T / sqrt(d)) v`` over the keys :func:`visible` leaves;
    ``k`` and ``v`` carry a head a group of query heads.  departure: the zoo
    runs Pallas flash kernels (``ops/attention.py``), which never hold the
    score matrix and never visit a block of keys wholly behind the window."""
    seq, d = q.shape[1], q.shape[-1]
    group = q.shape[2] // k.shape[2]
    k, v = (jnp.repeat(x, group, axis=2) for x in (k, v))
    rows = block_rows(seq)
    columns = jnp.arange(seq)

    def rows_from(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, rows, axis=1)
        scores = jnp.einsum("bqhd,bkhd->bhqk", qb, k) / math.sqrt(d)
        seen = visible(start + jnp.arange(rows), columns, window)
        scores = jnp.where(seen[None, None], scores, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v)

    blocks = jax.lax.map(jax.checkpoint(rows_from), jnp.arange(0, seq, rows))
    return jnp.moveaxis(blocks, 0, 1).reshape(q.shape)


def attention(x, a, layer_type):
    """The layer's attention part on its normed input ``x``: the RMSNorm a
    head on q and k comes before the rotary positions (assumed, after
    Qwen3-MoE), whose rule and whose keys are the layer type's."""
    def projected(name):
        return jnp.einsum("bse,ehd->bshd", x, a[name]["kernel"])

    q = rotary(rms_norm(projected("query"), a["q_norm"]), layer_type)
    k = rotary(rms_norm(projected("key"), a["k_norm"]), layer_type)
    window = SLIDING_WINDOW if layer_type == "sliding_attention" else None
    u = masked_attention(q, k, projected("value"), window)
    return jnp.einsum("bshd,hde->bse", u, a["out"]["kernel"])


# ---- the routed experts -----------------------------------------------------------


def swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def route(tokens, m):
    """The weight of every expert for every token (zero where the expert was
    not chosen), over all the experts the router scores: softmax over all of
    them, the ``EXPERTS_PER_TOKEN`` largest, over their sum
    (``norm_topk_prob``)."""
    experts = m["router"]["kernel"].shape[1]
    logits = tokens @ m["router"]["kernel"]
    if not ROUTER_TRAINS:
        logits = jax.lax.stop_gradient(logits)
    probs = jax.nn.softmax(logits, axis=-1)
    top, chosen = jax.lax.top_k(probs, EXPERTS_PER_TOKEN)
    if NORM_TOPK_PROB:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    one_hot = jax.nn.one_hot(chosen, experts, dtype=tokens.dtype)
    return jnp.einsum("tk,tke->te", top, one_hot)


def experts(x, m):
    """``sum_e weight[:, e] * SwiGLU_e(x)`` over the experts held here."""
    tokens = x.reshape(-1, x.shape[-1])
    held = m["w_up"].shape[0]
    weight = jax.lax.dynamic_slice_in_dim(
        route(tokens, m), FIRST_EXPERT, held, axis=1
    )

    def one(weights_of_expert, stacks):
        return swiglu(tokens, *stacks) * weights_of_expert[:, None]

    def add(y, per_expert):
        return y + jax.checkpoint(one)(*per_expert), None

    y, _ = jax.lax.scan(
        add, jnp.zeros_like(tokens), (weight.T, (m["w_gate"], m["w_up"], m["w_down"]))
    )
    return y.reshape(x.shape)


def block(x, p, layer_type):
    """The one part ``p`` carries under its norm, ``x + part(RMSNorm(x))``."""
    y = rms_norm(x, p["RMSNorm_0"])
    if "attn" in p:
        return x + attention(y, p["attn"], layer_type)
    return x + experts(y, p["moe"])


# ---- the stack and its loss ------------------------------------------------------


def token_losses(x, head, labels):
    """``logsumexp(logits) - logits[label]`` at every position, over the
    head's rows (the vocabulary slice); the head is untied, without bias."""
    seq = x.shape[1]
    rows = block_rows(seq)

    def rows_from(start):
        logits = jax.lax.dynamic_slice_in_dim(x, start, rows, axis=1) @ head["kernel"]
        wanted = jax.lax.dynamic_slice_in_dim(labels, start, rows, axis=1)
        picked = jnp.take_along_axis(logits, wanted[..., None], axis=-1)[..., 0]
        return jax.nn.logsumexp(logits, axis=-1) - picked

    blocks = jax.lax.map(jax.checkpoint(rows_from), jnp.arange(0, seq, rows))
    return jnp.moveaxis(blocks, 0, 1).reshape(labels.shape)


def loss_fn(params, tokens, labels):
    x = params["tok_embed"]["embedding"][tokens]
    layer_types = iter(LAYER_TYPES)
    for index in range(sum(name.startswith("block_") for name in params)):
        p = params[f"block_{index}"]
        layer_type = next(layer_types) if "attn" in p else None
        x = jax.checkpoint(block, static_argnums=(2,))(x, p, layer_type)
    x = rms_norm(x, params["RMSNorm_0"])
    return jnp.mean(token_losses(x, params["lm_head"], labels))


def loss_and_grads(params, features, labels):
    """``(loss, grads)``; ``grads`` has the tree of ``params``.  Depth, the
    kind of each part, widths, heads, the experts held and the vocabulary
    slice are the parameter tree's own shapes."""
    params = jax.tree_util.tree_map(lambda p: jnp.asarray(p, jnp.float32), params)
    tokens = jnp.asarray(features["tokens"], jnp.int32)
    labels = jnp.asarray(labels, jnp.int32)
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(loss_fn)(params, tokens, labels)
