"""Plain float32 reference of AFMoE (``arcee-ai/Trinity-Mini`` ``config.json``,
``model_type: afmoe``), as ``configs/trinity_mini_26b_a3b.json`` describes
it: loss and gradient of one batch.

``h^0 = sqrt(d) * E[token]`` (``mup_enabled``).  A layer is ``a = h +
Norm_2(Attn(Norm_1(h)))`` then ``h' = a + Norm_4(FFN(Norm_3(a)))``, four
RMSNorms a layer; the zoo model writes it as two one-part blocks (``w`` or
``*`` then ``-`` or ``E`` of its ``layer_pattern``), each ``x +
norm_out(part(norm_in(x)))``, so a block here is read by the keys its
parameters carry: ``attn`` (grouped-query attention with an RMSNorm a head on
q and k, an output gate ``out(u * sigmoid(gate(x)))``, and by the layer's
type either rotary positions and a window of ``SLIDING_WINDOW`` keys, the
query's own among them, or no position signal and every earlier key),
``mlp_gate`` (the leading dense SwiGLU layer), ``moe`` (sigmoid-routed SwiGLU
experts and a shared expert).  Which attention part is a window part is
``LAYER_TYPES`` (``config.json``'s ``layer_types``, cut as the configuration
cuts it): the parameter tree does not carry it.  Then a final RMSNorm, an
untied head without bias, the mean next-token cross-entropy.

Everything is ``jax.numpy`` in float32 under
``default_matmul_precision("highest")``; the scores are materialised with the
window as a mask, the routers select with ``lax.top_k``; no kernel, no sort,
no grouped matmul; nothing of the program is imported: the parameter tree is
read by its leaf names.  What the tree does not carry, the numbers below, is
the published configuration's.

The chip's share.  The expert stacks hold ``w_up.shape[0]`` of the router's
experts, those from ``FIRST_EXPERT`` on; a pair routed to another expert adds
nothing, here as in the program, and that partial sum goes on.  The head's
rows are the vocabulary slice's.

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
# config.json: rms_norm_eps, rope_theta (rope_scaling null), sliding_window,
# layer_types (its first five entries: the cut), num_experts_per_tok,
# route_norm, route_scale (n_group = topk_group = 1: no limit on the groups a
# token's experts come from), mup_enabled
RMS_NORM_EPS = 1e-5
ROPE_THETA = 1e4
SLIDING_WINDOW = 2048
LAYER_TYPES = (
    "sliding_attention", "sliding_attention", "sliding_attention",
    "full_attention", "sliding_attention",
)
EXPERTS_PER_TOKEN = 8
ROUTE_NORM = True
ROUTE_SCALE = 2.826
MUP_ENABLED = True
# the first expert this chip holds (``deployment`` in the configuration)
FIRST_EXPERT = 0


def rms_norm(x, p):
    variance = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(variance + RMS_NORM_EPS) * p["scale"]


# ---- attention: a window with rotary positions, or everything with none ------


def rotate_half(x):
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([-x2, x1], axis=-1)


def rotary(x):
    """HF ``apply_rotary_pos_emb`` on ``x`` (batch, T, heads, d): frequency
    ``i`` of the ``d / 2`` turns the pair ``(x_i, x_{i + d/2})`` of position
    ``t`` by ``t * theta^(-2i/d)``."""
    steps, d = x.shape[1], x.shape[-1]
    inv_freq = 1.0 / (ROPE_THETA ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    freqs = jnp.arange(steps, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    emb = jnp.concatenate([freqs, freqs], axis=-1)[None, :, None, :]
    return x * jnp.cos(emb) + rotate_half(x) * jnp.sin(emb)


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
    """The layer's attention part on its normed input ``x`` (HF
    ``AfmoeAttention``): the RMSNorm a head on q and k comes before the
    rotary positions, which a ``sliding_attention`` layer alone applies; the
    gate is taken of the same input and meets the heads' merged output
    before ``o_proj``."""
    def projected(name):
        return jnp.einsum("bse,ehd->bshd", x, a[name]["kernel"])

    q = rms_norm(projected("query"), a["q_norm"])
    k = rms_norm(projected("key"), a["k_norm"])
    v = projected("value")
    window = None
    if layer_type == "sliding_attention":
        q, k, window = rotary(q), rotary(k), SLIDING_WINDOW
    u = masked_attention(q, k, v, window)
    u = u * jax.nn.sigmoid(projected("gate"))
    return jnp.einsum("bshd,hde->bse", u, a["out"]["kernel"])


# ---- feed-forward: the dense layer, the routed experts, the shared expert ----------


def swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def route(tokens, m, bias):
    """The weight of every expert for every token (zero where the expert was
    not chosen), over all the experts the router scores: ``score_func``
    sigmoid; the ``EXPERTS_PER_TOKEN`` largest of ``scores + bias`` (the
    selection bias, outside the gradient); the chosen experts' scores WITHOUT
    the bias, over their sum (``route_norm``), times ``route_scale``."""
    experts = m["router"]["kernel"].shape[1]
    scores = jax.nn.sigmoid(tokens @ m["router"]["kernel"])
    chosen = jax.lax.top_k(scores + jax.lax.stop_gradient(bias), EXPERTS_PER_TOKEN)[1]
    top = jnp.take_along_axis(scores, chosen, axis=-1)
    if ROUTE_NORM:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    one_hot = jax.nn.one_hot(chosen, experts, dtype=tokens.dtype)
    return jnp.einsum("tk,tke->te", top * ROUTE_SCALE, one_hot)


def experts(x, m, bias):
    """``sum_e weight[:, e] * SwiGLU_e(x)`` over the experts held here plus
    the shared expert on every token, unweighted."""
    tokens = x.reshape(-1, x.shape[-1])
    held = m["w_up"].shape[0]
    weight = jax.lax.dynamic_slice_in_dim(
        route(tokens, m, bias), FIRST_EXPERT, held, axis=1
    )

    def one(weights_of_expert, stacks):
        return swiglu(tokens, *stacks) * weights_of_expert[:, None]

    def add(y, per_expert):
        return y + jax.checkpoint(one)(*per_expert), None

    y, _ = jax.lax.scan(
        add, jnp.zeros_like(tokens), (weight.T, (m["w_gate"], m["w_up"], m["w_down"]))
    )
    y = y + swiglu(
        tokens, *(m[f"shared_{name}"]["kernel"] for name in ("gate", "up", "down"))
    )
    return y.reshape(x.shape)


def selection_bias(buffers, name, m):
    """The router's selection bias of block ``name``: a buffer the program
    keeps outside its parameters (collection ``router_stats``); zero where
    none is given, as at the seeded init."""
    try:
        return jnp.asarray(buffers[name]["moe"]["selection_bias"], jnp.float32)
    except (KeyError, TypeError):
        return jnp.zeros((m["router"]["kernel"].shape[1],), jnp.float32)


def block(x, p, bias, layer_type):
    """The one part ``p`` carries under its two norms, ``x +
    RMSNorm_1(part(RMSNorm_0(x)))``."""
    y = rms_norm(x, p["RMSNorm_0"])
    if "attn" in p:
        y = attention(y, p["attn"], layer_type)
    elif "mlp_gate" in p:
        y = swiglu(y, *(p[f"mlp_{name}"]["kernel"] for name in ("gate", "up", "down")))
    else:
        y = experts(y, p["moe"], bias)
    return x + rms_norm(y, p["RMSNorm_1"])


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


def loss_fn(params, tokens, labels, buffers=None):
    x = params["tok_embed"]["embedding"][tokens]
    if MUP_ENABLED:
        x = x * math.sqrt(x.shape[-1])
    layer_types = iter(LAYER_TYPES)
    for index in range(sum(name.startswith("block_") for name in params)):
        name = f"block_{index}"
        p = params[name]
        bias = selection_bias(buffers, name, p["moe"]) if "moe" in p else None
        layer_type = next(layer_types) if "attn" in p else None
        x = jax.checkpoint(block, static_argnums=(3,))(x, p, bias, layer_type)
    x = rms_norm(x, params["RMSNorm_0"])
    return jnp.mean(token_losses(x, params["lm_head"], labels))


def loss_and_grads(params, features, labels, router_stats=None):
    """``(loss, grads)``; ``grads`` has the tree of ``params``.  Depth, the
    kind of each part, widths, heads, the experts held and the vocabulary
    slice are the parameter tree's own shapes.  ``router_stats`` is the
    program's collection of that name, for the routers' selection biases."""
    params = jax.tree_util.tree_map(lambda p: jnp.asarray(p, jnp.float32), params)
    tokens = jnp.asarray(features["tokens"], jnp.int32)
    labels = jnp.asarray(labels, jnp.int32)
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(loss_fn)(params, tokens, labels, router_stats)
