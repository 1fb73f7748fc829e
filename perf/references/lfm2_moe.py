"""Plain float32 reference of Liquid AI's ``lfm2_moe`` stack
(``LiquidAI/LFM2-24B-A2B`` ``config.json``), as
``configs/lfm2_24b_a2b.json`` describes it: loss and gradient of one batch.

``h^0 = E[token]``.  A layer is ``h = x + Op(Norm_1(x))`` then ``y = h +
FF(Norm_2(h))``, two RMSNorms a layer; the zoo model writes it as two
one-part blocks (``c`` or ``*`` then ``-`` or ``E`` of its
``layer_pattern``), each ``x + part(norm(x))``, so a block here is read by
the key its parameters carry:

- ``conv``, the gated short convolution: ``[B | C | X] = W_in u`` (three
  widths ``d``), ``z = B * X``, ``c_t = sum_{s < 3} k_s z_{t - s}`` a channel
  with zeros before the sequence's first token, ``Op(u) = W_out (C * c)``;
  no bias, no activation.  ``k_s`` is row ``2 - s`` of the layer's
  ``conv_kernel`` (``torch.nn.Conv1d``'s order: the last row on the current
  token);
- ``attn``, grouped-query attention: an RMSNorm a head on q and k (one
  scale for q, one for k, over a head's 64 numbers), then rotate-half rotary
  positions at ``ROPE_THETA`` over the whole head, causal ``softmax(q k^T /
  sqrt(d)) v``, four query heads a key head;
- ``mlp_gate``, the leading dense SwiGLU layer;
- ``moe``: ``s = sigmoid(W_g v)``; the ``EXPERTS_PER_TOKEN`` chosen are the
  largest of ``s + b`` (``b`` the selection bias, a buffer outside the
  gradient, used for the choice only); ``w_e = s_e / (sum_chosen s +
  NORM_TOPK_EPS)`` times ``ROUTED_SCALING``; ``FF(v) = sum_e w_e E_e(v)``,
  ``E_e`` a SwiGLU.  No shared expert.

Then a final RMSNorm and the head, which is the token embedding again:
``logits = Norm(x) E^T``, so the embedding's gradient is the sum of its two
uses.  The mean next-token cross-entropy; no auxiliary loss.

Everything is ``jax.numpy`` in float32 under
``default_matmul_precision("highest")``; the convolution is three shifted
multiply-adds, the scores are materialised, the routers select with
``lax.top_k``; no kernel, no sort, no grouped matmul; nothing of the program
is imported: the parameter tree is read by its leaf names.  What the tree
does not carry, the numbers below, is the published configuration's.

The chip's share.  The expert stacks hold ``w_up.shape[0]`` of the router's
experts, those from ``FIRST_EXPERT`` on; a pair routed to another expert adds
nothing, here as in the program, and that partial sum goes on.  The
embedding's rows are the vocabulary slice's.

Memory, not mathematics: attention is materialised over blocks of
``QUERY_BLOCK`` query rows against the whole context, the head and its loss
run over the same blocks, the experts run as a loop over the held ones, each
applied to every row and weighed by the rows that chose it, and each block of
rows, each expert and each layer is recomputed in the backward pass
(``jax.checkpoint``)."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

QUERY_BLOCK = 256
# config.json: norm_eps, rope_theta, num_experts_per_tok, norm_topk_prob,
# routed_scaling_factor, use_expert_bias (the bias is ``router_stats``'s)
RMS_NORM_EPS = 1e-5
ROPE_THETA = 1e6
EXPERTS_PER_TOKEN = 4
NORM_TOPK_PROB = True
ROUTED_SCALING = 1.0
# HF ``Lfm2MoeSparseMoeBlock``: the chosen scores over their sum plus this
NORM_TOPK_EPS = 1e-6
# the first expert this chip holds (``deployment`` in the configuration)
FIRST_EXPERT = 0


def rms_norm(x, p):
    variance = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(variance + RMS_NORM_EPS) * p["scale"]


# ---- the gated short convolution ------------------------------------------------


def steps_back(z, s):
    """``z_{t - s}`` along axis 1, zeros before a sequence's first token."""
    if not s:
        return z
    return jnp.pad(z, ((0, 0), (s, 0), (0, 0)))[:, : z.shape[1]]


def short_conv(u, p):
    b, c, x = jnp.split(u @ p["in_proj"]["kernel"], 3, axis=-1)
    z = b * x
    taps = p["conv_kernel"]
    last = taps.shape[0] - 1
    conv = sum(taps[last - s] * steps_back(z, s) for s in range(last + 1))
    return (c * conv) @ p["out_proj"]["kernel"]


# ---- attention ----------------------------------------------------------------------


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


def causal_attention(q, k, v):
    """``softmax(q k^T / sqrt(d)) v`` over the keys ``s <= t``; ``k`` and
    ``v`` carry a head a group of query heads.  departure: the zoo runs
    Pallas flash kernels (``ops/attention.py``), which never hold the score
    matrix."""
    seq, d = q.shape[1], q.shape[-1]
    group = q.shape[2] // k.shape[2]
    k, v = (jnp.repeat(x, group, axis=2) for x in (k, v))
    rows = block_rows(seq)
    columns = jnp.arange(seq)

    def rows_from(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, rows, axis=1)
        scores = jnp.einsum("bqhd,bkhd->bhqk", qb, k) / math.sqrt(d)
        seen = (start + jnp.arange(rows))[:, None] >= columns[None, :]
        scores = jnp.where(seen[None, None], scores, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v)

    blocks = jax.lax.map(jax.checkpoint(rows_from), jnp.arange(0, seq, rows))
    return jnp.moveaxis(blocks, 0, 1).reshape(q.shape)


def attention(x, a):
    def projected(name):
        return jnp.einsum("bse,ehd->bshd", x, a[name]["kernel"])

    q = rotary(rms_norm(projected("query"), a["q_norm"]))
    k = rotary(rms_norm(projected("key"), a["k_norm"]))
    u = causal_attention(q, k, projected("value"))
    return jnp.einsum("bshd,hde->bse", u, a["out"]["kernel"])


# ---- feed-forward: the dense layer, the routed experts ----------------------------


def swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def route(tokens, m, bias):
    """The weight of every expert for every token (zero where the expert was
    not chosen), over all the experts the router scores."""
    experts = m["router"]["kernel"].shape[1]
    scores = jax.nn.sigmoid(tokens @ m["router"]["kernel"])
    chosen = jax.lax.top_k(
        scores + jax.lax.stop_gradient(bias), EXPERTS_PER_TOKEN
    )[1]
    top = jnp.take_along_axis(scores, chosen, axis=-1)
    if NORM_TOPK_PROB:
        top = top / (jnp.sum(top, axis=-1, keepdims=True) + NORM_TOPK_EPS)
    one_hot = jax.nn.one_hot(chosen, experts, dtype=tokens.dtype)
    return jnp.einsum("tk,tke->te", top * ROUTED_SCALING, one_hot)


def experts(x, m, bias):
    """``sum_e weight[:, e] * SwiGLU_e(x)`` over the experts held here; there
    is no shared expert."""
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
    return y.reshape(x.shape)


def selection_bias(buffers, name, m):
    """The router's selection bias of block ``name``: a buffer the program
    keeps outside its parameters (collection ``router_stats``); zero where
    none is given, as at the seeded init."""
    try:
        return jnp.asarray(buffers[name]["moe"]["selection_bias"], jnp.float32)
    except (KeyError, TypeError):
        return jnp.zeros((m["router"]["kernel"].shape[1],), jnp.float32)


def block(x, p, bias):
    """The one part ``p`` carries under its norm, ``x + part(RMSNorm(x))``."""
    y = rms_norm(x, p["RMSNorm_0"])
    if "conv" in p:
        return x + short_conv(y, p["conv"])
    if "attn" in p:
        return x + attention(y, p["attn"])
    if "mlp_gate" in p:
        return x + swiglu(
            y, *(p[f"mlp_{name}"]["kernel"] for name in ("gate", "up", "down"))
        )
    return x + experts(y, p["moe"], bias)


# ---- the stack and its loss ------------------------------------------------------


def token_losses(x, embedding, labels):
    """``logsumexp(logits) - logits[label]`` at every position, the logits
    over the embedding's rows (the vocabulary slice): the head is tied."""
    seq = x.shape[1]
    rows = block_rows(seq)

    def rows_from(start):
        logits = jax.lax.dynamic_slice_in_dim(x, start, rows, axis=1) @ embedding.T
        wanted = jax.lax.dynamic_slice_in_dim(labels, start, rows, axis=1)
        picked = jnp.take_along_axis(logits, wanted[..., None], axis=-1)[..., 0]
        return jax.nn.logsumexp(logits, axis=-1) - picked

    blocks = jax.lax.map(jax.checkpoint(rows_from), jnp.arange(0, seq, rows))
    return jnp.moveaxis(blocks, 0, 1).reshape(labels.shape)


def loss_fn(params, tokens, labels, buffers=None):
    embedding = params["tok_embed"]["embedding"]
    x = embedding[tokens]
    for index in range(sum(name.startswith("block_") for name in params)):
        name = f"block_{index}"
        p = params[name]
        bias = selection_bias(buffers, name, p["moe"]) if "moe" in p else None
        x = jax.checkpoint(block)(x, p, bias)
    x = rms_norm(x, params["RMSNorm_0"])
    return jnp.mean(token_losses(x, embedding, labels))


def loss_and_grads(params, features, labels, router_stats=None):
    """``(loss, grads)``; ``grads`` has the tree of ``params``.  Depth, the
    kind of each part, widths, heads, the taps, the experts held and the
    vocabulary slice are the parameter tree's own shapes.  ``router_stats``
    is the program's collection of that name, for the routers' selection
    biases."""
    params = jax.tree_util.tree_map(lambda p: jnp.asarray(p, jnp.float32), params)
    tokens = jnp.asarray(features["tokens"], jnp.int32)
    labels = jnp.asarray(labels, jnp.int32)
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(loss_fn)(params, tokens, labels, router_stats)
