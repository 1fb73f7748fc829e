"""Plain float32 reference of Qwen3-Next (``Qwen/Qwen3-Next-80B-A3B-Instruct``
``config.json``, ``model_type: qwen3_next``), as
``configs/qwen3_next_80b_a3b.json`` describes it: loss and gradient of one
batch.

A layer is ``h' = h + Mixer(RMSNorm(h))`` then ``h'' = h' + MoE(RMSNorm(h'))``;
the zoo model writes it as two one-part blocks (``d`` or ``*`` then ``E`` of
its ``layer_pattern``), each ``x + part(norm(x))``, so a block here is read by
the key its parameters carry:

``gdn``, the Gated DeltaNet mixer: one projection gives ``q, k``
(``LINEAR_KEY_HEADS`` heads of ``LINEAR_KEY_DIM``) and ``v, z`` (value heads
of the width of the part's norm scale), a second ``b, a``; a causal depthwise
convolution without bias, then SiLU, over ``[q | k | v]``; ``q`` and ``k``
L2-normalised a head (``x / sqrt(sum x^2 + 1e-6)``), a key head serving
``values / keys`` value heads in turn (``repeat_interleave``), ``q`` over
``sqrt(dk)``; ``beta = sigmoid(b)``, ``g = -exp(A_log) softplus(a +
dt_bias)``; the recurrence ONE STEP AT A TIME, a ``lax.scan`` over tokens::

    S_t = exp(g_t) S_{t-1} + beta_t k_t (v_t - (exp(g_t) S_{t-1})^T k_t)^T
    o_t = S_t^T q_t

then ``RMSNorm(o_t) * w * silu(z_t)`` a head, the heads joined, the output
projection.

``attn``, the gated softmax mixer: grouped-query attention with an RMSNorm a
head on q and k, rotary positions (rotate-half, ``ROPE_THETA``) on the first
``ROTARY_DIM`` lanes of a head, the others passing through, every earlier key,
the heads' output times ``sigmoid(gate(x))`` before the output projection.

``moe``: a softmax router over all the experts, the ``EXPERTS_PER_TOKEN``
largest renormalised, SwiGLU experts, and one shared SwiGLU expert on every
token times ``sigmoid(x w_g)``.

Then a final RMSNorm, an untied head without bias, the mean next-token
cross-entropy.

Everything is ``jax.numpy`` in float32 under
``default_matmul_precision("highest")``; no kernel, no chunk, no solve, no
sort, no grouped matmul; nothing of the program is imported: the parameter
tree is read by its leaf names.  What the tree does not carry, the numbers
below, is the published configuration's.

The chip's share.  The expert stacks hold ``w_up.shape[0]`` of the router's
experts, those from ``FIRST_EXPERT`` on; the router is as wide as published,
and a pair routed to an expert that is not held adds nothing, here as in the
program: that partial sum goes on.  The shared expert is counted once
(``SHARED_EXPERT_SHARE`` 1: of the chips that share a layer, one adds it).
The head's rows are the vocabulary slice's.  The routing is a constant of the
step (``ROUTER_TRAINS``, the configuration's ``router_trains`` false).

Memory, not mathematics: the recurrence runs over blocks of ``SCAN_BLOCK``
steps, attention is materialised over blocks of ``QUERY_BLOCK`` query rows
against the whole context, the head and its loss run over the same blocks,
the experts run as a loop over the held ones, and each block, each expert and
each layer is recomputed in the backward pass (``jax.checkpoint``)."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

QUERY_BLOCK = 256
SCAN_BLOCK = 256
# config.json: rms_norm_eps, linear_num_key_heads, linear_key_head_dim,
# rope_theta, partial_rotary_factor x head_dim, num_experts_per_tok,
# norm_topk_prob
RMS_NORM_EPS = 1e-6
LINEAR_KEY_HEADS = 16
LINEAR_KEY_DIM = 128
ROPE_THETA = 10000000.0
ROTARY_DIM = 64
EXPERTS_PER_TOKEN = 10
NORM_TOPK_PROB = True
L2_EPS = 1e-6
# the configuration's router_trains: this cut does not differentiate its routing
ROUTER_TRAINS = False
# the first expert this chip holds (``deployment`` in the configuration)
FIRST_EXPERT = 0
# how much of the shared expert this share of the layer adds
SHARED_EXPERT_SHARE = 1.0


def rms_norm(x, p):
    variance = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(variance + RMS_NORM_EPS) * p["scale"]


def block_rows(seq: int, rows: int) -> int:
    """``rows`` where it divides the context, else the whole context."""
    return rows if seq % rows == 0 else seq


# ---- the Gated DeltaNet mixer ---------------------------------------------------


def causal_conv_silu(x, taps):
    """``silu`` of a depthwise convolution along time that sees the present
    and the ``k - 1`` steps before it: ``x`` (batch, T, channels), ``taps``
    (k, channels), no bias."""
    count, steps = taps.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (count - 1, 0), (0, 0)))
    out = sum(padded[:, tap:tap + steps] * taps[tap] for tap in range(count))
    return jax.nn.silu(out)


def l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), -1, keepdims=True) + L2_EPS)


def delta_step(state, at):
    """One token: the state decays, the value the state does not yet give
    for this key is written at strength ``beta``, the query reads."""
    q_t, k_t, v_t, g_t, beta_t = at
    state = jnp.exp(g_t)[..., None, None] * state
    missing = v_t - jnp.einsum("bhkv,bhk->bhv", state, k_t)
    state = state + jnp.einsum("bh,bhk,bhv->bhkv", beta_t, k_t, missing)
    return state, jnp.einsum("bhkv,bhk->bhv", state, q_t)


def delta_rule(q, k, v, g, beta):
    """The recurrence a step at a time.  ``q``, ``k``, ``v`` (batch, T, H,
    d), ``g``, ``beta`` (batch, T, H); the state (batch, H, dk, dv) starts
    at zero.  departure: the zoo runs the chunked form (a triangular solve a
    chunk of 64 steps) in two compiled kernels (``ops/gated_delta.py``), which
    never hold a state a token."""
    batch, steps, heads, dk = k.shape
    rows = block_rows(steps, SCAN_BLOCK)

    @jax.checkpoint
    def rows_from(state, of_block):
        return jax.lax.scan(delta_step, state, of_block)

    def by_block(x):  # (batch, T, ...) -> (blocks, rows, batch, ...)
        x = jnp.moveaxis(x, 1, 0)
        return x.reshape(steps // rows, rows, *x.shape[1:])

    start = jnp.zeros((batch, heads, dk, v.shape[-1]), jnp.float32)
    _, out = jax.lax.scan(
        rows_from, start, tuple(by_block(x) for x in (q, k, v, g, beta))
    )
    return jnp.moveaxis(out.reshape(steps, *out.shape[2:]), 0, 1)


def gated_norm(o, z, scale):
    """``RMSNorm(o) * scale * silu(z)`` over a head: the norm before the
    gate."""
    variance = jnp.mean(jnp.square(o), axis=-1, keepdims=True)
    return o * jax.lax.rsqrt(variance + RMS_NORM_EPS) * scale * jax.nn.silu(z)


def gated_delta_net(x, p):
    batch, steps, _ = x.shape
    dv = p["norm_scale"].shape[0]
    values = p["A_log"].shape[0]
    keys, dk = LINEAR_KEY_HEADS, LINEAR_KEY_DIM
    mixed = x @ p["in_proj_qkvz"]["kernel"]
    ba = x @ p["in_proj_ba"]["kernel"]
    inner = 2 * keys * dk + values * dv
    convolved = causal_conv_silu(mixed[..., :inner], p["conv_kernel"])
    z = mixed[..., inner:].reshape(batch, steps, values, dv)
    q = convolved[..., : keys * dk].reshape(batch, steps, keys, dk)
    k = convolved[..., keys * dk : 2 * keys * dk].reshape(batch, steps, keys, dk)
    v = convolved[..., 2 * keys * dk :].reshape(batch, steps, values, dv)
    q, k = (
        jnp.repeat(l2norm(heads), values // keys, axis=2) for heads in (q, k)
    )
    q = q / math.sqrt(dk)
    beta = jax.nn.sigmoid(ba[..., :values])
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(ba[..., values:] + p["dt_bias"])
    y = gated_norm(delta_rule(q, k, v, g, beta), z, p["norm_scale"])
    return y.reshape(batch, steps, values * dv) @ p["out_proj"]["kernel"]


# ---- the gated softmax mixer ------------------------------------------------------


def rotate_half(x):
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([-x2, x1], axis=-1)


def rotary(x):
    """HF ``apply_rotary_pos_emb`` under ``partial_rotary_factor``: the first
    ``ROTARY_DIM`` lanes of a head turn (frequency ``i`` of ``ROTARY_DIM / 2``
    the pair ``(x_i, x_{i + ROTARY_DIM/2})`` by ``t * theta^(-2i /
    ROTARY_DIM)``), the others pass through."""
    steps = x.shape[1]
    turning, passing = x[..., :ROTARY_DIM], x[..., ROTARY_DIM:]
    inv_freq = 1.0 / ROPE_THETA ** (
        jnp.arange(0, ROTARY_DIM, 2, dtype=jnp.float32) / ROTARY_DIM
    )
    freqs = jnp.arange(steps, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    emb = jnp.concatenate([freqs, freqs], axis=-1)[None, :, None, :]
    turned = turning * jnp.cos(emb) + rotate_half(turning) * jnp.sin(emb)
    return jnp.concatenate([turned, passing], axis=-1)


def causal_attention(q, k, v):
    """``softmax(q k^T / sqrt(d)) v`` over every earlier key and the query's
    own; ``k`` and ``v`` carry a head a group of query heads.  departure: the
    zoo runs flash kernels (``ops/attention.py``), which never hold the score
    matrix."""
    seq, d = q.shape[1], q.shape[-1]
    group = q.shape[2] // k.shape[2]
    k, v = (jnp.repeat(x, group, axis=2) for x in (k, v))
    rows = block_rows(seq, QUERY_BLOCK)
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
    u = u * jax.nn.sigmoid(projected("gate"))
    return jnp.einsum("bshd,hde->bse", u, a["out"]["kernel"])


# ---- the experts ------------------------------------------------------------------


def swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def route(tokens, m):
    """The weight of every expert for every token (zero where the expert was
    not chosen): softmax over all of them, the ``EXPERTS_PER_TOKEN`` largest,
    over their sum (``norm_topk_prob``)."""
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


def shared_expert(tokens, m):
    y = swiglu(
        tokens, m["shared_gate"]["kernel"], m["shared_up"]["kernel"],
        m["shared_down"]["kernel"],
    )
    return y * jax.nn.sigmoid(tokens @ m["shared_expert_gate"]["kernel"])


def experts(x, m):
    """``sum_e weight[:, e] * SwiGLU_e(x)`` over the experts held here, and
    this share of the shared expert."""
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
    if SHARED_EXPERT_SHARE:
        y = y + SHARED_EXPERT_SHARE * shared_expert(tokens, m)
    return y.reshape(x.shape)


def block(x, p):
    """The one part ``p`` carries under its norm, ``x + part(RMSNorm(x))``."""
    y = rms_norm(x, p["RMSNorm_0"])
    if "gdn" in p:
        return x + gated_delta_net(y, p["gdn"])
    if "attn" in p:
        return x + attention(y, p["attn"])
    return x + experts(y, p["moe"])


# ---- the stack and its loss ------------------------------------------------------


def token_losses(x, head, labels):
    """``logsumexp(logits) - logits[label]`` at every position, over the
    head's rows (the vocabulary slice); the head is untied, without bias."""
    seq = x.shape[1]
    rows = block_rows(seq, QUERY_BLOCK)

    def rows_from(start):
        logits = jax.lax.dynamic_slice_in_dim(x, start, rows, axis=1) @ head["kernel"]
        wanted = jax.lax.dynamic_slice_in_dim(labels, start, rows, axis=1)
        picked = jnp.take_along_axis(logits, wanted[..., None], axis=-1)[..., 0]
        return jax.nn.logsumexp(logits, axis=-1) - picked

    blocks = jax.lax.map(jax.checkpoint(rows_from), jnp.arange(0, seq, rows))
    return jnp.moveaxis(blocks, 0, 1).reshape(labels.shape)


def loss_fn(params, tokens, labels):
    x = params["tok_embed"]["embedding"][tokens]
    for index in range(sum(name.startswith("block_") for name in params)):
        x = jax.checkpoint(block)(x, params[f"block_{index}"])
    x = rms_norm(x, params["RMSNorm_0"])
    return jnp.mean(token_losses(x, params["lm_head"], labels))


def loss_and_grads(params, features, labels):
    """``(loss, grads)``; ``grads`` has the tree of ``params``.  Depth, the
    kind of each part, widths, value heads, the experts held and the
    vocabulary slice are the parameter tree's own shapes."""
    params = jax.tree_util.tree_map(lambda p: jnp.asarray(p, jnp.float32), params)
    tokens = jnp.asarray(features["tokens"], jnp.int32)
    labels = jnp.asarray(labels, jnp.int32)
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(loss_fn)(params, tokens, labels)
