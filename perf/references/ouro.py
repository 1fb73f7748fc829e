"""Plain float32 reference of Ouro (``ByteDance/Ouro-2.6B`` ``config.json``,
``model_type: ouro``; "Scaling Latent Reasoning via Looped Language Models",
arXiv:2510.25741), as ``configs/ouro_2p6b.json`` describes it: loss and
gradient of one batch.

A layer, its weights shared by all passes: ``a = x + N2(Attn(N1(x)))``, ``y =
a + N4(MLP(N3(a)))``, four RMSNorms a layer (the published
``input_layernorm``, ``input_layernorm_2``, ``post_attention_layernorm``,
``post_attention_layernorm_2``: ``RMSNorm_0`` .. ``RMSNorm_3`` of a block
here); ``Attn``: causal heads with rotary positions by halves over the whole
head, no bias; ``MLP(u) = W_down(silu(W_gate u) * W_up u)``.  The loop: ``h_0
= Emb(tokens)``; for ``t = 1 .. TOTAL_UT_STEPS``: ``h_t = N_f(Stack(h_{t-1}))``
(the next pass reads the normed state), ``logits_t = W_head h_t``, ``g_t =
sigmoid((w_g . h_t + b_g) / sqrt(width))`` (one gate for all passes; the
tree holds the published ``Linear``'s weights times ``sqrt(width)``).  The exit distribution
a token: ``p_t = g_t prod_{j<t} (1 - g_j)``, the last pass taking what is
left, ``prod_{j<T} (1 - g_j)``.  The loss (the paper's stage I): the mean over
tokens of ``sum_t p_t CE_t - EXIT_ENTROPY_WEIGHT * H(p)``, ``CE_t`` the next
token's cross-entropy under ``logits_t`` and ``H(p) = -sum_t p_t log p_t``.

Everything is ``jax.numpy`` in float32 under
``default_matmul_precision("highest")``; the scores are materialised under a
mask; the passes and the layers are Python loops, so a shared weight's
gradient is the sum autodiff makes over its uses; no kernel, no ``scan`` over
a pass or a layer; nothing of the program is imported: the parameter tree is
read by its leaf names.  What the tree does not carry, the numbers below, is
the published configuration's (and ``assumed`` in the configuration's file).

Memory, not mathematics: attention is materialised over blocks of
``QUERY_BLOCK`` query rows against the whole context, a pass's head and
cross-entropy run over the same blocks, and each block of rows and each
application of a layer is recomputed in the backward pass
(``jax.checkpoint``), so that a sample of 2 x 4,096 tokens holds no 4,096^2 x
16 score tensor a layer and no 8,192 x 49,152 logits a pass."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

QUERY_BLOCK = 512
# config.json: rms_norm_eps, rope_theta (rope_scaling null), total_ut_steps
RMS_NORM_EPS = 1e-6
ROPE_THETA = 1e6
TOTAL_UT_STEPS = 4
# assumed (the configuration's file, ``run.model_params.exit_entropy_weight``,
# which tests/perf/test_perf_ouro.py holds this equal to: a reference is
# handed no configuration and imports nothing to read one with): the paper's
# entropy weight, stage I
EXIT_ENTROPY_WEIGHT = 0.1


def rms_norm(x, p):
    variance = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(variance + RMS_NORM_EPS) * p["scale"]


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
    """``softmax(q k^T / sqrt(d)) v``, query ``t`` reading keys ``0 .. t``;
    ``(batch, seq, heads, d)`` each.  departure: the zoo runs Pallas flash
    kernels (``ops/attention.py``), which never hold the score matrix."""
    seq, d = q.shape[1], q.shape[-1]
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
    q, k, v = (
        jnp.einsum("bse,ehd->bshd", x, a[name]["kernel"])
        for name in ("query", "key", "value")
    )
    u = causal_attention(rotary(q), rotary(k), v)
    return jnp.einsum("bshd,hde->bse", u, a["out"]["kernel"])


def swiglu(x, p):
    gate, up, down = (p[f"mlp_{name}"]["kernel"] for name in ("gate", "up", "down"))
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def layer(x, p):
    """One layer under its four norms."""
    a = x + rms_norm(attention(rms_norm(x, p["RMSNorm_0"]), p["attn"]), p["RMSNorm_1"])
    return a + rms_norm(swiglu(rms_norm(a, p["RMSNorm_2"]), p), p["RMSNorm_3"])


def token_losses(x, head, labels):
    """``logsumexp(logits) - logits[label]`` at every position; the head is
    untied, without bias."""
    seq = x.shape[1]
    rows = block_rows(seq)

    def rows_from(start):
        logits = jax.lax.dynamic_slice_in_dim(x, start, rows, axis=1) @ head["kernel"]
        wanted = jax.lax.dynamic_slice_in_dim(labels, start, rows, axis=1)
        picked = jnp.take_along_axis(logits, wanted[..., None], axis=-1)[..., 0]
        return jax.nn.logsumexp(logits, axis=-1) - picked

    blocks = jax.lax.map(jax.checkpoint(rows_from), jnp.arange(0, seq, rows))
    return jnp.moveaxis(blocks, 0, 1).reshape(labels.shape)


def exit_gate(h, gate):
    """The probability of leaving after this pass, a token."""
    logit = (h @ gate["kernel"])[..., 0] + gate["bias"][0]
    return jax.nn.sigmoid(logit / math.sqrt(h.shape[-1]))


def exits(params, tokens):
    """``[(h_t, g_t) ...]``, a pass each: the normed state and the exit
    gate's probability a token."""
    depth = sum(name.startswith("block_") for name in params)
    h = params["tok_embed"]["embedding"][tokens]
    reached = []
    for _ in range(TOTAL_UT_STEPS):
        for index in range(depth):
            h = jax.checkpoint(layer)(h, params[f"block_{index}"])
        h = rms_norm(h, params["RMSNorm_0"])
        reached.append((h, exit_gate(h, params["exit_gate"])))
    return reached


def exit_distribution(gates):
    """``[p_1 .. p_T]`` from ``[g_1 .. g_T]`` (``g_T`` is not read)."""
    stayed, p = 1.0, []
    for g in gates[:-1]:
        p.append(g * stayed)
        stayed = stayed * (1.0 - g)
    return p + [stayed]


def loss_fn(params, tokens, labels):
    reached = exits(params, tokens)
    p = exit_distribution([g for _, g in reached])
    # (a loop over the passes, not four calls: the head's gradient is summed
    # in the loop's one accumulator and not held a pass, 0.4 GB each)
    cross_entropy = jax.lax.map(
        lambda h: token_losses(h, params["lm_head"], labels),
        jnp.stack([h for h, _ in reached]),
    )
    expected = sum(p_t * ce_t for p_t, ce_t in zip(p, cross_entropy))
    # (x log x, 0 at 0)
    entropy = -sum(jax.scipy.special.xlogy(p_t, p_t) for p_t in p)
    return jnp.mean(expected - EXIT_ENTROPY_WEIGHT * entropy)


def loss_and_grads(params, features, labels):
    """``(loss, grads)``; ``grads`` has the tree of ``params``.  Depth, widths
    and heads are the parameter tree's own shapes."""
    params = jax.tree_util.tree_map(lambda p: jnp.asarray(p, jnp.float32), params)
    tokens = jnp.asarray(features["tokens"], jnp.int32)
    labels = jnp.asarray(labels, jnp.int32)
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(loss_fn)(params, tokens, labels)
