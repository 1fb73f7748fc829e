"""Plain float32 reference of OLMoE (Muennighoff et al., arXiv:2409.02060;
``allenai/OLMoE-1B-7B-0125-Instruct`` ``config.json``), as
``configs/olmoe_1b7b.json`` describes it: loss and gradient of one batch.

It follows HF ``modeling_olmoe.py``: token embedding; pre-RMSNorm blocks of
causal multi-head attention — no bias, an RMSNorm with a learned scale over
the whole q and k projections before the split into heads, rotary positions
in the rotate-half convention — and a sparse expert block (softmax over all
experts in float32, the ``EXPERTS_PER_TOKEN`` largest, their probabilities
unnormalised as weights, SwiGLU experts); a final RMSNorm, an untied linear
head, the mean next-token cross-entropy plus the load-balance loss and the
router z-loss.  Where the zoo model departs from HF's file the reference
follows the zoo, and the line that does says so.  Everything is
``jax.numpy`` in float32 under ``default_matmul_precision("highest")``; no
kernel, no sort, no grouped matmul; nothing of the program is imported: the
parameter tree is read by its leaf names.  What the tree does not carry —
the numbers below — is the published configuration's.

Memory, not mathematics: attention is materialised over blocks of
``QUERY_BLOCK`` query rows against the whole context, the head and its loss
run over the same blocks, the experts run as a loop (``jax.lax.scan``) over
their stacked weights, each applied to every row and masked to the rows
that chose it, and each block of rows, each expert and each layer is
recomputed in the backward pass (``jax.checkpoint``)."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

QUERY_BLOCK = 512
# config.json: num_experts_per_tok, norm_topk_prob, rms_norm_eps, rope_theta
EXPERTS_PER_TOKEN = 8
NORM_TOPK_PROB = False
RMS_NORM_EPS = 1e-5
ROPE_THETA = 10000.0
# the paper's section 3 (not in config.json; ``assumed`` in the configuration)
LOAD_BALANCE_WEIGHT = 0.01
ROUTER_Z_WEIGHT = 0.001


def rms_norm(x, p):
    variance = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(variance + RMS_NORM_EPS) * p["scale"]


def rotate_half(x):
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([-x2, x1], axis=-1)


def rotary(x):
    """``apply_rotary_pos_emb``: ``x`` (batch, seq, heads, d)."""
    seq, d = x.shape[1], x.shape[3]
    inv_freq = 1.0 / (ROPE_THETA ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    freqs = jnp.arange(seq, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    emb = jnp.concatenate([freqs, freqs], axis=-1)[None, :, None, :]
    return x * jnp.cos(emb) + rotate_half(x) * jnp.sin(emb)


def block_rows(seq: int) -> int:
    """Rows of a block: ``QUERY_BLOCK`` where it divides the context, else
    the whole context at once."""
    return QUERY_BLOCK if seq % QUERY_BLOCK == 0 else seq


def visible(rows, columns):
    """The causal mask: query row ``i`` sees key columns ``0..i``."""
    return rows[:, None] >= columns[None, :]


def causal_attention(q, k, v):
    """``softmax(QK^T / sqrt(d)) V`` with the causal mask; ``(batch, seq,
    heads, d)`` each.  departure: the zoo runs Pallas flash kernels
    (``ops/attention.py``), which never hold the score matrix."""
    seq, d = q.shape[1], q.shape[3]
    rows = block_rows(seq)
    columns = jnp.arange(seq)

    def rows_from(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, rows, axis=1)
        scores = jnp.einsum("bqhd,bkhd->bhqk", qb, k) / math.sqrt(d)
        seen = visible(start + jnp.arange(rows), columns)
        scores = jnp.where(seen[None, None], scores, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v)

    blocks = jax.lax.map(jax.checkpoint(rows_from), jnp.arange(0, seq, rows))
    return jnp.moveaxis(blocks, 0, 1).reshape(q.shape)


def attention(x, a):
    """``OlmoeAttention``: no bias, ``clip_qkv`` null.  The zoo keeps each
    projection's kernel as (embed, heads, d); the norm runs over heads * d."""
    def projected(name):
        return jnp.einsum("bse,ehd->bshd", x, a[name]["kernel"])

    def normed(y, p):  # q_norm(q_proj(x)): over the whole width, then heads
        flat = y.reshape(*y.shape[:2], -1)
        return rms_norm(flat, p).reshape(y.shape)

    q = rotary(normed(projected("query"), a["q_norm"]))
    k = rotary(normed(projected("key"), a["k_norm"]))
    y = causal_attention(q, k, projected("value"))
    return jnp.einsum("bshd,hde->bse", y, a["out"]["kernel"])


def route(x, m):
    """``OlmoeSparseMoeBlock``'s router: the weight of every expert for
    every token (zero where the expert was not chosen), and the two
    auxiliary losses of this layer."""
    experts = m["router"]["kernel"].shape[1]
    logits = x @ m["router"]["kernel"]
    probs = jax.nn.softmax(logits, axis=-1)
    top, chosen = jax.lax.top_k(probs, EXPERTS_PER_TOKEN)
    if NORM_TOPK_PROB:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    one_hot = jax.nn.one_hot(chosen, experts, dtype=x.dtype)  # (tokens, k, E)
    weight = jnp.einsum("tk,tke->te", top, one_hot)
    # load_balancing_loss_func: E * sum_e (pairs to e / tokens) * mean_t p[t, e]
    fraction = jnp.sum(one_hot, axis=(0, 1)) / x.shape[0]
    balance = experts * jnp.sum(fraction * jnp.mean(probs, axis=0))
    # the paper's router z-loss; HF's file does not have it, the zoo does
    z = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))
    return weight, balance, z


def experts(x, m):
    """``y = sum_e weight[:, e] * down_e(silu(gate_e(x)) * up_e(x))``: HF
    gathers the rows that chose an expert; here every expert sees every row
    and the rows that did not choose it weigh zero (static shapes)."""
    tokens = x.reshape(-1, x.shape[-1])
    weight, balance, z = route(tokens, m)

    def one(weights_of_expert, stacks):
        gate, up, down = stacks
        hidden = jax.nn.silu(tokens @ gate) * (tokens @ up)
        return (hidden @ down) * weights_of_expert[:, None]

    def add(y, per_expert):
        return y + jax.checkpoint(one)(*per_expert), None

    y, _ = jax.lax.scan(
        add, jnp.zeros_like(tokens),
        (weight.T, (m["w_gate"], m["w_up"], m["w_down"])),
    )
    return y.reshape(x.shape), balance, z


def block(x, p):
    """``OlmoeDecoderLayer``: pre-norm residual attention, pre-norm residual
    experts."""
    x = x + attention(rms_norm(x, p["RMSNorm_0"]), p["attn"])
    y, balance, z = experts(rms_norm(x, p["RMSNorm_1"]), p["moe"])
    return x + y, balance, z


def next_token_loss(x, head, labels):
    """Mean over every position of ``logsumexp(logits) - logits[label]``;
    the head is untied and has no bias (``tie_word_embeddings`` false)."""
    seq = x.shape[1]
    rows = block_rows(seq)

    def rows_from(start):
        logits = jax.lax.dynamic_slice_in_dim(x, start, rows, axis=1) @ head["kernel"]
        wanted = jax.lax.dynamic_slice_in_dim(labels, start, rows, axis=1)
        picked = jnp.take_along_axis(logits, wanted[..., None], axis=-1)[..., 0]
        return jnp.sum(jax.nn.logsumexp(logits, axis=-1) - picked)

    sums = jax.lax.map(jax.checkpoint(rows_from), jnp.arange(0, seq, rows))
    return jnp.sum(sums) / labels.size


def loss_fn(params, tokens, labels):
    x = params["tok_embed"]["embedding"][tokens]
    layers = sum(name.startswith("block_") for name in params)
    stacked = jax.tree_util.tree_map(
        lambda *leaves: jnp.stack(leaves),
        *(params[f"block_{layer}"] for layer in range(layers)),
    )

    def layer(x, p):
        x, balance, z = jax.checkpoint(block)(x, p)
        return x, (balance, z)

    x, (balance, z) = jax.lax.scan(layer, x, stacked)
    x = rms_norm(x, params["RMSNorm_0"])
    # departure: HF computes one load-balance loss over the layers' router
    # outputs concatenated; the zoo sows one a layer and takes their mean
    # (the same at depth 1, which is what the benchmark runs)
    return (
        next_token_loss(x, params["lm_head"], labels)
        + LOAD_BALANCE_WEIGHT * jnp.mean(balance)
        + ROUTER_Z_WEIGHT * jnp.mean(z)
    )


def loss_and_grads(params, features, labels):
    """``(loss, grads)``; ``grads`` has the tree of ``params``.  Depth,
    width, heads, experts and their width are the parameter tree's own
    shapes."""
    params = jax.tree_util.tree_map(lambda p: jnp.asarray(p, jnp.float32), params)
    tokens = jnp.asarray(features["tokens"], jnp.int32)
    labels = jnp.asarray(labels, jnp.int32)
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(loss_fn)(params, tokens, labels)
