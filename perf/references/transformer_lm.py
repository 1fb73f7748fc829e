"""Plain float32 reference of the zoo's causal transformer LM, as
``configs/gpt2_small.json`` describes it: loss and gradient of one batch.

It follows GPT-2 (Radford et al. 2019; ``openai-community/gpt2``
``config.json``): token embedding, 12 pre-LayerNorm blocks of causal
multi-head attention and a 4x GELU (tanh form, ``gelu_new``) MLP, a final
LayerNorm, a linear head, the mean next-token cross-entropy.  Where the zoo
model departs from GPT-2 the reference follows the zoo, and the line that
does says so.  Everything is ``jax.numpy`` in float32 under
``default_matmul_precision("highest")``; no kernel, no cache; nothing of
the program is imported: the parameter tree is read by its leaf names.

Memory, not mathematics: attention is materialised ``softmax(QK^T/sqrt(d))V``
over blocks of ``QUERY_BLOCK`` query rows against the whole context, the head
and its loss run over the same blocks of rows, and each block of rows and
each layer is recomputed in the backward pass (``jax.checkpoint``), so that a
sample of 8,192 tokens holds no 8,192^2 x 12 score tensor, no 8,192 x 50,257
logits and one layer's activations at a time; the layers run as a loop
(``jax.lax.scan``) over their stacked parameters, one compiled block for all."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

QUERY_BLOCK = 512
# departure: flax's LayerNorm default, which the zoo's blocks take; GPT-2's
# config.json says 1e-5
LAYER_NORM_EPSILON = 1e-6


def layer_norm(x, p):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)  # biased
    return (x - mean) / jnp.sqrt(var + LAYER_NORM_EPSILON) * p["scale"] + p["bias"]


def sinusoidal_positions(seq_len: int, dim: int):
    """Vaswani et al. 2017, section 3.5.  departure: GPT-2 learns a table of
    1,024 positions; the zoo adds this fixed encoding, sines in the even
    columns and cosines in the odd ones, so no table limits the context."""
    pos = jnp.arange(seq_len, dtype=jnp.float32)[:, None]
    rate = jnp.exp(
        jnp.arange(0, dim, 2, dtype=jnp.float32) * (-math.log(10000.0) / dim)
    )
    angles = pos * rate
    return jnp.stack([jnp.sin(angles), jnp.cos(angles)], axis=-1).reshape(
        seq_len, dim
    )


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


def dense(x, p):
    return x @ p["kernel"] + p["bias"]


def block(x, p):
    """One pre-LayerNorm block.  departure: "the zoo's TransformerBlock, not
    GPT-2's exact block": separate query, key and value projections with
    kernels of shape (embed, heads, d) where GPT-2 has one fused ``c_attn``;
    the mathematics is the same."""
    a = p["attn"]
    y = layer_norm(x, p["LayerNorm_0"])
    q, k, v = (
        jnp.einsum("bse,ehd->bshd", y, a[name]["kernel"]) + a[name]["bias"]
        for name in ("query", "key", "value")
    )
    y = causal_attention(q, k, v)
    x = x + jnp.einsum("bshd,hde->bse", y, a["out"]["kernel"]) + a["out"]["bias"]
    y = layer_norm(x, p["LayerNorm_1"])
    y = jax.nn.gelu(dense(y, p["mlp_up"]), approximate=True)  # gelu_new
    return x + dense(y, p["mlp_down"])


def next_token_loss(x, head, labels):
    """Mean over every position of ``logsumexp(logits) - logits[label]``.
    departure: an untied head with a bias (163M parameters); GPT-2 reuses
    the embedding matrix and has no bias."""
    seq = x.shape[1]
    rows = block_rows(seq)

    def rows_from(start):
        logits = dense(jax.lax.dynamic_slice_in_dim(x, start, rows, axis=1), head)
        wanted = jax.lax.dynamic_slice_in_dim(labels, start, rows, axis=1)
        picked = jnp.take_along_axis(logits, wanted[..., None], axis=-1)[..., 0]
        return jnp.sum(jax.nn.logsumexp(logits, axis=-1) - picked)

    sums = jax.lax.map(jax.checkpoint(rows_from), jnp.arange(0, seq, rows))
    return jnp.sum(sums) / labels.size


def loss_fn(params, tokens, labels):
    x = params["tok_embed"]["embedding"][tokens]
    x = x + sinusoidal_positions(tokens.shape[1], x.shape[-1])[None]
    layers = sum(name.startswith("block_") for name in params)
    # program size, not mathematics: one compiled block for every layer, run
    # as a loop over the layers' parameters stacked (unrolled, the compiled
    # reference was 600 MB)
    stacked = jax.tree_util.tree_map(
        lambda *leaves: jnp.stack(leaves),
        *(params[f"block_{layer}"] for layer in range(layers)),
    )
    x, _ = jax.lax.scan(lambda x, p: (jax.checkpoint(block)(x, p), None), x, stacked)
    x = layer_norm(x, params["LayerNorm_0"])
    return next_token_loss(x, params["lm_head"], labels)


def loss_and_grads(params, features, labels):
    """``(loss, grads)`` of the mean next-token cross-entropy; ``grads`` has
    the tree of ``params``.  Depth, width and heads are the parameter tree's
    own shapes."""
    params = jax.tree_util.tree_map(lambda p: jnp.asarray(p, jnp.float32), params)
    tokens = jnp.asarray(features["tokens"], jnp.int32)
    labels = jnp.asarray(labels, jnp.int32)
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(loss_fn)(params, tokens, labels)
