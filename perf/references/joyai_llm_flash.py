"""Plain float32 reference of ``joyai_llm_flash`` (JoyAI-LLM-Flash 48B-A2.7B,
``configs/joyai_llm_flash_48b_a3b.json``): loss and gradient of one batch.

Its layers are DeepSeek-V3's (arXiv:2412.19437, sections 2.1 and 2.2).  A
layer is ``x + Attn(RMSNorm(x))`` then ``x + FFN(RMSNorm(x))``; the zoo
model writes it as two one-part blocks (``*`` then ``-`` or ``E`` of its
``layer_pattern``) and the multi-token-prediction module's layer as one
two-part block, so a block here is read by the keys its parameters carry:
``attn`` (multi-head latent attention), ``mlp_gate`` (the leading dense
SwiGLU layer), ``moe`` (sigmoid-routed SwiGLU experts and a shared expert).
Then a final RMSNorm, an untied head without bias, the mean next-token
cross-entropy, and the second-token loss of one multi-token-prediction
module that shares the embedding and the head.  Where the zoo model leaves
the paper or HF's DeepSeek-V3 modelling file the reference follows the zoo,
and the line that does says so.  Everything is ``jax.numpy`` in
float32 under ``default_matmul_precision("highest")``; no kernel, no sort, no
grouped matmul; nothing of the program is imported: the parameter tree is
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
# config.json: rms_norm_eps, rope_theta (rope_scaling null),
# num_experts_per_tok, norm_topk_prob, routed_scaling_factor (n_group =
# topk_group = 1: no limit on the groups a token's experts come from),
# n_shared_experts 1
RMS_NORM_EPS = 1e-6
ROPE_THETA = 3.2e7
EXPERTS_PER_TOKEN = 8
NORM_TOPK_PROB = True
ROUTED_SCALING = 2.5
# the first expert this chip holds (``deployment`` in the configuration)
FIRST_EXPERT = 0
# not in config.json (``assumed``): the paper's lambda for its first 10 T tokens
MTP_WEIGHT = 0.3


def rms_norm(x, p):
    variance = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(variance + RMS_NORM_EPS) * p["scale"]


# ---- multi-head latent attention -----------------------------------------------


def rotate_pairs(x):
    """RoPE on the whole last axis of ``x`` (batch, T, heads, d) with
    ``rope_interleave``: the adjacent pair ``(x_2i, x_2i+1)`` of position
    ``t`` turns by ``t * theta^(-2i/d)``."""
    steps, d = x.shape[1], x.shape[-1]
    rate = ROPE_THETA ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(steps, dtype=jnp.float32)[:, None] * rate[None, :]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    turned = jnp.stack([even * cos - odd * sin, odd * cos + even * sin], axis=-1)
    return turned.reshape(x.shape)


def block_rows(seq: int) -> int:
    """Rows of a block: ``QUERY_BLOCK`` where it divides the context, else
    the whole context at once."""
    return QUERY_BLOCK if seq % QUERY_BLOCK == 0 else seq


def causal_attention(q, k, v):
    """``softmax(q k^T / sqrt(d_qk)) v`` with the causal mask; ``v`` is
    narrower than ``q`` and ``k``.  departure: the zoo runs Pallas flash
    kernels (``ops/attention.py``), which never hold the score matrix."""
    seq = q.shape[1]
    rows = block_rows(seq)
    columns = jnp.arange(seq)

    def rows_from(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, rows, axis=1)
        scores = jnp.einsum("bqhd,bkhd->bhqk", qb, k) / math.sqrt(q.shape[-1])
        seen = (start + jnp.arange(rows))[:, None] >= columns[None, :]
        scores = jnp.where(seen[None, None], scores, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v)

    blocks = jax.lax.map(jax.checkpoint(rows_from), jnp.arange(0, seq, rows))
    return jnp.moveaxis(blocks, 0, 1).reshape(*q.shape[:3], v.shape[-1])


def latent_attention(x, a):
    """DeepSeek-V3 eqs. 1-11 (HF ``DeepseekV3Attention`` in training): the
    query through a normed latent of ``q_lora_rank``, keys and values through
    a normed latent of ``kv_lora_rank``, ONE rotary key a token shared by all
    heads, RoPE on the rotary slices only.  ``kv_lora_rank`` is the latent
    norm's width, ``qk_rope_head_dim`` what ``kv_a`` gives beyond it,
    ``qk_nope_head_dim`` the rest of a query head."""
    rank = a["kv_a_norm"]["scale"].shape[0]
    nope = a["q_b"]["kernel"].shape[-1] - (a["kv_a"]["kernel"].shape[1] - rank)
    c_q = rms_norm(x @ a["q_a"]["kernel"], a["q_a_norm"])
    q = jnp.einsum("bsr,rhd->bshd", c_q, a["q_b"]["kernel"])
    latent = x @ a["kv_a"]["kernel"]
    c_kv = rms_norm(latent[..., :rank], a["kv_a_norm"])
    kv = jnp.einsum("bsr,rhd->bshd", c_kv, a["kv_b"]["kernel"])
    q_rot = rotate_pairs(q[..., nope:])
    k_rot = rotate_pairs(latent[..., None, rank:])
    q = jnp.concatenate([q[..., :nope], q_rot], axis=-1)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_rot, q_rot.shape)], axis=-1
    )
    y = causal_attention(q, k, kv[..., nope:])
    return jnp.einsum("bshd,hde->bse", y, a["out"]["kernel"])


# ---- feed-forward: the dense layer, the routed experts, the shared expert ----------


def swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def route(tokens, m, bias):
    """The weight of every expert for every token (zero where the expert was
    not chosen), over all the experts the router scores: ``scoring_func``
    sigmoid; ``topk_method`` noaux_tc with one group, the largest of
    ``scores + e_score_correction_bias``; the chosen experts' scores WITHOUT
    the bias, over their sum, times ``routed_scaling_factor``.  departure: HF
    divides by the sum plus 1e-20; the zoo and this file by the sum."""
    experts = m["router"]["kernel"].shape[1]
    scores = jax.nn.sigmoid(tokens @ m["router"]["kernel"])
    chosen = jax.lax.top_k(scores + jax.lax.stop_gradient(bias), EXPERTS_PER_TOKEN)[1]
    top = jnp.take_along_axis(scores, chosen, axis=-1)
    if NORM_TOPK_PROB:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    one_hot = jax.nn.one_hot(chosen, experts, dtype=tokens.dtype)
    return jnp.einsum("tk,tke->te", top * ROUTED_SCALING, one_hot)


def experts(x, m, bias):
    """``sum_e weight[:, e] * SwiGLU_e(x)`` over the experts held here plus
    the shared expert on every token."""
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
    """The router's ``e_score_correction_bias`` of block ``name``: a buffer
    the program keeps outside its parameters (collection ``router_stats``);
    zero where none is given, as at the seeded init."""
    try:
        return jnp.asarray(buffers[name]["moe"]["selection_bias"], jnp.float32)
    except (KeyError, TypeError):
        return jnp.zeros((m["router"]["kernel"].shape[1],), jnp.float32)


def block(x, p, bias):
    """The parts ``p`` carries, each ``x + part(RMSNorm(x))`` under the next
    of its norms: latent attention, then the dense MLP or the experts."""
    norms = iter(p[f"RMSNorm_{i}"] for i in range(2))
    if "attn" in p:
        x = x + latent_attention(rms_norm(x, next(norms)), p["attn"])
    if "mlp_gate" in p:
        x = x + swiglu(
            rms_norm(x, next(norms)),
            *(p[f"mlp_{name}"]["kernel"] for name in ("gate", "up", "down")),
        )
    if "moe" in p:
        x = x + experts(rms_norm(x, next(norms)), p["moe"], bias)
    return x


def run_block(x, params, name, buffers):
    p = params[name]
    bias = selection_bias(buffers, name, p["moe"]) if "moe" in p else None
    return jax.checkpoint(block)(x, p, bias)


# ---- the stack and its two losses ---------------------------------------------------


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


def loss_parts(params, tokens, labels, buffers=None):
    """``(main, second)``: the mean next-token loss, and ``MTP_WEIGHT`` times
    the second-token loss of the one module (paper eqs. 21-25 at depth 1)."""
    embedding = params["tok_embed"]["embedding"]
    x = embedding[tokens]
    for layer in range(sum(name.startswith("block_") for name in params)):
        x = run_block(x, params, f"block_{layer}", buffers)
    head = params["lm_head"]
    main = jnp.mean(token_losses(rms_norm(x, params["RMSNorm_0"]), head, labels))
    # assumed (config.json gives num_nextn_predict_layers and no more): h is
    # the last block's output BEFORE the final norm; the concatenation is
    # [RMSNorm(h) ; RMSNorm(Emb(t_{i+1}))] in that order.  t_{i+1} is the
    # input shifted left by one; position T-1 has no t_{T+1} in the batch
    # (the zoo fills it with t_0; causal, so no other position sees it) and
    # is left out of the loss.  Position i's target is t_{i+2} = labels[i+1]:
    # T - 1 of them a sequence, the last from the labels alone
    ahead = embedding[jnp.roll(tokens, -1, axis=1)]
    joined = jnp.concatenate(
        [rms_norm(x, params["mtp_1_hnorm"]), rms_norm(ahead, params["mtp_1_enorm"])],
        axis=-1,
    )
    h = run_block(joined @ params["mtp_1_proj"]["kernel"], params, "mtp_1_block", buffers)
    per_token = token_losses(
        rms_norm(h, params["mtp_1_norm"]), head, jnp.roll(labels, -1, axis=1)
    )
    # the paper's divisor (eq. 24): T - 1 terms over T
    second = jnp.mean(jnp.sum(per_token[:, :-1], axis=1) / labels.shape[1])
    return main, MTP_WEIGHT * second


def loss_fn(params, tokens, labels, buffers=None):
    main, second = loss_parts(params, tokens, labels, buffers)
    return main + second


def loss_and_grads(params, features, labels, router_stats=None):
    """``(loss, grads)``; ``grads`` has the tree of ``params``.  Depth, the
    kind of each layer, widths, heads, the experts held and the vocabulary
    slice are the parameter tree's own shapes.  ``router_stats`` is the
    program's collection of that name, for the routers' selection biases."""
    params = jax.tree_util.tree_map(lambda p: jnp.asarray(p, jnp.float32), params)
    tokens = jnp.asarray(features["tokens"], jnp.int32)
    labels = jnp.asarray(labels, jnp.int32)
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(loss_fn)(params, tokens, labels, router_stats)
