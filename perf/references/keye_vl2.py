"""Plain float32 reference of Keye-VL-2.0-30B-A3B's language model
(``Kwai-Keye/Keye-VL-2.0-30B-A3B`` ``config.json``), as
``configs/keye_vl2_30b_a3b.json`` describes it: loss and gradient of one
batch in the sparse training stage.

A Qwen3-MoE decoder (pre-RMSNorm blocks; grouped-query attention with an
RMSNorm a head on q and k and rotary positions in the rotate-half convention;
softmax top-8 of 128 SwiGLU experts, the chosen weights divided by their
sum; a final RMSNorm and an untied head) whose attention is DeepSeek sparse
attention (DeepSeek-V3.2-Exp technical report): an indexer of
``INDEX_HEADS`` narrow heads over one key head scores every visible key from
the layer's input DETACHED (eq. 1), a query attends to its ``TOPK`` best
keys alone (eq. 2, here over grouped heads), and the indexer is trained to
the main attention's distribution over the set, detached (eq. 4).  Rotary
positions have three components (``MROPE_SECTION``: temporal, height,
width; text gives all three the token's index).  The loss is the mean
next-token cross-entropy + the load-balance loss + the sum over layers of
the indexers' KL.

Everything is ``jax.numpy`` in float32 under
``default_matmul_precision("highest")``; the selection is ``lax.top_k`` (a
tie goes to the lower index) and the softmax over the selected set is
materialised; no kernel, no sort by expert, no grouped matmul; nothing of
the program is imported: the parameter tree is read by its leaf names, and
what it does not carry (the numbers below) is the published
configuration's.  The experts held are the share ``FIRST_EXPERT ..`` of the
router's width that the tree's stacks hold; what the absent ones would add
is left out, as the program leaves it out.

Memory, not mathematics: a layer's attention runs over blocks of
``QUERY_BLOCK`` query rows against the whole context (scores, selection,
softmax and the KL of a block together), the head and its loss over the same
blocks, the experts as a loop over their stacked weights, and each block of
rows, each expert and each layer is recomputed in the backward pass."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

QUERY_BLOCK = 256
# config.json: num_experts_per_tok, norm_topk_prob, rms_norm_eps, rope_theta,
# rope_scaling.mrope_section, sa_config.topk
EXPERTS_PER_TOKEN = 8
NORM_TOPK_PROB = True
RMS_NORM_EPS = 1e-6
ROPE_THETA = 1e7
MROPE_SECTION = (16, 24, 24)
TOPK = 2048
FIRST_EXPERT = 0
# ``assumed`` in the configuration
LOAD_BALANCE_WEIGHT = 0.001
INDEXER_KL_WEIGHT = 1.0


def rms_norm(x, p):
    variance = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(variance + RMS_NORM_EPS) * p["scale"]


def layer_norm(x, p):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    variance = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(variance + RMS_NORM_EPS) * p["scale"] + p["bias"]


def rotate_half(x):
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([-x2, x1], axis=-1)


def sections_for(width: int) -> tuple:
    """``MROPE_SECTION`` over a head of ``width``: in proportion where the
    head is narrower than the sections' own (the indexer's 64 beside 128)."""
    scale = 2 * sum(MROPE_SECTION) // width or 1
    return tuple(n // scale for n in MROPE_SECTION)


def rotary(x, positions):
    """Qwen2-VL's ``apply_multimodal_rotary_pos_emb``: ``x`` (batch, seq,
    heads, d), ``positions`` (batch, 3, seq); frequency ``i`` of the ``d/2``
    takes its angle from the component whose section holds it."""
    d = x.shape[-1]
    inv_freq = 1.0 / (ROPE_THETA ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    sections = sections_for(d)
    assert sum(sections) == d // 2, (sections, d)
    component = jnp.repeat(
        jnp.arange(3), jnp.asarray(sections), total_repeat_length=d // 2
    )
    # (batch, seq, d/2): position of the frequency's own component
    own = jnp.take(positions.astype(jnp.float32), component, axis=1)
    freqs = jnp.swapaxes(own, 1, 2) * inv_freq
    emb = jnp.concatenate([freqs, freqs], axis=-1)[:, :, None, :]
    return x * jnp.cos(emb) + rotate_half(x) * jnp.sin(emb)


def block_rows(seq: int) -> int:
    return QUERY_BLOCK if seq % QUERY_BLOCK == 0 else seq


def index_scores(qi, ki, w):
    """Eq. 1: ``I[t, s] = sum_j w[t, j] relu(qi[t, j] . ki[s])``; ``qi``
    (batch, rows, heads, d), ``ki`` (batch, seq, d), ``w`` (batch, rows,
    heads) -> (batch, rows, seq)."""
    per_head = jax.nn.relu(jnp.einsum("bqjd,bkd->bjqk", qi, ki))
    return jnp.einsum("bjqk,bqj->bqk", per_head, w)


def select(scores, seen, topk):
    """The ``min(t + 1, topk)`` visible keys a query with the largest index
    scores, as a boolean (batch, rows, seq); ``lax.top_k`` gives a tie to
    the lower index.  Passes no gradient."""
    seq = scores.shape[-1]
    hidden = jnp.where(seen, jax.lax.stop_gradient(scores), -jnp.inf)
    _, index = jax.lax.top_k(hidden, min(topk, seq))
    chosen = jax.vmap(
        jax.vmap(lambda ix: jnp.zeros((seq,), bool).at[ix].set(True))
    )(index)
    return chosen & seen


def indexer_kl(probs, scores, chosen):
    """Eq. 4 summed over the rows: ``KL(p || softmax_S I)`` with ``p`` the
    main attention's probabilities averaged over its heads, DETACHED."""
    target = jax.lax.stop_gradient(jnp.mean(probs, axis=1))
    log_index = jax.nn.log_softmax(jnp.where(chosen, scores, -jnp.inf), axis=-1)
    live = chosen & (target > 0)
    log_target = jnp.log(jnp.where(live, target, 1.0))
    return jnp.sum(
        jnp.where(live, target * (log_target - jnp.where(live, log_index, 0.0)), 0.0)
    )


def sparse_attention(q, k, v, qi, ki, w, with_selection=False):
    """Eq. 2 over grouped heads and the layer's KL sum: ``q`` (batch, seq,
    heads, d), ``k``, ``v`` (batch, seq, kv heads, d).  departure: the zoo
    runs Pallas kernels that never hold a score matrix."""
    seq, heads, d = q.shape[1], q.shape[2], q.shape[3]
    group = heads // k.shape[2]
    k, v = (jnp.repeat(x, group, axis=2) for x in (k, v))
    rows = block_rows(seq)
    columns = jnp.arange(seq)

    def rows_from(start):
        def cut(x):
            return jax.lax.dynamic_slice_in_dim(x, start, rows, axis=1)

        seen = (start + jnp.arange(rows))[:, None] >= columns[None, :]
        index = index_scores(cut(qi), ki, cut(w))
        chosen = select(index, seen[None], TOPK)
        scores = jnp.einsum("bqhd,bkhd->bhqk", cut(q), k) / math.sqrt(d)
        probs = jax.nn.softmax(
            jnp.where(chosen[:, None], scores, -jnp.inf), axis=-1
        )
        y = jnp.einsum("bhqk,bkhd->bqhd", probs, v)
        out = (y, indexer_kl(probs, index, chosen))
        return out + (chosen,) if with_selection else out

    made = jax.lax.map(jax.checkpoint(rows_from), jnp.arange(0, seq, rows))
    y = jnp.moveaxis(made[0], 0, 1).reshape(q.shape)
    if with_selection:
        chosen = jnp.moveaxis(made[2], 0, 1)
        return y, jnp.sum(made[1]), chosen.reshape(q.shape[0], seq, seq)
    return y, jnp.sum(made[1])


def attention(x, a, positions, with_selection=False):
    """The layer's attention part on its normed input ``x``."""
    def projected(name, source=x):
        return jnp.einsum("bse,ehd->bshd", source, a[name]["kernel"])

    q = rotary(rms_norm(projected("query"), a["q_norm"]), positions)
    k = rotary(rms_norm(projected("key"), a["k_norm"]), positions)
    v = projected("value")
    detached = jax.lax.stop_gradient(x)
    heads, width = a["index_query"]["kernel"].shape[1:]
    qi = rotary(projected("index_query", detached), positions)
    ki = layer_norm(detached @ a["index_key"]["kernel"], a["index_key_norm"])
    ki = rotary(ki[:, :, None, :], positions)[:, :, 0, :]
    w = (detached @ a["index_weights"]["kernel"]) / math.sqrt(heads * width)
    y, kl, *chosen = sparse_attention(q, k, v, qi, ki, w, with_selection)
    out = jnp.einsum("bshd,hde->bse", y, a["out"]["kernel"])
    return (out, kl / (x.shape[0] * x.shape[1]), *chosen)


def route(x, m):
    """Softmax over every expert in float32, the ``EXPERTS_PER_TOKEN``
    largest, divided by their sum; the weight of every expert for every
    token and the layer's load-balance loss."""
    experts = m["router"]["kernel"].shape[1]
    probs = jax.nn.softmax(x @ m["router"]["kernel"], axis=-1)
    top, chosen = jax.lax.top_k(probs, EXPERTS_PER_TOKEN)
    if NORM_TOPK_PROB:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    one_hot = jax.nn.one_hot(chosen, experts, dtype=x.dtype)
    weight = jnp.einsum("tk,tke->te", top, one_hot)
    fraction = jnp.sum(one_hot, axis=(0, 1)) / x.shape[0]
    balance = experts * jnp.sum(fraction * jnp.mean(probs, axis=0))
    return weight, balance


def experts(x, m):
    """The held experts' part of ``sum_e weight[:, e] down_e(silu(gate_e x)
    * up_e x)``: the stacks hold experts ``FIRST_EXPERT ..`` of the router's
    width."""
    tokens = x.reshape(-1, x.shape[-1])
    weight, balance = route(tokens, m)
    held = m["w_gate"].shape[0]
    weight = jax.lax.dynamic_slice_in_dim(weight, FIRST_EXPERT, held, axis=1)

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
    return y.reshape(x.shape), balance


def block(x, p, positions):
    y, kl = attention(rms_norm(x, p["RMSNorm_0"]), p["attn"], positions)
    x = x + y
    y, balance = experts(rms_norm(x, p["RMSNorm_1"]), p["moe"])
    return x + y, balance, kl


def next_token_loss(x, head, labels):
    seq = x.shape[1]
    rows = block_rows(seq)

    def rows_from(start):
        logits = jax.lax.dynamic_slice_in_dim(x, start, rows, axis=1) @ head["kernel"]
        wanted = jax.lax.dynamic_slice_in_dim(labels, start, rows, axis=1)
        picked = jnp.take_along_axis(logits, wanted[..., None], axis=-1)[..., 0]
        return jnp.sum(jax.nn.logsumexp(logits, axis=-1) - picked)

    sums = jax.lax.map(jax.checkpoint(rows_from), jnp.arange(0, seq, rows))
    return jnp.sum(sums) / labels.size


def text_positions(tokens):
    """Text: all three components are the token's index."""
    batch, seq = tokens.shape
    return jnp.broadcast_to(jnp.arange(seq)[None, None, :], (batch, 3, seq))


def layers_of(params):
    return [
        params[f"block_{i}"]
        for i in range(sum(name.startswith("block_") for name in params))
    ]


def loss_parts(params, tokens, labels, positions):
    """``(main, balance, indexer_kl)``, weights applied."""
    x = params["tok_embed"]["embedding"][tokens]
    balances, kls = [], []
    for p in layers_of(params):
        x, balance, kl = jax.checkpoint(block)(x, p, positions)
        balances.append(balance)
        kls.append(kl)
    x = rms_norm(x, params["RMSNorm_0"])
    return (
        next_token_loss(x, params["lm_head"], labels),
        LOAD_BALANCE_WEIGHT * sum(balances) / len(balances),
        INDEXER_KL_WEIGHT * sum(kls),
    )


def selections(params, features, inputs=None):
    """Each layer's selected set, ``[(batch, seq, seq) bool ...]``: what the
    chip comparison holds the program's mask to.  ``inputs``: each layer's
    normed attention input as the program computed it (its ``indexer_input``);
    then every layer selects from the program's own activations, and what
    the two sets differ by is the indexer's and the selection's alone."""
    params, tokens, positions = _inputs(params, features)
    x = params["tok_embed"]["embedding"][tokens]
    out = []
    with jax.default_matmul_precision("highest"):
        for layer, p in enumerate(layers_of(params)):
            normed = (
                rms_norm(x, p["RMSNorm_0"]) if inputs is None
                else jnp.asarray(inputs[layer], jnp.float32)
            )
            y, _, chosen = attention(normed, p["attn"], positions, True)
            out.append(chosen)
            if inputs is None:
                x = x + y
                x = x + experts(rms_norm(x, p["RMSNorm_1"]), p["moe"])[0]
    return out


def _inputs(params, features):
    params = jax.tree_util.tree_map(lambda p: jnp.asarray(p, jnp.float32), params)
    tokens = jnp.asarray(features["tokens"], jnp.int32)
    positions = features.get("positions") if isinstance(features, dict) else None
    if positions is None:
        positions = text_positions(tokens)
    return params, tokens, jnp.asarray(positions)


def loss_and_grads(params, features, labels):
    """``(loss, grads)``; ``grads`` has the tree of ``params``.  Depth,
    widths, heads, the indexer's sizes and the experts held are the
    parameter tree's own shapes."""
    params, tokens, positions = _inputs(params, features)
    labels = jnp.asarray(labels, jnp.int32)
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(
            lambda p: sum(loss_parts(p, tokens, labels, positions))
        )(params)
