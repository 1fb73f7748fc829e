"""Plain float32 reference of NVIDIA's ``nemotron_h`` hybrid stack as
``configs/nemotron_twotower_30b_a3b.json`` describes it (the tower that
``nvidia/Nemotron-Labs-TwoTower-30B-A3B-Base-BF16`` ``config.json`` defines,
trained causally on next-token loss): loss and gradient of one batch.

It follows HF ``modeling_nemotron_h.py``.  Every layer is one pre-RMSNorm
residual block holding ONE mixer, told apart here by the key its parameters
carry: ``mamba`` (Mamba-2), ``moe`` (routed experts and a shared expert),
``attn`` (grouped-query attention).  No position signal is added anywhere;
no bias but the convolution's; a final RMSNorm and an untied linear head;
the mean next-token cross-entropy plus the load-balance loss.  Where the zoo
model departs from HF's file the reference follows the zoo, and the line
that does says so.  Everything is ``jax.numpy`` in float32 under
``default_matmul_precision("highest")``; no kernel, no chunked scan, no sort,
no grouped matmul; nothing of the program is imported: the parameter tree is
read by its leaf names.  What the tree does not carry — the numbers below —
is the published configuration's.

The chip's share.  The expert stacks hold ``w_up.shape[0]`` of the
router's experts, those from ``FIRST_EXPERT`` on; a pair routed to another
expert adds nothing, here as in the program, and that partial sum goes on.
The head's rows are the vocabulary slice's.

Memory, not mathematics: the recurrence is the sequential ``lax.scan`` over
time, one step at a time, with the steps of a block of ``SCAN_BLOCK``
recomputed in the backward pass; attention is materialised over blocks of
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
SCAN_BLOCK = 128
# config.json: n_groups, norm_eps / layer_norm_epsilon, num_experts_per_tok,
# norm_topk_prob, routed_scaling_factor (n_group = topk_group = 1: no limit
# on the groups a token's experts come from)
SSM_GROUPS = 8
RMS_NORM_EPS = 1e-5
EXPERTS_PER_TOKEN = 6
NORM_TOPK_PROB = True
ROUTED_SCALING = 2.5
SHARED_EXPERT = True  # n_shared_experts 1
# the first expert this chip holds (``deployment`` in the configuration)
FIRST_EXPERT = 0
# not in config.json (``assumed`` in the configuration)
LOAD_BALANCE_WEIGHT = 1e-4


def rms_norm(x, p):
    variance = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(variance + RMS_NORM_EPS) * p["scale"]


# ---- M: Mamba-2 ----------------------------------------------------------------


def causal_conv(x, kernel, bias):
    """``nn.Conv1d(groups=channels, padding=k-1)[..., :T]``: channel ``c`` at
    step ``t`` is ``sum_j kernel[j, c] * x[t - (k-1) + j, c] + bias[c]``."""
    taps, steps = kernel.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    return bias + sum(
        padded[:, j:j + steps] * kernel[j] for j in range(taps)
    )


def selective_scan(x, dt, a, b, c, d):
    """``h_t = exp(dt_t A) h_{t-1} + dt_t x_t (x) B_t``, ``y_t = C_t . h_t +
    D x_t``, a step at a time.  ``x`` (batch, T, G, H/G, P) and ``dt``
    (batch, T, G, H/G) by group; ``b``, ``c`` (batch, T, G, N), which every
    head of a group reads; ``a``, ``d`` (G, H/G)."""

    def step(h, inputs):
        x_t, dt_t, b_t, c_t = inputs
        decay = jnp.exp(dt_t * a)[..., None, None]
        h = decay * h + jnp.einsum("bgh,bgn,bghp->bghnp", dt_t, b_t, x_t)
        return h, jnp.einsum("bgn,bghnp->bghp", c_t, h)

    def block(h, inputs):
        return jax.lax.scan(step, h, inputs)

    steps = x.shape[1]
    rows = SCAN_BLOCK if steps % SCAN_BLOCK == 0 else steps
    blocked = tuple(
        jnp.moveaxis(v, 1, 0).reshape(steps // rows, rows, *v.shape[:1], *v.shape[2:])
        for v in (x, dt, b, c)
    )
    h0 = jnp.zeros((*x.shape[:1], *x.shape[2:4], b.shape[-1], x.shape[-1]), x.dtype)
    _, y = jax.lax.scan(jax.checkpoint(block), h0, blocked)
    y = jnp.moveaxis(y.reshape(steps, *y.shape[2:]), 0, 1)
    return y + d[..., None] * x


def time_step(dt, bias):
    """``softplus(dt + dt_bias)``; ``time_step_limit`` (0, inf) clamps
    nothing."""
    return jax.nn.softplus(dt + bias)


def gated_group_norm(y, z, scale):
    """``MambaRMSNormGated`` with ``norm_before_gate=False``: the gate first,
    then the mean square within each of the ``SSM_GROUPS`` groups."""
    gated = y * jax.nn.silu(z)
    parts = gated.reshape(*gated.shape[:-1], SSM_GROUPS, -1)
    variance = jnp.mean(jnp.square(parts), axis=-1, keepdims=True)
    parts = parts * jax.lax.rsqrt(variance + RMS_NORM_EPS)
    return parts.reshape(gated.shape) * scale


def mamba(u, m):
    """``NemotronHMamba2Mixer``: ``d_in`` is heads x head size
    (``mamba_num_heads`` x ``mamba_head_dim``), not ``expand`` x hidden."""
    heads = m["A_log"].shape[0]
    inner = m["norm_scale"].shape[0]
    conv_width = m["conv_kernel"].shape[1]
    states = (conv_width - inner) // (2 * SSM_GROUPS)
    batch, steps = u.shape[:2]
    z, xbc, dt = jnp.split(
        u @ m["in_proj"]["kernel"], [inner, inner + conv_width], axis=-1
    )
    xbc = jax.nn.silu(causal_conv(xbc, m["conv_kernel"], m["conv_bias"]))
    x, b, c = jnp.split(xbc, [inner, inner + SSM_GROUPS * states], axis=-1)
    by_group = (SSM_GROUPS, heads // SSM_GROUPS)
    y = selective_scan(
        x.reshape(batch, steps, *by_group, -1),
        time_step(dt, m["dt_bias"]).reshape(batch, steps, *by_group),
        -jnp.exp(m["A_log"]).reshape(by_group),
        b.reshape(batch, steps, SSM_GROUPS, states),
        c.reshape(batch, steps, SSM_GROUPS, states),
        m["D"].reshape(by_group),
    )
    y = gated_group_norm(y.reshape(batch, steps, inner), z, m["norm_scale"])
    return y @ m["out_proj"]["kernel"]


# ---- *: grouped-query attention ------------------------------------------------


def block_rows(seq: int) -> int:
    """Rows of a block: ``QUERY_BLOCK`` where it divides the context, else
    the whole context at once."""
    return QUERY_BLOCK if seq % QUERY_BLOCK == 0 else seq


def visible(rows, columns):
    """The causal mask: query row ``i`` sees key columns ``0..i``."""
    return rows[:, None] >= columns[None, :]


def causal_attention(q, k, v):
    """``softmax(QK^T / sqrt(d)) V`` with the causal mask, each key/value
    head serving ``heads / kv_heads`` consecutive query heads.  departure:
    the zoo runs Pallas flash kernels (``ops/attention.py``), which never
    hold the score matrix and index the shared head instead of repeating
    it."""
    seq, d = q.shape[1], q.shape[3]
    group = q.shape[2] // k.shape[2]
    k, v = (jnp.repeat(x, group, axis=2) for x in (k, v))
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
    """``NemotronHAttention``: no bias, and no rotary embedding is applied
    (``rope_theta`` is in config.json and unused by the modelling file)."""
    def projected(name):
        return jnp.einsum("bse,ehd->bshd", x, a[name]["kernel"])

    y = causal_attention(projected("query"), projected("key"), projected("value"))
    return jnp.einsum("bshd,hde->bse", y, a["out"]["kernel"])


# ---- E: routed experts and the shared expert ---------------------------------


def score(logits):
    return jax.nn.sigmoid(logits)


def choose(scores, bias):
    """``NemotronHTopkRouter.get_topk_indices`` with one group: the largest
    of ``scores + e_score_correction_bias``."""
    return jax.lax.top_k(scores + bias, EXPERTS_PER_TOKEN)[1]


def pair_weights(scores, chosen):
    """The chosen experts' scores WITHOUT the bias, over their sum, times
    ``routed_scaling_factor``."""
    top = jnp.take_along_axis(scores, chosen, axis=-1)
    if NORM_TOPK_PROB:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    return top * ROUTED_SCALING


def activation(x):
    """``mlp_hidden_act`` ``relu2``."""
    return jnp.square(jax.nn.relu(x))


def route(x, m, bias):
    """The weight of every expert for every token (zero where the expert was
    not chosen), over all the experts the router scores, and this layer's
    load-balance loss."""
    experts = m["router"]["kernel"].shape[1]
    scores = score(x @ m["router"]["kernel"])
    chosen = choose(scores, jax.lax.stop_gradient(bias))
    one_hot = jax.nn.one_hot(chosen, experts, dtype=x.dtype)  # (tokens, k, E)
    weight = jnp.einsum("tk,tke->te", pair_weights(scores, chosen), one_hot)
    # departure: config.json names no balance loss; the zoo adds the standard
    # one (``assumed``): E * sum_e (pairs to e / tokens) * mean_t s'[t, e],
    # s' the scores over their sum
    fraction = jnp.sum(one_hot, axis=(0, 1)) / x.shape[0]
    share = scores / jnp.sum(scores, axis=-1, keepdims=True)
    balance = experts * jnp.sum(fraction * jnp.mean(share, axis=0))
    return weight, balance


def shared_expert(tokens, m):
    return activation(tokens @ m["shared_up"]["kernel"]) @ m["shared_down"]["kernel"]


def experts(x, m, bias):
    """``NemotronHMOE``: ``sum_e weight[:, e] * down_e(relu(up_e(x))^2)`` over
    the experts held here plus the shared expert on every token."""
    tokens = x.reshape(-1, x.shape[-1])
    weight, balance = route(tokens, m, bias)
    held = m["w_up"].shape[0]
    weight = jax.lax.dynamic_slice_in_dim(weight, FIRST_EXPERT, held, axis=1)

    def one(weights_of_expert, stacks):
        up, down = stacks
        return (activation(tokens @ up) @ down) * weights_of_expert[:, None]

    def add(y, per_expert):
        return y + jax.checkpoint(one)(*per_expert), None

    y, _ = jax.lax.scan(
        add, jnp.zeros_like(tokens), (weight.T, (m["w_up"], m["w_down"]))
    )
    if SHARED_EXPERT:
        y = y + shared_expert(tokens, m)
    return y.reshape(x.shape), balance


# ---- the stack --------------------------------------------------------------------


def block(x, p, bias):
    """``NemotronHBlock``: ``x + mixer(norm(x))``, one mixer a layer."""
    y = rms_norm(x, p["RMSNorm_0"])
    if "mamba" in p:
        return x + mamba(y, p["mamba"]), 0.0
    if "attn" in p:
        return x + attention(y, p["attn"]), 0.0
    y, balance = experts(y, p["moe"], bias)
    return x + y, balance


def next_token_loss(x, head, labels):
    """Mean over every position of ``logsumexp(logits) - logits[label]`` over
    the head's rows (the vocabulary slice); the head is untied and has no
    bias."""
    seq = x.shape[1]
    rows = block_rows(seq)

    def rows_from(start):
        logits = jax.lax.dynamic_slice_in_dim(x, start, rows, axis=1) @ head["kernel"]
        wanted = jax.lax.dynamic_slice_in_dim(labels, start, rows, axis=1)
        picked = jnp.take_along_axis(logits, wanted[..., None], axis=-1)[..., 0]
        return jnp.sum(jax.nn.logsumexp(logits, axis=-1) - picked)

    sums = jax.lax.map(jax.checkpoint(rows_from), jnp.arange(0, seq, rows))
    return jnp.sum(sums) / labels.size


def selection_bias(buffers, name, m):
    """The router's ``e_score_correction_bias`` of layer ``name``: a buffer
    the program keeps outside its parameters (collection ``router_stats``);
    zero where none is given, as at the seeded init."""
    try:
        return jnp.asarray(buffers[name]["moe"]["selection_bias"], jnp.float32)
    except (KeyError, TypeError):
        return jnp.zeros((m["router"]["kernel"].shape[1],), jnp.float32)


def loss_fn(params, tokens, labels, buffers=None):
    x = params["tok_embed"]["embedding"][tokens]
    balances = []
    for layer in range(sum(name.startswith("block_") for name in params)):
        name = f"block_{layer}"
        p = params[name]
        bias = selection_bias(buffers, name, p["moe"]) if "moe" in p else None
        x, balance = jax.checkpoint(block)(x, p, bias)
        if "moe" in p:
            balances.append(balance)
    x = rms_norm(x, params["RMSNorm_0"])
    loss = next_token_loss(x, params["lm_head"], labels)
    if balances:
        # the zoo sows one loss a layer and takes their mean
        loss = loss + LOAD_BALANCE_WEIGHT * sum(balances) / len(balances)
    return loss


def loss_and_grads(params, features, labels, buffers=None):
    """``(loss, grads)``; ``grads`` has the tree of ``params``.  Depth, the
    kind of each layer, widths, heads, the experts held and the vocabulary
    slice are the parameter tree's own shapes.  ``buffers`` is the program's
    ``router_stats`` collection, for the routers' selection biases."""
    params = jax.tree_util.tree_map(lambda p: jnp.asarray(p, jnp.float32), params)
    tokens = jnp.asarray(features["tokens"], jnp.int32)
    labels = jnp.asarray(labels, jnp.int32)
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(loss_fn)(params, tokens, labels, buffers)
