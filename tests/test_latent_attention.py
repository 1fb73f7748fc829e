"""Latent attention (MLA) and multi-token prediction: the flash kernels at
two head widths against ``mha_reference``, the mixer against DeepSeek-V3's
equations written out by hand, adjacent-pair RoPE on a slice of the head,
and the second-token loss (every position that has a target, the paper's
divisor, a masked row) against a hand-written one."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elasticdl_tpu.layers.attention import (
    LatentSelfAttention,
    TransformerBlock,
    rope,
)
from elasticdl_tpu.models import long_seq_transformer as lm
from elasticdl_tpu.ops.attention import flash_attention, mha_reference
from elasticdl_tpu.telemetry import router_load
from elasticdl_tpu.trainer.state import TrainState
from elasticdl_tpu.trainer.step import build_train_step, weighted_mean_loss


def _qkv(seq, heads, kv_heads, d_qk, d_v, seed=0):
    rng = np.random.RandomState(seed)
    return (
        rng.randn(1, seq, heads, d_qk).astype(np.float32),
        rng.randn(1, seq, kv_heads, d_qk).astype(np.float32),
        rng.randn(1, seq, kv_heads, d_v).astype(np.float32),
    )


# (seq, heads, kv heads, score width, value width, block)
WIDTHS = [
    (256, 2, 2, 192, 128, 128),  # the published widths, blocks halved on the diagonal
    (128, 2, 2, 64, 64, 32),     # control: one width, as every other model
    (128, 4, 2, 24, 16, 32),     # two widths under grouped-query heads
    (96, 2, 1, 16, 40, 32),      # values WIDER than the scores, uneven blocks
]


@pytest.mark.parametrize("seq,heads,kv_heads,d_qk,d_v,block", WIDTHS)
@pytest.mark.parametrize("causal", [True, False])
def test_flash_forward_at_two_widths(seq, heads, kv_heads, d_qk, d_v, block, causal):
    q, k, v = _qkv(seq, heads, kv_heads, d_qk, d_v)
    out = flash_attention(q, k, v, causal=causal, block_q=block, block_k=block)
    ref = mha_reference(q, k, v, causal=causal)
    assert out.shape == (1, seq, heads, d_v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("seq,heads,kv_heads,d_qk,d_v,block", WIDTHS)
@pytest.mark.parametrize("gradient", ["dq", "dk", "dv"])
def test_flash_gradients_at_two_widths(seq, heads, kv_heads, d_qk, d_v, block, gradient):
    """Each of the three gradients against ``jax.vjp`` of the oracle under a
    random cotangent: ``dq`` and ``dk`` as wide as the scores, ``dv`` as wide
    as the values."""
    q, k, v = _qkv(seq, heads, kv_heads, d_qk, d_v, seed=1)
    g = np.random.RandomState(2).randn(1, seq, heads, d_v).astype(np.float32)
    index = ["dq", "dk", "dv"].index(gradient)
    got = jax.vjp(
        lambda *a: flash_attention(*a, causal=True, block_q=block, block_k=block),
        q, k, v,
    )[1](g)[index]
    want = jax.vjp(lambda *a: mha_reference(*a, causal=True), q, k, v)[1](g)[index]
    assert got.shape == (q, k, v)[index].shape
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-4, rtol=2e-4)


def test_default_scale_is_one_over_root_of_the_score_width():
    q, k, v = _qkv(64, 2, 2, 24, 16)
    explicit = flash_attention(q, k, v, causal=True, sm_scale=24 ** -0.5)
    np.testing.assert_allclose(
        np.asarray(flash_attention(q, k, v, causal=True)), np.asarray(explicit),
        atol=1e-6,
    )
    np.testing.assert_allclose(
        np.asarray(mha_reference(q, k, v, causal=True)), np.asarray(explicit),
        atol=2e-5, rtol=2e-5,
    )


def test_keys_as_wide_as_the_values_but_not_the_queries_are_refused():
    q, k, v = _qkv(64, 2, 2, 24, 16)
    with pytest.raises(ValueError, match="q and k head widths differ"):
        flash_attention(q, v, v, causal=True)


# ---- adjacent-pair RoPE on a slice -----------------------------------------------


def test_interleaved_rope_turns_adjacent_pairs_as_complex_numbers():
    x = np.random.RandomState(0).randn(2, 12, 3, 8).astype(np.float32)
    positions = jnp.arange(12)
    theta = 3.2e7
    got = np.asarray(rope(x, positions, theta, interleave=True))
    rate = theta ** (-np.arange(4) / 4.0)
    turn = np.exp(1j * np.arange(12)[:, None] * rate[None, :])[None, :, None, :]
    want = (x[..., 0::2] + 1j * x[..., 1::2]) * turn
    np.testing.assert_allclose(got[..., 0::2], want.real, atol=1e-5)
    np.testing.assert_allclose(got[..., 1::2], want.imag, atol=1e-5)
    # position 0 is left alone, norms are kept
    np.testing.assert_allclose(got[:, 0], x[:, 0], atol=1e-6)
    np.testing.assert_allclose(
        np.linalg.norm(got, axis=-1), np.linalg.norm(x, axis=-1), rtol=1e-5
    )


def test_interleaved_rope_gives_the_scores_of_hfs_regrouped_halves():
    """HF's ``apply_rotary_pos_emb_interleave`` regroups a slice to halves
    and rotates halves: q and k are permuted alike, the scores are the same."""
    rng = np.random.RandomState(1)
    q, k = (rng.randn(1, 10, 2, 8).astype(np.float32) for _ in range(2))
    positions = jnp.arange(10)

    def regrouped(x):
        halves = jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)
        return rope(halves, positions, 1e4)

    ours = jnp.einsum(
        "bqhd,bkhd->bhqk", rope(q, positions, 1e4, True), rope(k, positions, 1e4, True)
    )
    theirs = jnp.einsum("bqhd,bkhd->bhqk", regrouped(q), regrouped(k))
    np.testing.assert_allclose(np.asarray(ours), np.asarray(theirs), atol=1e-4)


def test_rope_touches_the_rotary_slice_alone():
    """With the queries' rotary columns zeroed the rotary key meets nothing,
    so the convention of the rotation cannot show; with them it does."""
    fields = dict(
        num_heads=2, q_lora_rank=12, kv_lora_rank=8, qk_nope_head_dim=8,
        qk_rope_head_dim=4, v_head_dim=8, rope_theta=1e4, causal=True,
    )
    x = np.random.RandomState(0).randn(1, 16, 16).astype(np.float32)
    pairs = LatentSelfAttention(**fields, rope_interleave=True)
    halves = LatentSelfAttention(**fields, rope_interleave=False)
    params = pairs.init(jax.random.PRNGKey(0), x)
    assert not np.allclose(
        np.asarray(pairs.apply(params, x)), np.asarray(halves.apply(params, x)),
        atol=1e-4,
    )
    kernel = params["params"]["q_b"]["kernel"]
    params["params"]["q_b"]["kernel"] = kernel.at[..., 8:].set(0.0)
    np.testing.assert_allclose(
        np.asarray(pairs.apply(params, x)), np.asarray(halves.apply(params, x)),
        atol=1e-6,
    )


# ---- the mixer against the equations ----------------------------------------------


def _rms(x, scale, eps=1e-6):
    return x / np.sqrt(np.mean(np.square(x), axis=-1, keepdims=True) + eps) * scale


def _turn_pairs(x, theta):
    """(T, d) rows, adjacent pairs, position = row."""
    d = x.shape[-1]
    rate = theta ** (-np.arange(0, d, 2) / d)
    angle = np.arange(x.shape[0])[:, None] * rate[None, :]
    out = np.empty_like(x)
    out[:, 0::2] = x[:, 0::2] * np.cos(angle) - x[:, 1::2] * np.sin(angle)
    out[:, 1::2] = x[:, 1::2] * np.cos(angle) + x[:, 0::2] * np.sin(angle)
    return out


def test_mixer_is_the_papers_equations_token_by_token():
    heads, nope, rot, d_v, rank_q, rank_kv, embed, seq = 3, 8, 4, 6, 10, 8, 16, 12
    theta = 3.2e7
    mixer = LatentSelfAttention(
        num_heads=heads, q_lora_rank=rank_q, kv_lora_rank=rank_kv,
        qk_nope_head_dim=nope, qk_rope_head_dim=rot, v_head_dim=d_v,
        rope_theta=theta, causal=True,
    )
    x = np.random.RandomState(0).randn(1, seq, embed).astype(np.float32)
    variables = mixer.init(jax.random.PRNGKey(1), x)
    p = jax.tree_util.tree_map(
        # scales away from 1, so a norm in the wrong place shows
        lambda a: np.asarray(a) * (1.3 if a.ndim == 1 else 1.0), variables["params"]
    )
    assert {k: v[next(iter(v))].shape for k, v in p.items()} == {
        "q_a": (embed, rank_q), "q_a_norm": (rank_q,),
        "q_b": (rank_q, heads, nope + rot),
        "kv_a": (embed, rank_kv + rot), "kv_a_norm": (rank_kv,),
        "kv_b": (rank_kv, heads, nope + d_v), "out": (heads, d_v, embed),
    }
    got = np.asarray(mixer.apply({"params": p}, x))[0]

    rows = x[0].astype(np.float64)
    c_q = _rms(rows @ p["q_a"]["kernel"], p["q_a_norm"]["scale"])
    q = np.einsum("tr,rhd->thd", c_q, p["q_b"]["kernel"])
    latent = rows @ p["kv_a"]["kernel"]
    c_kv = _rms(latent[:, :rank_kv], p["kv_a_norm"]["scale"])
    k_r = _turn_pairs(latent[:, rank_kv:], theta)  # ONE rotary key a token
    kv = np.einsum("tr,rhd->thd", c_kv, p["kv_b"]["kernel"])
    want = np.zeros((seq, embed))
    for h in range(heads):
        q_h = np.concatenate([q[:, h, :nope], _turn_pairs(q[:, h, nope:], theta)], axis=1)
        k_h = np.concatenate([kv[:, h, :nope], k_r], axis=1)
        scores = q_h @ k_h.T / np.sqrt(nope + rot)
        scores = np.where(np.tril(np.ones((seq, seq), bool)), scores, -np.inf)
        probs = np.exp(scores - scores.max(axis=1, keepdims=True))
        probs /= probs.sum(axis=1, keepdims=True)
        want += probs @ kv[:, h, nope:] @ p["out"]["kernel"][h]
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-4)


def test_block_with_latent_fields_is_the_same_block_and_refuses_to_decode():
    latent = (
        ("q_lora_rank", 12), ("kv_lora_rank", 8), ("qk_nope_head_dim", 8),
        ("qk_rope_head_dim", 4), ("v_head_dim", 8), ("rope_interleave", True),
    )
    x = np.random.RandomState(0).randn(1, 8, 16).astype(np.float32)
    block = TransformerBlock(
        causal=True, norm="rmsnorm", use_bias=False, mlp="swiglu", kind="*",
        latent_fields=(("num_heads", 2), ("rope_theta", 1e4)) + latent,
    )
    params = block.init(jax.random.PRNGKey(0), x)["params"]
    assert set(params) == {"RMSNorm_0", "attn"}
    assert set(params["attn"]) == {
        "q_a", "q_a_norm", "q_b", "kv_a", "kv_a_norm", "kv_b", "out"
    }
    mixer = LatentSelfAttention(
        num_heads=2, causal=True, rope_theta=1e4, **dict(latent)
    )
    normed = _rms(x, np.asarray(params["RMSNorm_0"]["scale"]))
    want = x + np.asarray(mixer.apply({"params": params["attn"]}, normed))
    np.testing.assert_allclose(
        np.asarray(block.apply({"params": params}, x)), want, atol=1e-5
    )
    with pytest.raises(NotImplementedError, match="latent"):
        block.clone(decode=True, max_decode_len=8).init(
            jax.random.PRNGKey(0), x[:, :1], False, jnp.zeros((), jnp.int32)
        )


# ---- multi-token prediction ----------------------------------------------------------


def tiny_mtp_model(**fields):
    return lm.custom_model(**{
        **dict(
            vocab_size=32, embed_dim=16, num_heads=2, num_layers=2, norm="rmsnorm",
            use_bias=False, positions="rope", mlp="swiglu", mlp_width=24,
            q_lora_rank=12, kv_lora_rank=8, qk_nope_head_dim=8, qk_rope_head_dim=4,
            v_head_dim=8, mtp_depth=1, mtp_weight=0.3,
        ),
        **fields,
    })


def _batch(rows=2, seq=12, vocab=32, seed=0):
    tokens = np.random.RandomState(seed).randint(0, vocab, (rows, seq + 1))
    return {"tokens": tokens[:, :-1].astype(np.int32)}, tokens[:, 1:].astype(np.int32)


def _log_softmax(logits):
    logits = np.asarray(logits, np.float64)
    top = logits.max(axis=-1, keepdims=True)
    return logits - top - np.log(np.exp(logits - top).sum(axis=-1, keepdims=True))


@pytest.mark.parametrize("depth", [1, 2])
def test_second_token_loss_is_the_hand_written_one(depth):
    features, labels = _batch()
    model = tiny_mtp_model(mtp_depth=depth)
    variables = model.init(jax.random.PRNGKey(0), features)
    assert {"mtp_1_proj", "mtp_1_block", "mtp_1_hnorm", "mtp_1_enorm", "mtp_1_norm"} <= set(
        variables["params"]
    )
    assert ("mtp_2_block" in variables["params"]) == (depth == 2)
    assert variables["params"]["mtp_1_proj"]["kernel"].shape == (32, 16)
    outputs, _ = model.apply(
        variables, features, training=True, mutable=[router_load.LOSS_PARTS]
    )
    assert len(outputs["mtp_logits"]) == depth
    parts = lm.loss_parts(labels, outputs)
    rows, seq = labels.shape
    main = -np.mean([
        _log_softmax(outputs["logits"])[r, i, labels[r, i]]
        for r in range(rows) for i in range(seq)
    ])
    # module k at position i predicts t_{i+k+1} = labels[i + k]: seq - k of
    # them a row, the last k in no input; each sum is divided by seq, and
    # the modules' losses by their number
    ahead = np.mean([
        -sum(
            _log_softmax(outputs["mtp_logits"][k - 1])[r, i, labels[r, i + k]]
            for i in range(seq - k)
        ) / seq
        for k in range(1, depth + 1) for r in range(rows)
    ])
    np.testing.assert_allclose(float(parts["main"]), main, rtol=1e-5)
    np.testing.assert_allclose(float(parts["mtp"]), 0.3 * ahead, rtol=1e-5)
    np.testing.assert_allclose(
        float(lm.loss(labels, outputs)), main + 0.3 * ahead, rtol=1e-5
    )


def test_last_position_of_the_module_weighs_nothing_and_the_last_label_does():
    features, labels = _batch(seed=3)
    model = tiny_mtp_model()
    variables = model.init(jax.random.PRNGKey(0), features)

    def second(tokens, labels):
        outputs, _ = model.apply(
            variables, {"tokens": tokens}, training=True,
            mutable=[router_load.LOSS_PARTS],
        )
        return float(lm.loss_parts(labels, outputs)["mtp"])

    base = second(features["tokens"], labels)
    moved = labels.copy()
    moved[:, 0] = (moved[:, 0] + 1) % 32  # t_1 as a TARGET is the main loss's alone
    assert second(features["tokens"], moved) == pytest.approx(base, rel=1e-6)
    moved = labels.copy()
    moved[:, -1] = (moved[:, -1] + 1) % 32  # t_T: the last position's target
    assert second(features["tokens"], moved) != pytest.approx(base, rel=1e-6)


def test_a_masked_row_weighs_nothing_in_either_loss():
    features, labels = _batch(rows=2, seed=5)
    model = tiny_mtp_model()
    variables = jax.jit(model.init)(jax.random.PRNGKey(0), features)

    def weighted(params, tokens, labels, weights):
        outputs, _ = model.apply(
            {**variables, "params": params}, {"tokens": tokens}, training=True,
            mutable=[router_load.LOSS_PARTS],
        )
        parts = weighted_mean_loss(lm.loss.parts, labels, outputs, weights)
        return parts["main"] + parts["mtp"], parts

    weighted = jax.jit(weighted)
    grad = jax.jit(jax.grad(lambda *a: weighted(*a)[0]))
    padded = grad(variables["params"], features["tokens"], labels, jnp.array([1.0, 0.0]))
    alone = grad(
        variables["params"], features["tokens"][:1], labels[:1], jnp.array([1.0])
    )
    for a, b in zip(jax.tree_util.tree_leaves(padded), jax.tree_util.tree_leaves(alone)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)
    _, parts = weighted(variables["params"], features["tokens"], labels, jnp.array([1.0, 0.0]))
    _, first = weighted(variables["params"], features["tokens"][:1], labels[:1], jnp.array([1.0]))
    assert float(parts["mtp"]) == pytest.approx(float(first["mtp"]), rel=1e-6)
    assert float(parts["mtp"]) > 0


def test_prediction_and_evaluation_return_the_main_logits():
    features, _ = _batch()
    model = tiny_mtp_model()
    variables = model.init(jax.random.PRNGKey(0), features)
    logits = model.apply(variables, features, training=False)
    assert logits.shape == (2, 12, 32)
    outputs, _ = model.apply(
        variables, features, training=True, mutable=[router_load.LOSS_PARTS]
    )
    np.testing.assert_allclose(np.asarray(outputs["logits"]), np.asarray(logits), atol=1e-6)
    # the shared embedding and head are one module each
    assert sum("embed" in k for k in variables["params"]) == 1
    assert sum("lm_head" in k for k in variables["params"]) == 1


def test_a_model_without_the_module_keeps_its_tree_and_its_plain_loss():
    features, labels = _batch()
    model = tiny_mtp_model(mtp_depth=0)
    variables = model.init(jax.random.PRNGKey(0), features)
    assert set(variables) == {"params"}
    assert not any(k.startswith("mtp") for k in variables["params"])
    logits = model.apply(variables, features, training=True)
    assert set(lm.loss_parts(labels, logits)) == {"main"}
    assert float(lm.loss(labels, logits)) == pytest.approx(
        float(lm.loss_parts(labels, logits)["main"])
    )


@pytest.mark.parametrize("weights", [None, (1.0, 0.0)])
def test_the_step_leaves_both_losses_in_the_state(weights):
    """The route ``telemetry/router_load.py`` takes: device arrays inside
    the train state, read on demand; their sum is the loss the step reports."""
    features, labels = _batch()
    model = tiny_mtp_model()
    variables = model.init(jax.random.PRNGKey(0), features)
    model_state = {k: v for k, v in variables.items() if k != "params"}
    assert set(model_state) == {router_load.LOSS_PARTS}
    state = TrainState.create(
        model.apply, variables["params"], lm.optimizer(), model_state
    )
    step = build_train_step(lm.loss, donate=False)
    args = (features, labels) + (() if weights is None else (jnp.array(weights),))
    new_state, metrics = step(state, *args)
    parts = router_load.read_loss_parts(new_state.model_state)
    assert set(parts) == {"main", "mtp"} and parts["mtp"] > 0
    assert float(metrics["loss"]) == pytest.approx(parts["main"] + parts["mtp"], rel=1e-6)
    assert router_load.read_loss_parts(state.model_state) == {"main": 0.0, "mtp": 0.0}
    assert router_load.read_loss_parts({}) is None
    # the same structure goes in and comes out: a scan can carry it
    assert jax.tree_util.tree_structure(state) == jax.tree_util.tree_structure(new_state)
