"""The sparse-attention kernels (``ops/sparse_attention.py`` and the flash
kernels over a selected set, ``ops/attention.py::selected_flash_attention``)
against the materialised form, interpreted on the CPU: the exact selection,
forward and every gradient of the attention over the set at grouped heads,
the indexer's loss and its three gradients, and the layer's positions of
several components."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elasticdl_tpu.layers.attention import MultiHeadSelfAttention, rope
from elasticdl_tpu.ops import attention as attention_ops
from elasticdl_tpu.ops import sparse_attention as sparse_ops

BATCH, HEADS, KV_HEADS, WIDTH = 2, 8, 1, 32  # grouped 8 : 1
INDEX_HEADS, INDEX_WIDTH = 4, 16


def operands(seq, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = jax.random.normal(keys[0], (BATCH, seq, HEADS, WIDTH))
    k = jax.random.normal(keys[1], (BATCH, seq, KV_HEADS, WIDTH))
    v = jax.random.normal(keys[2], (BATCH, seq, KV_HEADS, WIDTH))
    qi = jax.random.normal(keys[3], (BATCH, seq, INDEX_HEADS, INDEX_WIDTH))
    ki = jax.random.normal(keys[4], (BATCH, seq, INDEX_WIDTH))
    w = jax.random.normal(keys[5], (BATCH, seq, INDEX_HEADS)) * 0.25
    return q, k, v, qi, ki, w


def selection(qi, ki, w, topk, block):
    mask, lse, kept, ties = sparse_ops.index_select(qi, ki, w, topk, block, block)
    return mask, sparse_ops.transpose_mask(mask, block), lse, kept, ties


# topk smaller than the sequence (most queries select), larger (none does),
# and a sequence of several blocks against one of a single block
@pytest.mark.parametrize(
    "seq,topk,block", [(256, 48, 128), (128, 512, 128), (384, 96, 128), (64, 8, 64)]
)
def test_selected_set_attention_and_the_selection_match_the_materialised_form(
    seq, topk, block
):
    q, k, v, qi, ki, w = operands(seq)
    mask, mask_t, lse_i, kept, _ = selection(qi, ki, w, topk, block)
    scores = sparse_ops.index_scores_reference(qi, ki, w)
    chosen = sparse_ops.select_reference(scores, topk)
    np.testing.assert_array_equal(sparse_ops.dense_mask(mask), chosen)
    np.testing.assert_array_equal(
        kept, np.broadcast_to(np.minimum(np.arange(seq) + 1, topk), kept.shape)
    )
    np.testing.assert_allclose(
        lse_i, jax.nn.logsumexp(jnp.where(chosen, scores, -jnp.inf), axis=-1),
        rtol=1e-5, atol=1e-5,
    )

    def through_kernels(q, k, v):
        out, lse = attention_ops.selected_flash_attention(q, k, v, mask, mask_t)
        return jnp.sum(out * jnp.cos(out)), (out, lse)

    def materialised(q, k, v):
        out, probs = sparse_ops.selected_reference(q, k, v, chosen)
        return jnp.sum(out * jnp.cos(out)), (out, probs)

    (_, (out, lse)), grads = jax.value_and_grad(
        through_kernels, argnums=(0, 1, 2), has_aux=True
    )(q, k, v)
    (_, (want, probs)), want_grads = jax.value_and_grad(
        materialised, argnums=(0, 1, 2), has_aux=True
    )(q, k, v)
    np.testing.assert_allclose(out, want, rtol=2e-5, atol=2e-5)
    for got, wanted, name in zip(grads, want_grads, "qkv"):
        np.testing.assert_allclose(
            got, wanted, rtol=2e-4, atol=2e-4, err_msg=f"d{name}"
        )
    if topk >= seq:  # nothing to select: the dense causal kernels' numbers
        dense = attention_ops.flash_attention(q, k, v, True)
        np.testing.assert_allclose(out, dense, rtol=2e-5, atol=2e-5)

    # the indexer's loss over the same set, and its three gradients
    def kl_kernel(qi, ki, w):
        return sparse_ops.indexer_kl(q, k, lse, mask, qi, ki, w, lse_i)

    def kl_materialised(qi, ki, w):
        return sparse_ops.indexer_kl_reference(
            probs, sparse_ops.index_scores_reference(qi, ki, w), chosen
        )

    value, kl_grads = jax.value_and_grad(kl_kernel, argnums=(0, 1, 2))(qi, ki, w)
    wanted, want_kl_grads = jax.value_and_grad(
        kl_materialised, argnums=(0, 1, 2)
    )(qi, ki, w)
    np.testing.assert_allclose(value, wanted, rtol=1e-5)
    # (the value alone runs the kernel without its gradient half)
    np.testing.assert_allclose(kl_kernel(qi, ki, w), wanted, rtol=1e-5)
    for got, want_grad, name in zip(kl_grads, want_kl_grads, ("qi", "ki", "w")):
        np.testing.assert_allclose(
            got, want_grad, rtol=2e-4, atol=2e-5, err_msg=f"d{name}"
        )


def test_the_selection_passes_no_gradient_and_the_target_none():
    q, k, v, qi, ki, w = operands(128)
    mask, mask_t, lse_i, _, _ = selection(qi, ki, w, 32, 128)
    _, lse = attention_ops.selected_flash_attention(q, k, v, mask, mask_t)

    def loss(q, k, qi):
        mask, lse_i, _, _ = sparse_ops.index_select(qi, ki, w, 32, 128, 128)
        return sparse_ops.indexer_kl(q, k, lse, mask, qi, ki, w, lse_i)

    dq, dk, dqi = jax.grad(loss, argnums=(0, 1, 2))(q, k, qi)
    assert not np.any(dq) and not np.any(dk) and np.any(dqi)


def test_rotary_positions_of_several_components():
    """Text positions (every component the token's index) are plain RoPE;
    distinct components turn each frequency by its own component's angle."""
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 16, 3, 16))
    index = jnp.arange(16)
    text = jnp.broadcast_to(index[None, None, :], (2, 3, 16))
    np.testing.assert_allclose(
        rope(x, text, 1e4, sections=(2, 4, 2)), rope(x, index, 1e4), rtol=1e-6
    )
    parts = jnp.stack([index // 8, (index // 4) % 2, index % 4])
    positions = jnp.broadcast_to(parts[None], (2, 3, 16))
    got = rope(x, positions, 1e4, sections=(2, 4, 2))
    rate = 1e4 ** (-np.arange(8) / 8)
    component = np.repeat(np.arange(3), (2, 4, 2))
    angle = np.asarray(parts).T[:, component] * rate  # (seq, 8)
    cos, sin = np.cos(angle)[None, :, None, :], np.sin(angle)[None, :, None, :]
    x1, x2 = np.split(np.asarray(x), 2, axis=-1)
    want = np.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    with pytest.raises(ValueError, match="sections"):
        rope(x, positions, 1e4, sections=(2, 2, 2))


def test_a_sparse_layer_is_causal_training_only():
    layer = MultiHeadSelfAttention(
        num_heads=4, causal=True, index_topk=8, index_heads=2, index_head_dim=8,
        decode=True, max_decode_len=8,
    )
    with pytest.raises(NotImplementedError, match="sparse"):
        layer.init(jax.random.PRNGKey(0), jnp.zeros((1, 1, 32)), decode_pos=0)
