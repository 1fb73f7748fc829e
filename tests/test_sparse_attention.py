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

    def grads_of(f, **more):  # one program a side
        return jax.jit(jax.value_and_grad(f, argnums=(0, 1, 2), **more))

    (_, (out, lse)), grads = grads_of(through_kernels, has_aux=True)(q, k, v)
    (_, (want, probs)), want_grads = grads_of(materialised, has_aux=True)(q, k, v)
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

    value, kl_grads = grads_of(kl_kernel)(qi, ki, w)
    wanted, want_kl_grads = grads_of(kl_materialised)(qi, ki, w)
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


# ---- a pass of the select runs only where its answer is not known --------------


def tied_operands(seq, heads=2, width=8, seed=0):
    """Every key one of three vectors: whole runs of exact ties, across the
    last place for nearly every query (``tests/perf/test_perf_keye.py``)."""
    rng = np.random.default_rng(seed)
    qi = jnp.asarray(rng.normal(size=(1, seq, heads, width)), jnp.float32)
    three = rng.normal(size=(3, width))
    ki = jnp.asarray(three[rng.integers(3, size=seq)][None], jnp.float32)
    w = jnp.asarray(np.abs(rng.normal(size=(1, seq, heads))), jnp.float32)
    return qi, ki, w


def zeros_operands(seq, heads=2, width=8, seed=0):
    """Runs of exact zeros (every head's ReLU shut) across the last place:
    queries that point away from most keys."""
    rng = np.random.default_rng(seed)
    ki = np.abs(rng.normal(size=(1, seq, width)))
    qi = -np.abs(rng.normal(size=(1, seq, heads, width)))
    open_ = rng.random(size=(1, seq)) < 0.1  # a tenth of the keys score
    ki = np.where(open_[..., None], -ki, ki)
    w = np.abs(rng.normal(size=(1, seq, heads)))
    return tuple(jnp.asarray(x, jnp.float32) for x in (qi, ki, w))


def random_operands(seq, seed=0):
    return operands(seq, seed)[3:]


CASES = {
    "random": (random_operands, 256, 48, 128),
    "random_three_blocks": (random_operands, 384, 96, 128),
    "tied_keys": (tied_operands, 256, 8, 128),
    "exact_zeros": (zeros_operands, 256, 64, 128),
    "fewer_keys_than_topk": (random_operands, 128, 512, 128),
    "one_narrow_block": (random_operands, 64, 8, 64),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_hinted_selection_is_the_selection(case):
    """``index_select_hinted`` on the threshold ``index_select_threshold``
    found and the same operands: the mask on every pair, ``lse`` and both
    counters equal, every block held; and both are ``lax.top_k``'s set."""
    make, seq, topk, block = CASES[case]
    qi, ki, w = make(seq)
    mask, lse, kept, ties, _, threshold = sparse_ops.index_select_threshold(
        qi, ki, w, topk, block, block
    )
    for got, want in zip(
        sparse_ops.index_select(qi, ki, w, topk, block, block),
        (mask, lse, kept, ties),
    ):
        np.testing.assert_array_equal(got, want)
    again = sparse_ops.index_select_hinted(
        qi, ki, w, threshold, topk, block, block
    )
    for got, want in zip(again, (mask, lse, kept, ties)):
        np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(again[4], 1.0)
    chosen = sparse_ops.select_reference(
        sparse_ops.index_scores_reference(qi, ki, w), topk
    )
    np.testing.assert_array_equal(sparse_ops.dense_mask(again[0]), chosen)
    np.testing.assert_array_equal(
        kept, np.broadcast_to(np.minimum(np.arange(seq) + 1, topk), kept.shape)
    )


def _one_ulp_up(threshold, qi, ki, w):
    kth, cut = threshold
    return (kth + 1, cut), (qi, ki, w)


def _cut_one_short(threshold, qi, ki, w):
    kth, cut = threshold
    return (kth, cut - 1), (qi, ki, w)


def _one_bf16_bit(threshold, qi, ki, w):
    # the last place of one key's bfloat16 numbers, as a recomputation
    # that rounded differently would hand it over
    moved = ki.astype(jnp.bfloat16)
    bits = jax.lax.bitcast_convert_type(moved[:, 5], jnp.uint16) ^ 1
    moved = moved.at[:, 5].set(jax.lax.bitcast_convert_type(bits, jnp.bfloat16))
    return threshold, (qi, moved.astype(ki.dtype), w)


@pytest.mark.parametrize(
    "spoil", [_one_ulp_up, _cut_one_short, _one_bf16_bit],
    ids=["threshold_one_ulp_up", "cut_one_short", "operand_one_bf16_bit"],
)
@pytest.mark.parametrize("case", ["random", "tied_keys"])
def test_a_wrong_hint_falls_back_to_the_search(case, spoil):
    """A hint that does not select exactly ``min(t + 1, topk)`` keys in some
    row of a block sends that block through the search: the result is the
    exact selection of the operands in front of the kernel either way."""
    make, seq, topk, block = CASES[case]
    qi, ki, w = make(seq)
    if spoil is _one_bf16_bit:  # operands a bfloat16 layer would hand over
        qi, ki = (x.astype(jnp.bfloat16).astype(x.dtype) for x in (qi, ki))
    threshold = sparse_ops.index_select_threshold(qi, ki, w, topk, block, block)[5]
    threshold, moved = spoil(threshold, qi, ki, w)
    mask, lse, kept, ties, held = sparse_ops.index_select_hinted(
        *moved, threshold, topk, block, block
    )
    want = sparse_ops.index_select(*moved, topk, block, block)
    for got, wanted in zip((mask, lse, kept, ties), want):
        np.testing.assert_array_equal(got, wanted)
    np.testing.assert_array_equal(
        sparse_ops.dense_mask(mask),
        sparse_ops.select_reference(
            sparse_ops.index_scores_reference(*moved), topk
        ),
    )
    assert float(jnp.mean(held)) < 1.0  # some block fell back


def test_the_tie_search_runs_in_the_blocks_that_have_a_tie_to_cut():
    """``searched`` is 1.0 for the queries of a block whose tie search ran:
    none without a tie at the last place, and exactly the blocks that hold a
    query with one (``ties``) otherwise."""
    seq, block = 512, 128
    # distinct scores: one head of weight 1 against keys that grow
    qi = jnp.ones((1, seq, 1, 8), jnp.float32)
    ki = jnp.broadcast_to(jnp.arange(1.0, seq + 1)[None, :, None], (1, seq, 8))
    w = jnp.ones((1, seq, 1), jnp.float32)
    made = sparse_ops.index_select_threshold(qi, ki, w, 16, block, block)
    assert float(jnp.sum(made[3])) == 0 and float(jnp.sum(made[4])) == 0
    # one run of equal keys that only the third block's queries must cut
    # (later queries see 16 larger keys; earlier ones keep all they see)
    ki = ki.at[:, 256:300].set(ki[:, 256])
    _, _, _, ties, searched, _ = sparse_ops.index_select_threshold(
        qi, ki, w, 16, block, block
    )
    by_block = np.asarray(ties).reshape(seq // block, block).sum(axis=1) > 0
    assert by_block.tolist() == [False, False, True, False]
    np.testing.assert_array_equal(
        np.asarray(searched).reshape(seq // block, block),
        np.broadcast_to(by_block[:, None], (seq // block, block)).astype(np.float32),
    )


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_a_recomputed_sparse_layer_is_the_layer(dtype, monkeypatch):
    """A sparse model's loss, its parts, its counters and every gradient
    under ``remat_layers`` (the recomputed pass checks the first pass's
    threshold, ``layers/recompute.py``) against what ``nn.remat``, which
    searches again, gives, and the same model's without recomputation: all
    three equal in float32; in bfloat16 as close as two programs XLA fuses
    its own ways come (``nn.remat``'s gradients differ from the plain
    model's by 5% of the largest, and ours read a layer's input as stored,
    where XLA may hand ``nn.remat``'s first pass the unrounded sum)."""
    import flax.linen as nn

    from elasticdl_tpu.models import long_seq_transformer as zoo

    fields = dict(
        vocab_size=64, embed_dim=32, num_heads=4, num_kv_heads=2, head_dim=8,
        num_layers=2, dtype=dtype, norm="rmsnorm", use_bias=False,
        positions="rope", mrope_section=(1, 2, 1), qk_norm_per_head=True,
        index_topk=8, index_heads=2, index_head_dim=8, mlp="swiglu",
    )
    tokens = np.random.default_rng(0).integers(64, size=(2, 64)).astype(np.int32)
    features = {"tokens": tokens}

    # (recomputation changes no parameter: one init for the three models)
    variables = jax.jit(
        zoo.custom_model(**fields).init, static_argnames="training"
    )(jax.random.PRNGKey(0), features, training=False)
    state = {k: v for k, v in variables.items() if k != "params"}

    def run(remat):
        model = zoo.custom_model(remat_layers=remat, **fields)

        def loss(params):
            logits, new = model.apply(
                {"params": params, **state}, features, training=True,
                mutable=list(state) + ["losses"],
            )
            sown = sum(jax.tree_util.tree_leaves(new.pop("losses")))
            return zoo.loss(tokens, logits) + sown, new

        (value, new), grads = jax.jit(
            jax.value_and_grad(loss, has_aux=True)
        )(variables["params"])
        return jax.tree_util.tree_leaves((value, new, grads)), new, grads

    ours, new, grads = run(True)
    monkeypatch.setattr(zoo, "remat_with_findings", nn.remat)
    searched_again, _, _ = run(True)
    for ours_leaf, want in zip(ours, searched_again):
        if dtype == "float32":
            np.testing.assert_array_equal(ours_leaf, want)
        else:
            np.testing.assert_allclose(
                ours_leaf, want, rtol=0.05,
                atol=0.1 * float(jnp.max(jnp.abs(want))) + 1e-6,
            )
    if dtype == "float32":
        for ours_leaf, want in zip(ours, run(False)[0]):
            np.testing.assert_array_equal(ours_leaf, want)
    stats = new["selection_stats"]["block_0"]["attn"]
    assert set(stats) == {"kept_keys", "ties_broken", "tie_search_blocks"}
    assert np.any(grads["block_0"]["attn"]["index_query"]["kernel"])


# ---- the indexer's loss makes one pass a layer and step --------------------------


def _bf16_ulp(x):
    """The spacing of bfloat16 at ``x``'s magnitude (8 bits of precision)."""
    x = np.abs(np.asarray(x, np.float32))
    return 2.0 ** (np.floor(np.log2(np.maximum(x, 1e-30))) - 7)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize(
    "cotangent", [2.0**-14, 1.0 / 3.0], ids=["power_of_two", "a_third"]
)
def test_the_one_pass_kl_is_the_two_call_kl(dtype, cotangent):
    """``indexer_kl_found`` on what ``indexer_kl_with_grads`` found against
    ``indexer_kl`` under ``value_and_grad``: the value bit for bit (the
    gradient variant writes the rows the value variant writes); the three
    gradients bit for bit where the cotangent is a power of two (rounding
    before a scale by one is rounding after it) and within one rounding of
    the operand's dtype elsewhere; in float32 both are the materialised
    form's within this file's limits."""
    seq, topk, block = 256, 48, 128
    q, k, v, qi, ki, w = operands(seq)
    mask, mask_t, lse_i, _, _ = selection(qi, ki, w, topk, block)
    _, lse = attention_ops.selected_flash_attention(q, k, v, mask, mask_t)
    qi, ki = qi.astype(dtype), ki.astype(dtype)

    def two_calls(qi, ki, w):
        return cotangent * sparse_ops.indexer_kl(q, k, lse, mask, qi, ki, w, lse_i)

    found = sparse_ops.indexer_kl_with_grads(q, k, lse, mask, qi, ki, w, lse_i)
    assert [x.dtype for x in found[1]] == [qi.dtype, ki.dtype, w.dtype]

    def one_pass(qi, ki, w):
        return cotangent * sparse_ops.indexer_kl_found((qi, ki, w), found)

    want, want_grads = jax.value_and_grad(two_calls, argnums=(0, 1, 2))(qi, ki, w)
    value, grads = jax.value_and_grad(one_pass, argnums=(0, 1, 2))(qi, ki, w)
    np.testing.assert_array_equal(value, want)
    for got, wanted, name in zip(grads, want_grads, ("qi", "ki", "w")):
        assert got.dtype == wanted.dtype
        got, wanted = (np.asarray(x, np.float32) for x in (got, wanted))
        if cotangent == 2.0**-14 or dtype == "float32" or name == "w":
            np.testing.assert_array_equal(got, wanted, err_msg=f"d{name}")
        else:
            assert np.all(np.abs(got - wanted) <= _bf16_ulp(wanted)), name
    if dtype == "float32":
        _, probs = sparse_ops.selected_reference(
            q, k, v, sparse_ops.dense_mask(mask)
        )

        def materialised(qi, ki, w):
            return cotangent * sparse_ops.indexer_kl_reference(
                probs, sparse_ops.index_scores_reference(qi, ki, w),
                sparse_ops.dense_mask(mask),
            )

        wanted, want_grads = jax.value_and_grad(
            materialised, argnums=(0, 1, 2)
        )(qi, ki, w)
        np.testing.assert_allclose(value, wanted, rtol=1e-5)
        for got, want_grad, name in zip(grads, want_grads, ("qi", "ki", "w")):
            np.testing.assert_allclose(
                got, want_grad, rtol=2e-4, atol=2e-5 * cotangent,
                err_msg=f"d{name}",
            )


def _kl_calls(jaxpr):
    """The number of results of every ``dsa_kl`` call a jaxpr holds, its
    sub-programs' too: 1 the value variant, 4 with the three gradients."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            if eqn.params["name"] == sparse_ops.INDEXER_KL:
                found.append(len(eqn.outvars))
            continue
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    found += _kl_calls(sub)
    return sorted(found)


def _tiny_sparse_model(remat, layers=2):
    from elasticdl_tpu.models import long_seq_transformer as zoo

    model = zoo.custom_model(
        remat_layers=remat, vocab_size=64, embed_dim=32, num_heads=4,
        num_kv_heads=2, head_dim=8, num_layers=layers, dtype="float32",
        norm="rmsnorm", use_bias=False, positions="rope", index_topk=8,
        index_heads=2, index_head_dim=8, mlp="swiglu",
    )
    tokens = np.random.default_rng(0).integers(64, size=(2, 64)).astype(np.int32)
    features = {"tokens": tokens}
    variables = model.init(jax.random.PRNGKey(0), features, training=False)
    state = {k: v for k, v in variables.items() if k != "params"}

    def loss(params):
        logits, new = model.apply(
            {"params": params, **state}, features, training=True,
            mutable=list(state) + ["losses"],
        )
        return zoo.loss(tokens, logits) + sum(
            jax.tree_util.tree_leaves(new["losses"])
        )

    return loss, variables["params"]


@pytest.mark.parametrize("remat", [True, False], ids=["remat", "no_remat"])
def test_a_pass_that_is_not_differentiated_pays_for_no_gradient(remat):
    """An undifferentiated apply traces the loss's value variant alone, one
    call a layer, recomputed layers or not; a differentiated step without
    ``remat_layers`` is the two-call path as it was (value in the forward
    rule, gradients in the backward rule).  What a differentiated
    ``remat_layers`` step compiles to is ``tests/test_op_scopes.py``'s."""
    loss, params = _tiny_sparse_model(remat)
    assert _kl_calls(jax.make_jaxpr(loss)(params).jaxpr) == [1, 1]
    if not remat:
        step = jax.make_jaxpr(jax.value_and_grad(loss))(params)
        assert _kl_calls(step.jaxpr) == [1, 1, 4, 4]


def test_a_layer_can_see_that_its_first_pass_is_being_differentiated():
    """``recompute.offers_kept``: True in the forward rule's pass of a
    differentiated layer, False in an undifferentiated pass, in a recomputed
    one and outside ``remat_with_findings``; an offer made where it is True
    is what the recomputed pass finds."""
    import flax.linen as nn

    from elasticdl_tpu.layers import recompute

    seen = []

    class Probe(nn.Module):
        @nn.compact
        def __call__(self, x):
            finding = recompute.found()
            kept = recompute.offers_kept()
            seen.append((finding is not None, kept))
            if finding is None:
                recompute.offer(jnp.full((), 3.0 if kept else 5.0))
                finding = 1.0
            return nn.Dense(4)(x) * finding

    assert recompute.offers_kept() is False
    x = jnp.ones((2, 4))
    plain = Probe()
    params = plain.init(jax.random.PRNGKey(0), x)
    assert seen == [(False, False)]  # no ``remat_with_findings`` around it
    layer = recompute.remat_with_findings(Probe)()
    del seen[:]
    out = layer.apply(params, x)
    assert seen == [(False, False)]
    np.testing.assert_array_equal(out, plain.apply(params, x))
    del seen[:]
    grads = jax.grad(lambda p: jnp.sum(layer.apply(p, x)))(params)
    assert seen.count((False, True)) == 1  # the forward rule's pass
    assert (True, False) in seen  # the recomputed pass
    assert (True, True) not in seen
    # the backward pass differentiated the recomputed layer, which was
    # handed the kept pass's offer (3.0) and not an undifferentiated one's
    want = jax.grad(lambda p: jnp.sum(plain.apply(p, x) * 3.0))(params)
    for got, wanted in zip(
        jax.tree_util.tree_leaves(grads), jax.tree_util.tree_leaves(want)
    ):
        np.testing.assert_allclose(got, wanted, rtol=1e-6)
    assert recompute.offers_kept() is False
