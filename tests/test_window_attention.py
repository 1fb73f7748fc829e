"""Window attention (docs/designs/window_attention.md): the three flash
kernels under a window against the materialised form, the block plan's counts
against a brute-force count, the chunk stream's first and last live chunk, a
window at or past the sequence as the dense kernels exactly; and the layer's
parts around them: the output gate, the norm on a part's output, positions by
kind of layer, the scaled embedding, decoding through a window layer's cache,
the block plan's counter.  Kernels run interpreted, at sizes of a few blocks.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import elasticdl_tpu.ops.attention as attention_ops
from elasticdl_tpu.ops.attention import (
    attention,
    flash_attention,
    flash_block_plan,
    flash_layout,
    mha_reference,
    set_attention_mesh,
)

SEQ, BLOCK = 128, 16


@pytest.fixture(autouse=True)
def _reset_attention_mesh():
    yield
    set_attention_mesh(None)


def _operands(heads, kv_heads, width, seq=SEQ, batch=1, seed=0):
    rng = np.random.RandomState(seed)

    def made(h):
        return jnp.asarray(rng.randn(batch, seq, h, width), jnp.float32)

    return made(heads), made(kv_heads), made(kv_heads), made(heads)


def _eqns(jaxpr):
    """Every equation of ``jaxpr``, those of nested jaxprs included."""
    for eqn in jaxpr.eqns:
        yield eqn
        for param in eqn.params.values():
            params = param if isinstance(param, (list, tuple)) else [param]
            for one in params:
                inner = getattr(one, "jaxpr", one)
                if hasattr(inner, "eqns"):
                    yield from _eqns(inner)


def _kernel_names(fn, *args):
    """The names of the ``pallas_call``s of ``fn``'s jaxpr."""
    return {
        eqn.params["name"]
        for eqn in _eqns(jax.make_jaxpr(fn)(*args).jaxpr)
        if eqn.primitive.name == "pallas_call"
    }


def _forward_and_gradients(fn, q, k, v, w):
    grads = jax.grad(
        lambda q, k, v: jnp.sum(fn(q, k, v) * w), argnums=(0, 1, 2)
    )(q, k, v)
    return (fn(q, k, v), *grads)


# GQA 8 : 1 (folded, the cell's grouping) and ungrouped 64-wide heads (lanes)
LAYOUTS = {"gqa_8_to_1": (8, 1, 32, "folded"), "lanes_64": (2, 2, 64, "lanes")}
# smaller than a block, equal to one, no multiple of one, several blocks
WINDOWS = (5, BLOCK, 40, 3 * BLOCK)


@pytest.mark.parametrize("window", WINDOWS)
@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_window_kernels_match_the_materialised_form(layout, window):
    """Forward and all three gradients, per cotangent."""
    heads, kv_heads, width, name = LAYOUTS[layout]
    q, k, v, w = _operands(heads, kv_heads, width)
    assert flash_layout(q, k, v) == name

    def flash(q, k, v):
        return flash_attention(
            q, k, v, True, None, BLOCK, BLOCK, True, window
        )

    def plain(q, k, v):
        return mha_reference(q, k, v, causal=True, window=window)

    got = _forward_and_gradients(flash, q, k, v, w)
    want = _forward_and_gradients(plain, q, k, v, w)
    for a, b in zip(got, want):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=2e-5, rtol=2e-5
        )


@pytest.mark.parametrize("block_q,block_k", [(32, 16), (16, 32)])
def test_window_kernels_with_unequal_blocks(block_q, block_k):
    q, k, v, w = _operands(4, 2, 32)

    def flash(q, k, v):
        return flash_attention(q, k, v, True, None, block_q, block_k, True, 40)

    def plain(q, k, v):
        return mha_reference(q, k, v, causal=True, window=40)

    for a, b in zip(
        _forward_and_gradients(flash, q, k, v, w),
        _forward_and_gradients(plain, q, k, v, w),
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=2e-5, rtol=2e-5
        )


@pytest.mark.parametrize("window", [SEQ, SEQ + 1])
@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_a_window_that_holds_the_sequence_is_the_dense_kernels(layout, window):
    """Bit for bit, forward and gradients: the same kernels run."""
    heads, kv_heads, width, _ = LAYOUTS[layout]
    q, k, v, w = _operands(heads, kv_heads, width)

    def windowed(q, k, v):
        return flash_attention(q, k, v, True, None, BLOCK, BLOCK, True, window)

    def dense(q, k, v):
        return flash_attention(q, k, v, True, None, BLOCK, BLOCK, True)

    for a, b in zip(
        _forward_and_gradients(windowed, q, k, v, w),
        _forward_and_gradients(dense, q, k, v, w),
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert _kernel_names(windowed, q, k, v) == {"flash_fwd"}


def test_window_kernels_run_under_names_of_their_own():
    q, k, v, w = _operands(2, 1, 32)

    def windowed(q, k, v):
        return jnp.sum(
            flash_attention(q, k, v, True, None, BLOCK, BLOCK, True, 40) * w
        )

    assert _kernel_names(jax.grad(windowed, argnums=(0, 1, 2)), q, k, v) == {
        "swa_fwd", "swa_dq", "swa_dkv"
    }


@pytest.mark.parametrize(
    "chunk_rows,window",
    [(BLOCK, 5), (BLOCK, 40), (2 * BLOCK, 2 * BLOCK), (2 * BLOCK, 100)],
)
def test_the_chunk_stream_starts_at_the_first_live_chunk(
    monkeypatch, chunk_rows, window
):
    """Chunks of one and of two blocks: the grids' innermost dimension is
    the chunks a block can see, counted from its first live one, and a late
    k-block's stream ends with the sequence."""
    heads, kv_heads, width = 4, 2, 32
    monkeypatch.setattr(attention_ops, "_CHUNK_BYTES", chunk_rows * width * 4)
    jax.clear_caches()
    q, k, v, w = _operands(heads, kv_heads, width, seed=3)
    over_k, over_q = attention_ops._window_streams(
        window, SEQ, SEQ, BLOCK, BLOCK, chunk_rows, chunk_rows
    )
    whole = SEQ // chunk_rows
    want = min(whole, -(-(window + BLOCK - 1) // chunk_rows) + 1)
    assert over_k <= want and over_q <= want
    assert window > 40 or over_k < whole

    def flash(q, k, v):
        return flash_attention(q, k, v, True, None, BLOCK, BLOCK, True, window)

    def plain(q, k, v):
        return mha_reference(q, k, v, causal=True, window=window)

    try:
        got = _forward_and_gradients(flash, q, k, v, w)
    finally:
        jax.clear_caches()  # traced with the small chunk
    for a, b in zip(got, _forward_and_gradients(plain, q, k, v, w)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=2e-5, rtol=2e-5
        )


def test_every_key_behind_the_window_is_masked_or_skipped():
    """Scores of 6 * (row - column): huge behind the window, so one
    unmasked element there takes a row's whole softmax, in the forward and
    in each backward kernel."""
    seq, window = 128, 24
    position = np.arange(seq, dtype=np.float32)
    q = np.zeros((1, seq, 1, 8), np.float32)
    k = np.zeros((1, seq, 1, 8), np.float32)
    q[0, :, 0, 0], q[0, :, 0, 1] = 6.0 * position, -6.0
    k[0, :, 0, 0], k[0, :, 0, 1] = 1.0, position
    rng = np.random.RandomState(5)
    v = rng.randn(1, seq, 1, 8).astype(np.float32)
    w = jnp.asarray(rng.randn(1, seq, 1, 8), jnp.float32)

    def flash(q, k, v):
        return flash_attention(q, k, v, True, 1.0, 32, 32, True, window)

    def plain(q, k, v):
        return mha_reference(q, k, v, causal=True, sm_scale=1.0, window=window)

    got = _forward_and_gradients(flash, q, k, v, w)
    # row r sees its oldest key, column r - window + 1, with weight ~1
    oldest = np.maximum(np.arange(seq) - window + 1, 0)
    np.testing.assert_allclose(
        np.asarray(got[0])[0, :, 0], v[0, oldest, 0], atol=3e-2, rtol=0
    )
    for a, b in zip(got, _forward_and_gradients(plain, q, k, v, w)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=2e-3, rtol=2e-3
        )


# ---- the block plan ---------------------------------------------------------


def _brute_force_plan(seq, block_q, block_k, window):
    rows, columns = np.arange(seq)[:, None], np.arange(seq)[None, :]
    seen = (rows >= columns) & (rows - columns < window)
    live = masked = 0
    for i in range(0, seq, block_q):
        for j in range(0, seq, block_k):
            block = seen[i:i + block_q, j:j + block_k]
            live += block.any()
            masked += block.any() and not block.all()
    return live, masked, (seq // block_q) * (seq // block_k) - live


@pytest.mark.parametrize(
    "seq,block_q,block_k,window",
    [
        (128, 16, 16, 1), (128, 16, 16, 5), (128, 16, 16, 16),
        (128, 16, 16, 17), (128, 16, 16, 40), (128, 16, 16, 48),
        (128, 32, 8, 40), (128, 8, 32, 40), (128, 16, 16, 127),
        (128, 16, 16, 128), (2304, 384, 384, 1000), (96, 96, 96, 10),
        (96, 1, 1, 7),
    ],
)
def test_block_plan_with_a_window_matches_a_brute_force_count(
    seq, block_q, block_k, window
):
    """The blocks that hold a visible pair, and of them those that hold a
    hidden one; dK/dV walks the same grid by columns with bounds of its own,
    and a chunk stream cuts neither count."""
    want = _brute_force_plan(seq, block_q, block_k, window)
    assert flash_block_plan(seq, seq, block_q, block_k, True, window) == want
    num_q, num_k = seq // block_q, seq // block_k
    live = masked = 0
    for c0 in range(0, seq, block_k):
        first, full_from = attention_ops._q_blocks_visible(
            c0, block_k, 0, block_q, num_q
        )
        full_from, edge_from, end = attention_ops._q_blocks_in_window(
            c0, block_k, 0, block_q, num_q, window, first, full_from
        )
        assert 0 <= first <= full_from <= edge_from <= end <= num_q
        live += end - first
        masked += (full_from - first) + (end - edge_from)
    assert (live, masked, num_q * num_k - live) == want
    # forward and dQ, two chunks of blocks where the blocks divide so
    chunk = num_k // 2 * block_k if num_k % 2 == 0 else seq
    live = masked = 0
    for r0 in range(0, seq, block_q):
        for c0 in range(0, seq, chunk):
            full, upto = attention_ops._k_blocks_visible(
                r0, block_q, c0, block_k, chunk // block_k
            )
            behind, edge, full = attention_ops._k_blocks_in_window(
                r0, block_q, c0, block_k, window, full, upto
            )
            assert 0 <= behind <= edge <= full <= upto
            live += upto - behind
            masked += (edge - behind) + (upto - full)
    assert (live, masked, num_q * num_k - live) == want


def test_block_plan_of_the_window_cell():
    """``trinity_mini_seq16384``: a window layer visits 150 of the 528 blocks
    a full layer visits (28.4%; the pairs are 23.4%), at 8,192 tokens 70 of
    136; without a window the plan is what it was."""
    assert flash_block_plan(16384, 16384, 512, 512, True) == (528, 32, 496)
    assert flash_block_plan(16384, 16384, 512, 512, True, 2048) == (150, 60, 874)
    assert flash_block_plan(8192, 8192, 512, 512, True, 2048) == (70, 28, 186)
    assert flash_block_plan(8192, 8192, 512, 512, True) == (136, 16, 120)
    # two chunks of 4,096 rows a q-block or k-block at most, of the four
    assert attention_ops._window_streams(
        2048, 16384, 16384, 512, 512, 4096, 4096
    ) == (2, 2)


def test_block_plan_of_a_window_of_1024_at_16384():
    """``mellum2_seq16384``: at the 512-square blocks the kernels pick, a
    window of 1,024 is two whole blocks, so a row of blocks is the block on
    the diagonal, one whole block and one the trailing edge crosses: 93 live
    of 1,024 (17.6% of a full layer's 528), 32 + 30 masked, and no block is
    crossed twice (``window >= block_q + block_k - 1``), so the edge block is
    worked in halves like the diagonal one."""
    assert flash_block_plan(16384, 16384, 512, 512, True, 1024) == (93, 62, 931)
    assert attention_ops._crossings(512, 512, 1024) == (True, attention_ops._EDGE)
    pieces = attention_ops._block_pieces(512, 512, attention_ops._EDGE, True, 1024)
    assert [piece[:4] for piece in pieces] == [
        (0, 256, 0, 512), (256, 512, 256, 512)
    ]
    # one chunk of 4,096 rows holds a q-block's three live k-blocks but for
    # the q-blocks at a chunk's first two blocks: two chunks at most
    assert attention_ops._window_streams(
        1024, 16384, 16384, 512, 512, 4096, 4096
    ) == (2, 2)
    # blocks of 1,024 would be crossed by the diagonal AND the trailing edge
    assert attention_ops._crossings(1024, 1024, 1024) == (
        attention_ops._BOTH, attention_ops._BOTH
    )
    assert flash_block_plan(16384, 16384, 1024, 1024, True, 1024) == (31, 31, 225)


@pytest.mark.parametrize(
    "block,crossed_twice", [(1024, True), (512, False)],
    ids=["blocks_crossed_twice", "the_cells_blocks"],
)
def test_a_window_of_1024_at_published_widths(block, crossed_twice):
    """A window of 1,024 keys over 128-wide heads in groups of four, three
    windows long: at 1,024-square blocks every live block off the first is
    crossed by the diagonal and by the trailing edge and is computed whole
    under both conditions; at the cell's 512 the edge block is halved.
    Values and the three gradients against the masked plain form."""
    q, k, v, w = _operands(4, 1, 128, seq=3072)
    both = attention_ops._crossings(block, block, 1024)[0] == attention_ops._BOTH
    assert both == crossed_twice

    def flash(q, k, v):
        return flash_attention(q, k, v, True, None, block, block, True, 1024)

    def plain(q, k, v):
        return mha_reference(q, k, v, causal=True, window=1024)

    got = _forward_and_gradients(flash, q, k, v, w)
    want = _forward_and_gradients(plain, q, k, v, w)
    for a, b in zip(got, want):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-5, rtol=5e-5
        )


# ---- the dispatch -----------------------------------------------------------


def test_a_window_needs_causal_self_attention():
    q, k, v, _ = _operands(2, 2, 32)
    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, k, v, False, None, BLOCK, BLOCK, True, 40)
    with pytest.raises(ValueError, match="causal"):
        mha_reference(q, k, v, causal=False, window=40)
    with pytest.raises(ValueError, match="at least 1"):
        flash_attention(q, k, v, True, None, BLOCK, BLOCK, True, 0)


def test_a_window_across_sp_is_not_built():
    from elasticdl_tpu.parallel.mesh import MeshConfig

    mesh = MeshConfig.from_string("sp=4").create(devices=jax.devices()[:4])
    set_attention_mesh(mesh)
    q, k, v, _ = _operands(2, 2, 32)
    with pytest.raises(NotImplementedError, match="window"):
        attention(q, k, v, causal=True, window=40)


def test_attention_dispatch_hands_the_window_to_the_kernels():
    q, k, v, _ = _operands(4, 2, 32)
    np.testing.assert_allclose(
        np.asarray(attention(q, k, v, causal=True, window=40)),
        np.asarray(mha_reference(q, k, v, causal=True, window=40)),
        atol=2e-5, rtol=2e-5,
    )
    from elasticdl_tpu.parallel.mesh import MeshConfig

    mesh = MeshConfig.from_string("dp=2").create(devices=jax.devices()[:2])
    set_attention_mesh(mesh)
    q, k, v, _ = _operands(4, 2, 32, batch=2)
    np.testing.assert_allclose(
        np.asarray(jax.jit(lambda q, k, v: attention(q, k, v, True, window=40))(q, k, v)),
        np.asarray(mha_reference(q, k, v, causal=True, window=40)),
        atol=2e-5, rtol=2e-5,
    )


# ---- the layer's parts around the kernels -------------------------------------


def _layer(**fields):
    from elasticdl_tpu.layers.attention import MultiHeadSelfAttention

    return MultiHeadSelfAttention(
        num_heads=4, num_kv_heads=2, head_dim=16, causal=True, use_bias=False,
        **fields,
    )


def test_output_gate_multiplies_the_merged_heads_before_the_output_projection():
    x = jnp.asarray(np.random.RandomState(0).randn(2, 32, 24), jnp.float32)
    layer = _layer(output_gate=True)
    params = layer.init(jax.random.PRNGKey(0), x)["params"]
    assert params["gate"]["kernel"].shape == (24, 4, 16)

    def heads(name):
        return jnp.einsum("bse,ehd->bshd", x, params[name]["kernel"])

    u = mha_reference(heads("query"), heads("key"), heads("value"), causal=True)
    want = jnp.einsum(
        "bshd,hde->bse", u * jax.nn.sigmoid(heads("gate")), params["out"]["kernel"]
    )
    np.testing.assert_allclose(
        np.asarray(layer.apply({"params": params}, x)), np.asarray(want),
        atol=1e-5, rtol=1e-5,
    )


def test_window_layer_counts_the_block_plan_it_ran():
    from elasticdl_tpu.telemetry import router_load

    x = jnp.asarray(np.random.RandomState(0).randn(2, 64, 24), jnp.float32)
    layer = _layer(window=10)
    variables = layer.init(jax.random.PRNGKey(0), x)
    _, sown = layer.apply(
        {"params": variables["params"]}, x, mutable=[router_load.BLOCK_PLAN]
    )
    # one block of 64: visited and masked, nothing to skip; 2 rows x 4 heads
    assert router_load.read_block_plan(sown) == {
        "layers": 1, "visited": 8, "masked": 8, "skipped": 0, "skipped_share": 0.0,
    }
    assert router_load.read_block_plan({}) is None
    q = jax.ShapeDtypeStruct((1, 16384, 32, 128), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((1, 16384, 4, 128), jnp.bfloat16)
    assert attention_ops.window_block_plan(q, kv, kv, 2048) == (
        32 * 150, 32 * 60, 32 * 874
    )
    # a full layer sows nothing
    _, sown = _layer().apply(
        {"params": variables["params"]}, x, mutable=[router_load.BLOCK_PLAN]
    )
    assert not sown


def _tiny_lm(**fields):
    from elasticdl_tpu.models import long_seq_transformer as zoo

    return zoo.custom_model(**{
        **dict(
            vocab_size=64, embed_dim=32, num_heads=4, num_kv_heads=2,
            head_dim=16, num_layers=4, layer_pattern="w-*-", norm="rmsnorm",
            use_bias=False, positions="rope", sliding_window=6,
            full_attention_rope=False, qk_norm_per_head=True, output_gate=True,
            norm_outputs=True, scale_embedding=True, mlp="swiglu", mlp_width=48,
        ),
        **fields,
    })


def test_block_has_a_norm_on_each_parts_output_and_positions_by_kind():
    model = _tiny_lm()
    tokens = np.random.default_rng(0).integers(64, size=(2, 24)).astype(np.int32)
    variables = model.init(jax.random.PRNGKey(0), {"tokens": tokens})
    params = variables["params"]
    for block in ("block_0", "block_1", "block_2", "block_3"):
        assert {"RMSNorm_0", "RMSNorm_1"} <= set(params[block])
    assert set(variables["block_plan"]) == {"block_0"}  # the one window part
    assert model.apply({"params": params}, {"tokens": tokens}).shape == (2, 24, 64)
    # a full layer has no position signal: through one of them the last
    # token's logits are those of the SET of tokens before it
    fields = dict(layer_pattern="*-", num_layers=2, sliding_window=0)
    model = _tiny_lm(**fields)
    params = model.init(jax.random.PRNGKey(0), {"tokens": tokens})["params"]
    swapped = tokens.copy()
    swapped[:, [0, 5]] = tokens[:, [5, 0]]

    def last_logits_moved(model):
        moved = model.apply({"params": params}, {"tokens": swapped}) - model.apply(
            {"params": params}, {"tokens": tokens}
        )
        return float(jnp.max(jnp.abs(moved[:, -1])))

    assert last_logits_moved(model) < 1e-4
    assert last_logits_moved(_tiny_lm(**fields, full_attention_rope=True)) > 1e-3
    # and a window part turns q and k by their positions whatever the flag says
    assert last_logits_moved(_tiny_lm(**{**fields, "layer_pattern": "w-", "sliding_window": 64})) > 1e-3


def test_embedding_is_scaled_by_the_root_of_its_width():
    tokens = np.random.default_rng(0).integers(64, size=(1, 8)).astype(np.int32)
    scaled, plain = _tiny_lm(num_layers=0, layer_pattern=""), _tiny_lm(
        num_layers=0, layer_pattern="", scale_embedding=False
    )
    params = scaled.init(jax.random.PRNGKey(0), {"tokens": tokens})["params"]
    bigger = {**params, "tok_embed": {
        "embedding": params["tok_embed"]["embedding"] * 32**0.5
    }}
    np.testing.assert_allclose(
        np.asarray(scaled.apply({"params": params}, {"tokens": tokens})),
        np.asarray(plain.apply({"params": bigger}, {"tokens": tokens})),
        atol=1e-5, rtol=1e-5,
    )


def test_a_window_letter_needs_a_window():
    model = _tiny_lm(sliding_window=0)
    tokens = np.zeros((1, 8), np.int32)
    with pytest.raises(ValueError, match="needs a window"):
        model.init(jax.random.PRNGKey(0), {"tokens": tokens})


def test_decoding_through_a_window_layer_masks_the_caches_prefix():
    """One token at a time through the caches against the full forward pass:
    the window layer's cache holds every key and attends to the last 6."""
    model = _tiny_lm()
    tokens = np.random.default_rng(1).integers(64, size=(2, 16)).astype(np.int32)
    params = model.init(jax.random.PRNGKey(0), {"tokens": tokens})["params"]
    want = model.apply({"params": params}, {"tokens": tokens})
    decoder = model.clone(decode=True, max_decode_len=16)
    cache = decoder.init(jax.random.PRNGKey(0), {"tokens": tokens[:, :1]})["cache"]
    step = jax.jit(
        lambda cache, token: decoder.apply(
            {"params": params, "cache": cache}, {"tokens": token},
            mutable=["cache"],
        )
    )
    got = []
    for t in range(16):
        logits, mutated = step(cache, tokens[:, t:t + 1])
        cache = mutated["cache"]
        got.append(logits[:, 0])
    np.testing.assert_allclose(
        np.asarray(jnp.stack(got, axis=1)), np.asarray(want), atol=2e-4, rtol=2e-4
    )
    # and it is the window that is honoured: without it the logits differ
    dense = _tiny_lm(layer_pattern="*-*-", sliding_window=0, full_attention_rope=True)
    assert float(jnp.max(jnp.abs(
        dense.apply({"params": params}, {"tokens": tokens}) - want
    ))) > 1e-3
