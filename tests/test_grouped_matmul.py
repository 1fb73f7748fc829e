"""ops/grouped_matmul.py: the layout and the three kernels (interpreted on
the CPU) against ``einsum`` on ragged groups, an empty group included."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elasticdl_tpu.ops import grouped_matmul as g

GROUPS, INNER, COLS = 5, 16, 24


def ragged_case(sizes, tile_rows, seed=0):
    rng = np.random.RandomState(seed)
    ids = np.repeat(np.arange(len(sizes)), sizes).astype(np.int32)
    rng.shuffle(ids)
    pairs = len(ids)
    layout = g.group_layout(
        jnp.asarray(ids), GROUPS, tile_rows, g.num_rows(pairs, GROUPS, tile_rows)
    )
    x = jnp.asarray(rng.randn(pairs, INNER), jnp.float32)
    w = jnp.asarray(rng.randn(GROUPS, INNER, COLS), jnp.float32)
    return ids, layout, x, w


def einsum_reference(ids, x, w):
    """Each pair's row times its own group's matrix."""
    return jnp.einsum("pk,pkn->pn", x, w[jnp.minimum(ids, GROUPS - 1)]) * (
        ids < GROUPS
    )[:, None]


SIZES = {
    "ragged_with_an_empty_group": [7, 0, 19, 1, 13],
    "all_in_one_group": [0, 0, 40, 0, 0],
    "whole_tiles": [8, 8, 8, 8, 8],
}


@pytest.mark.parametrize("tile_rows", [8, 16])
@pytest.mark.parametrize("sizes", SIZES.values(), ids=SIZES.keys())
def test_layout_holds_every_pair_once(sizes, tile_rows):
    ids, layout, _, _ = ragged_case(sizes, tile_rows)
    pairs = len(ids)
    row_pair = np.asarray(layout.row_pair)
    held = row_pair[row_pair < pairs]
    assert sorted(held) == list(range(pairs))  # none dropped, none twice
    assert len(row_pair) == g.num_rows(pairs, GROUPS, tile_rows)
    np.testing.assert_array_equal(row_pair[np.asarray(layout.pair_row)], np.arange(pairs))
    # a tile's rows all belong to the tile's group; every group has a tile
    tile_group = np.asarray(layout.tile_group)
    assert set(range(GROUPS)) <= set(tile_group)
    assert (np.diff(tile_group) >= 0).all()
    for tile, group in enumerate(tile_group):
        rows = row_pair[tile * tile_rows : (tile + 1) * tile_rows]
        assert all(ids[p] == group for p in rows[rows < pairs])
        if group == GROUPS:
            assert (rows == pairs).all()
    # the stable sort keeps a group's pairs in their order
    for group in range(GROUPS):
        members = held[ids[held] == group]
        assert (np.diff(members) > 0).all()


@pytest.mark.parametrize("sizes", SIZES.values(), ids=SIZES.keys())
def test_grouped_matmul_and_both_gradients_match_einsum(sizes):
    ids, layout, x, w = ragged_case(sizes, 8)
    pairs = len(ids)
    row = jnp.minimum(layout.row_pair, pairs - 1)

    def ours(x, w):
        out = g.grouped_matmul(x[row], w, layout.tile_group, tile_rows=8)
        return jnp.sum(jnp.sin(out[layout.pair_row]))

    def reference(x, w):
        return jnp.sum(jnp.sin(einsum_reference(ids, x, w)))

    got = jax.value_and_grad(ours, argnums=(0, 1))(x, w)
    want = jax.value_and_grad(reference, argnums=(0, 1))(x, w)
    # x's gradient flows through the plain gather here, so padding rows'
    # cotangents (zero: they reach no output) are summed in harmlessly
    for a, b in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)
    # an empty group's weight gradient is written, as zeros
    for group, size in enumerate(sizes):
        if size == 0:
            assert not np.asarray(got[1][1][group]).any()


def test_pairs_of_no_group_get_no_row_and_tail_tiles_read_zero():
    """Ids equal to ``num_groups`` (another rank's experts) are left out;
    tiles past the last group come back as zeros."""
    ids = jnp.asarray([0, 5, 2, 5, 5, 1, 0, 5], jnp.int32)
    layout = g.group_layout(ids, GROUPS, 8, g.num_rows(8, GROUPS, 8))
    row_pair = np.asarray(layout.row_pair)
    assert sorted(row_pair[row_pair < 8]) == [0, 2, 5, 6]
    rows = jnp.ones((row_pair.shape[0], INNER), jnp.float32)
    w = jnp.ones((GROUPS, INNER, COLS), jnp.float32)
    out = np.asarray(g.grouped_matmul(rows, w, layout.tile_group, tile_rows=8))
    tail = np.repeat(np.asarray(layout.tile_group) == GROUPS, 8)
    assert tail.any() and not out[tail].any()
    assert (out[~tail] == INNER).all()


def test_weight_gradient_is_float32_for_float32_weights_of_bfloat16_rows():
    ids, layout, x, w = ragged_case([7, 0, 19, 1, 13], 16)
    row = jnp.minimum(layout.row_pair, len(ids) - 1)
    rows = x[row].astype(jnp.bfloat16)

    def loss(w):
        out = g.grouped_matmul(rows, w, layout.tile_group, tile_rows=16)
        return jnp.sum(out.astype(jnp.float32))

    out = g.grouped_matmul(rows, w, layout.tile_group, tile_rows=16)
    assert out.dtype == jnp.bfloat16
    assert jax.grad(loss)(w).dtype == jnp.float32


def test_rows_that_are_not_whole_tiles_are_refused():
    with pytest.raises(ValueError, match="tiles"):
        g.grouped_matmul(
            jnp.zeros((20, 8)), jnp.zeros((2, 8, 8)), jnp.zeros((2,), jnp.int32),
            tile_rows=8,
        )


def test_kernels_carry_the_names_the_benchmark_reads():
    """``perf/expert_rooflines.py`` finds the kernels on the op line by
    these names."""
    assert (g.GMM_FWD, g.GMM_DX, g.GMM_DW) == (
        "expert_gmm_fwd", "expert_gmm_dx", "expert_gmm_dw"
    )
    ids, layout, x, w = ragged_case([8, 8, 8, 8, 8], 8)
    rows = x[jnp.minimum(layout.row_pair, len(ids) - 1)]
    text = str(
        jax.make_jaxpr(
            jax.grad(
                lambda r, w: jnp.sum(
                    g.grouped_matmul(r, w, layout.tile_group, tile_rows=8, interpret=False)
                ),
                argnums=(0, 1),
            )
        )(rows, w)
    )
    for name in (g.GMM_FWD, g.GMM_DX, g.GMM_DW):
        assert name in text


LADDERS = {
    # pairs, groups here, groups routed over, tile rows -> rungs (rows)
    "the_cell_8_of_128": ((49152, 8, 128, 128), (7168, 14336, 50176)),
    "the_cell_16_of_256": ((65536, 16, 256, 128), (10240, 20480, 67584)),
    "ep4_of_64": ((65536, 16, 64, 128), (34816, 67584)),
    "every_group_here": ((65536, 64, 64, 128), (73728,)),
    "half_the_groups": ((1000, 4, 8, 8), (1032,)),  # twice a half is all
    "a_quarter": ((1000, 2, 8, 8), (520, 1016)),
}


@pytest.mark.parametrize("case", LADDERS.values(), ids=LADDERS.keys())
def test_ladder_is_derived_from_the_share_of_the_groups(case):
    """The low rung holds twice the balanced share of the pairs plus a tile
    a group, the next twice its rows; the last
    rung is ``num_rows`` of all pairs; a layout that holds every routed
    group has that one rung."""
    (pairs, groups, routed, tile_rows), rungs = case
    assert g.ladder(pairs, groups, routed, tile_rows) == rungs
    assert rungs[-1] == g.num_rows(pairs, groups, tile_rows)
    if len(rungs) > 1:
        held = -(-g.LOW_RUNG_SHARES * pairs * groups // routed)
        assert rungs[0] == (-(-held // tile_rows) + groups) * tile_rows
    for below, above in zip(rungs, rungs[1:-1]):
        assert above == 2 * below
    assert all(r % tile_rows == 0 for r in rungs) and list(rungs) == sorted(set(rungs))


@pytest.mark.parametrize("tile_rows", [8, 16])
@pytest.mark.parametrize(
    "sizes",
    [[7, 0, 19, 1, 13], [0, 0, 24, 0, 0], [8, 8, 8, 8, 8], [0, 0, 0, 0, 0]],
    ids=["ragged", "one_group", "whole_tiles", "no_pair_here"],
)
def test_layout_at_a_rung_holds_every_grouped_pair_once(sizes, tile_rows):
    """200 pairs routed over 40 groups of which 5 are laid out here: the
    sort is made once, the tiles needed are read from its counts, and the
    layout at the smallest rung that fits holds each grouped pair once, in
    the rows the full rung gives it."""
    rng = np.random.RandomState(2)
    ids = np.full((200,), GROUPS, np.int32)
    ids[: sum(sizes)] = np.repeat(np.arange(GROUPS), sizes)
    rng.shuffle(ids)
    ids = jnp.asarray(ids)
    order = g.group_order(ids, GROUPS)
    np.testing.assert_array_equal(order.sizes, sizes)
    needed = int(g.tiles_needed(order.sizes, tile_rows))
    assert needed == sum(max(-(-s // tile_rows), 1) for s in sizes)
    rungs = g.ladder(200, GROUPS, 40, tile_rows)
    assert len(rungs) >= 2 and needed * tile_rows <= rungs[0]
    low = g.group_layout(ids, GROUPS, tile_rows, rungs[0], order)
    full = g.group_layout(ids, GROUPS, tile_rows, rungs[-1])
    # laid out from the rows' side, the same rows without the pairs' index
    by_rows = g.group_layout(ids, GROUPS, tile_rows, rungs[0], order, by_rows=True)
    assert by_rows.pair_row is None
    np.testing.assert_array_equal(by_rows.row_pair, low.row_pair)
    np.testing.assert_array_equal(by_rows.tile_group, low.tile_group)
    assert low.row_pair.shape == (rungs[0],)
    assert low.tile_group.shape == (rungs[0] // tile_rows,)
    row_pair = np.asarray(low.row_pair)
    grouped = np.flatnonzero(np.asarray(ids) < GROUPS)
    assert sorted(row_pair[row_pair < 200]) == list(grouped)
    np.testing.assert_array_equal(row_pair[np.asarray(low.pair_row)[grouped]], grouped)
    # the low rung is the head of the full one: the same rows and tiles
    np.testing.assert_array_equal(low.pair_row, full.pair_row)
    np.testing.assert_array_equal(row_pair, np.asarray(full.row_pair)[: rungs[0]])
    np.testing.assert_array_equal(
        low.tile_group, np.asarray(full.tile_group)[: rungs[0] // tile_rows]
    )
    assert (np.asarray(full.row_pair)[rungs[0] :] == 200).all()


# --- the rows of a buffer added into their tokens (sum_by_token) ---


def scatter_add(rows, row_token, tokens, row_weight=None):
    """The plain form the kernel replaced (layers/moe.py up to PR 48):
    float32 products added into a float32 array a row at a time."""
    values = rows.astype(jnp.float32)
    if row_weight is not None:
        values = values * row_weight[:, None]
    return jnp.zeros((tokens, rows.shape[1]), jnp.float32).at[row_token].add(
        values, mode="drop"
    ).astype(rows.dtype)


def _rows_in_group_order(tokens, slots, sizes, tile_rows, tail, seed):
    """A routing of ``sizes[g]`` tokens to group ``g`` laid out by
    ``group_layout`` in a rung with ``tail`` tiles of no group after the
    last: ``row_token`` (the rows of each group in token order and padded
    to whole tiles, token ``tokens`` on a padding row, so the buffer as a
    whole is out of token order) and the layout's ``token_spans``.  A token
    is in ``slots`` groups at most, every seventh token in none."""
    rng = np.random.RandomState(seed)
    groups = len(sizes)
    free = np.full(tokens, slots)
    free[::7] = 0
    group_ids = np.full((tokens, slots), groups, np.int32)
    for group, size in enumerate(sizes):
        # (the tokens with most slots left first, so the sizes always fit)
        chosen = np.lexsort((rng.rand(tokens), -free))[:size]
        assert free[chosen].all()
        free[chosen] -= 1
        group_ids[chosen, free[chosen]] = group
    group_ids = jnp.asarray(group_ids.reshape(-1))
    order = g.group_order(group_ids, groups)
    rows = (int(g.tiles_needed(order.sizes, tile_rows)) + tail) * tile_rows
    layout = g.group_layout(group_ids, groups, tile_rows, rows, order, True)
    row_token = np.where(
        layout.row_pair < tokens * slots, layout.row_pair // slots, tokens
    ).astype(np.int32)
    return row_token, g.token_spans(group_ids, order.sizes, tokens, tile_rows)


SUMS = {
    # tokens, slots, rows of each group, tile rows, tail tiles of no group
    "tokens_with_no_row_the_others_with_every_slot": (40, 3, [11, 34, 7, 34, 16], 8, 0),
    "padding_rows_inside_and_after_the_last_group": (64, 2, [5, 0, 9, 1], 8, 3),
    "tokens_and_rows_that_end_mid_tile": (200, 4, [150, 37, 99, 1, 64], 8, 1),
    "most_tiles_of_tokens_with_no_row": (700, 2, [3, 20], 8, 0),
    "no_row_held": (130, 2, [0, 0, 0], 16, 2),
    "whole_tiles_of_held_rows": (256, 4, [128, 128, 128, 128], 128, 0),
    # nine groups are three blocks of the kernel's staged rows: past the
    # first round the middle one holds nothing and is skipped
    "two_groups_on_most_tokens_seven_on_few": (
        256, 4, [219, 2, 0, 7, 1, 0, 3, 5, 219], 8, 1
    ),
}


@pytest.mark.parametrize("weighted", [True, False], ids=["weights", "weight_1"])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "f32"])
@pytest.mark.parametrize("case", SUMS.values(), ids=SUMS.keys())
def test_sum_by_token_is_the_scatter_add(case, dtype, weighted):
    """The kernel against the plain scatter-add on rows in group order:
    bfloat16 rows to the last bit (their products with a float32 weight are
    summed in float32 on both sides and rounded once; a token has few
    enough rows here that the order of its terms rounds alike), float32
    rows to a float32 rounding of a token's sum."""
    tokens, slots, sizes, tile_rows, tail = case
    row_token, spans = _rows_in_group_order(
        tokens, slots, sizes, tile_rows, tail, 1
    )
    count = len(row_token)
    held = row_token < tokens
    assert not np.all(np.diff(row_token[held]) >= 0) or sum(map(bool, sizes)) < 2
    rng = np.random.RandomState(2)
    rows = jnp.asarray(rng.randn(count, 48), dtype)
    # weights that bfloat16 would round: 16 more bits than it keeps
    weight = jnp.asarray(rng.rand(count) + 2.0**-20, jnp.float32) if weighted else None
    got = jax.jit(lambda r, t, w: g.sum_by_token(r, t, tokens, spans, w))(
        rows, jnp.asarray(row_token), weight
    )
    want = scatter_add(rows, jnp.asarray(row_token), tokens, weight)
    assert got.shape == (tokens, 48) and got.dtype == dtype
    counts = np.bincount(row_token[held], minlength=tokens)
    assert counts[0] == 0 and counts.max() <= slots
    if case is SUMS["tokens_with_no_row_the_others_with_every_slot"]:
        assert set(counts) == {0, slots}
    np.testing.assert_array_equal(np.asarray(got)[counts == 0], 0)
    if dtype == jnp.bfloat16:
        # one rounding of a float32 sum: at most the neighbouring bfloat16
        # where the terms' order moved the sum across a rounding boundary
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            rtol=2.0**-7, atol=0,
        )
        assert np.mean(np.asarray(got) == np.asarray(want)) > 0.99
    else:
        bound = 4e-7 * float(jnp.abs(want).max())
        np.testing.assert_allclose(got, want, rtol=0, atol=bound)


def test_sum_by_token_rounds_neither_a_weight_nor_a_partial_sum():
    """Rows and weights chosen so that every product and sum is exact in
    float32 and none in bfloat16: a weight rounded to bfloat16 before the
    product, or a partial sum rounded on the way, gives another result."""
    tokens, width = 24, 32
    # two groups of a tile of four rows each, and a tile of no group
    row_token = jnp.asarray([5, 9, 24, 24, 0, 5, 9, 24, 24, 24, 24, 24], jnp.int32)
    group_ids = jnp.full((tokens, 2), 2, jnp.int32)
    group_ids = group_ids.at[jnp.asarray([5, 9]), 0].set(0)
    group_ids = group_ids.at[jnp.asarray([0, 5, 9]), 1].set(1).reshape(-1)
    spans = g.token_spans(group_ids, jnp.asarray([2, 3]), tokens, 4)
    rows = jnp.full((12, width), 3.0, jnp.bfloat16)
    # 1 + 2^-10 and 2^-9 + 2^-18: both lose their low bit in bfloat16
    weight = jnp.asarray(
        [1 + 2.0**-10, 2.0**-9 + 2.0**-18] * 6, jnp.float32
    )
    got = g.sum_by_token(rows, row_token, tokens, spans, weight).astype(jnp.float32)
    want = scatter_add(rows, row_token, tokens, weight).astype(jnp.float32)
    np.testing.assert_array_equal(got, want)
    # float32 rows: the sum of token 5, exact, has bits bfloat16 drops
    rows32 = rows.astype(jnp.float32)
    got32 = g.sum_by_token(rows32, row_token, tokens, spans, weight)
    np.testing.assert_array_equal(
        got32, scatter_add(rows32, row_token, tokens, weight)
    )
    token_5 = 3 * (1 + 2.0**-10 + 2.0**-9 + 2.0**-18)
    assert float(got32[5, 0]) == token_5
    assert float(jnp.asarray(token_5, jnp.bfloat16)) != token_5


def test_the_sum_has_a_name_of_its_own_on_the_op_line():
    """The trace's breakdown and ``op_scopes``' table show the kernel by
    this name; ``perf/expert_rooflines.py`` reads the grouped matmuls by
    theirs and must not take it for one of them."""
    import re

    from perf import expert_rooflines

    assert g.ROWS_SUM == "expert_rows_sum"
    assert not re.search(expert_rooflines.EXPERT_KERNELS, g.ROWS_SUM)
    text = str(
        jax.make_jaxpr(
            lambda r, t, s: g.sum_by_token(r, t, 16, (s, s), interpret=False)
        )(
            jnp.zeros((32, 128), jnp.bfloat16), jnp.zeros((32,), jnp.int32),
            jnp.zeros((1, 2), jnp.int32),
        )
    )
    assert g.ROWS_SUM in text and "scatter" not in text
