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
    layout = g.group_layout(jnp.asarray(ids), GROUPS, tile_rows)
    pairs = len(ids)
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
    layout = g.group_layout(ids, GROUPS, 8)
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
