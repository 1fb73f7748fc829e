"""The rotary positions' single-pass kernel (``ops/rotary.py``) against the
plain ``jnp`` form ``layers/attention.py::rope`` was before the kernel
(kept here as the reference, line for line), and the choice between the two
that ``rope`` makes from its input's shape."""

import contextlib
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elasticdl_tpu.layers.attention import rope
from elasticdl_tpu.ops import on_mesh, rotary
from elasticdl_tpu.parallel.mesh import MeshConfig

SECTIONS = (16, 24, 24)


def reference_rope(x, positions, theta, interleave=False, sections=()):
    """``rope`` as it stood before the kernel: split, turn, concatenate."""
    half = x.shape[-1] // 2
    rate = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    if positions.ndim == 3:
        component = jnp.repeat(
            jnp.arange(len(sections)), jnp.asarray(sections),
            total_repeat_length=half,
        )
        of_frequency = jnp.take(
            positions.astype(jnp.float32), component, axis=1
        ).transpose(0, 2, 1)
        angles = (of_frequency * rate)[:, :, None, :]
        cos, sin = jnp.cos(angles), jnp.sin(angles)
        x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
        return jnp.concatenate(
            [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
        ).astype(x.dtype)
    angles = positions.astype(jnp.float32)[:, None] * rate[None, :]
    cos = jnp.cos(angles)[None, :, None, :]
    sin = jnp.sin(angles)[None, :, None, :]
    if interleave:
        pairs = x.astype(jnp.float32).reshape(*x.shape[:-1], half, 2)
        x1, x2 = pairs[..., 0], pairs[..., 1]
    else:
        x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    turned = [x1 * cos - x2 * sin, x2 * cos + x1 * sin]
    if interleave:
        return jnp.stack(turned, axis=-1).reshape(x.shape).astype(x.dtype)
    return jnp.concatenate(turned, axis=-1).astype(x.dtype)


def _operands(batch, seq, heads, width, dtype, components):
    keys = jax.random.split(jax.random.PRNGKey(heads + seq), 3)
    x, g = (
        jax.random.normal(
            key, (batch, seq, heads, width), jnp.float32
        ).astype(dtype)
        for key in keys[:2]
    )
    if not components:
        return x, g, jnp.arange(seq), ()
    # three distinct components: a frame, a row and a column of their own
    positions = jax.random.randint(keys[2], (batch, 3, seq), 0, 4 * seq)
    assert not np.array_equal(positions[:, 0], positions[:, 1])
    assert not np.array_equal(positions[:, 1], positions[:, 2])
    sections = tuple(n * width // 128 for n in SECTIONS)
    return x, g, positions, sections


def _both_ways(form, positions, sections=(), **kwargs):
    def run(x, g):
        out, pull = jax.vjp(
            lambda x: form(x, positions, 1e4, sections=sections, **kwargs), x
        )
        return out, pull(g)[0]

    return jax.jit(run)


@pytest.mark.parametrize("components", [False, True], ids=["index", "mrope"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "heads,seq,width",
    # 32 : 4 heads of 128 as the cells have them, neither row count a
    # multiple of the 512-row tile; a head of two lane tiles
    [(32, 656, 128), (4, 1040, 128), (3, 528, 256)],
)
def test_the_kernel_is_the_plain_form(heads, seq, width, dtype, components):
    """Values bit for bit.  The gradient to within one rounding of a product:
    it is the same two products and one sum an element, but XLA's CPU
    backend contracts one of the two products into the sum (a fused
    multiply-add, unrounded) and which one depends on the order the
    expression was written in, so interpreted here the two forms differ in
    the last place where the chip, which has no such instruction, gives the
    same bits (``benchmarks/rope_sweep.py`` prints that comparison)."""
    x, g, positions, sections = _operands(
        2, seq, heads, width, dtype, components
    )
    assert rotary.rotate_tile(x.shape) is not None
    out, d_x = _both_ways(rope, positions, sections)(x, g)
    want, want_d_x = _both_ways(reference_rope, positions, sections)(x, g)
    assert out.dtype == want.dtype == dtype and d_x.dtype == dtype
    np.testing.assert_array_equal(
        np.asarray(out, np.float32), np.asarray(want, np.float32)
    )
    g32 = np.abs(np.asarray(g, np.float32))
    # one float32 rounding of either product (|cos|, |sin| <= 1) ...
    slack = 2.0**-23 * (g32 + np.roll(g32, width // 2, axis=-1))
    if dtype == jnp.bfloat16:  # ... which can move the result one bf16 unit
        slack = slack + 2.0**-7 * np.abs(np.asarray(want_d_x, np.float32))
    difference = np.abs(
        np.asarray(d_x, np.float32) - np.asarray(want_d_x, np.float32)
    )
    assert (difference <= slack).all(), difference.max()
    # and nearly everywhere none
    assert (difference == 0).mean() > 0.7


def _calls(form, x, positions, rule=1e4, **kwargs):
    """The kernel calls in ``form``'s jaxpr, by name."""
    text = str(
        jax.make_jaxpr(lambda x: form(x, positions, rule, **kwargs))(x)
    )
    return [
        name for name in (rotary.ROPE_FWD, rotary.ROPE_BWD) if name in text
    ]


def _lowered(form, x, positions, **kwargs):
    def rotated(x):
        return form(x, positions, 1e4, **kwargs)

    return jax.jit(rotated).lower(x).as_text()


def reference_tail(x, positions, theta, interleave=False, sections=(), skip=0):
    """A head whose last lanes rotate, as ``LatentSelfAttention`` spelled
    it before the kernel took the whole head: slice, turn, join."""
    if not skip:
        return reference_rope(x, positions, theta, interleave, sections)
    turned = reference_rope(
        x[..., skip:], positions, theta, interleave, sections
    )
    return jnp.concatenate([x[..., :skip], turned], axis=-1)


# the shapes the kernel takes since PR 63 beside the one it took before:
# (heads, width, rope()'s keywords, positions of three components).  A head
# of 64 is taken where it is its array's one head; several of them keep the
# plain form (``test_every_other_shape_lowers_to_the_text_it_did``)
FORMS = {
    # rotate-half with the partner 32 lanes away inside a lane tile: the
    # indexer's one key, with ``sections`` and without
    "half64_one_head": (1, 64, {}, False),
    "half64_one_head_sections": (1, 64, {}, True),
    # adjacent pairs: the one rotary key latent attention's heads share
    "pairs64_shared_head": (1, 64, {"interleave": True}, False),
    "pairs128": (4, 128, {"interleave": True}, False),
    # the whole 192-wide head, its first 128 lanes passing through
    "tail_pairs": (8, 192, {"interleave": True, "skip": 128}, False),
    "tail_two_tiles_sections": (2, 256, {"skip": 128}, True),
    # what it took before
    "half128": (4, 128, {}, False),
}


def _in(dtype, array):
    """``array`` (float64) rounded to float32 and then to ``dtype``, as
    float32 for comparing."""
    return np.asarray(
        jnp.asarray(array.astype(np.float32)).astype(dtype), np.float32
    )


def _or_one_product_contracted(got, want, operand, tables, partner, dtype):
    """``got`` is ``want`` bit for bit, or where it is not, it is ``operand *
    C + partner(operand) * S`` with one of the two products left unrounded
    in the sum: XLA's CPU backend contracts a multiply into the add after it
    (a fused multiply-add) in the interpreted kernel or in the plain form,
    whichever way the expression came out, where the chip, which has no
    such instruction, gives the same bits.  The three ways are evaluated
    here in float64, in which a product of two float32 numbers is exact.
    Returns the share of elements that are ``want`` bit for bit."""
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    cos, sin = (np.asarray(table, np.float64) for table in tables)
    x = np.asarray(operand, np.float64)
    ours, partners = x * cos, partner(x) * sin

    def rounded(product):
        return product.astype(np.float32).astype(np.float64)

    ways = [
        _in(dtype, a + b)
        for a, b in (
            (rounded(ours), rounded(partners)),
            (ours, rounded(partners)),
            (rounded(ours), partners),
        )
    ]
    equal = got == want
    explained = equal | np.logical_or.reduce([got == way for way in ways])
    assert explained.all(), np.abs(got - want)[~explained].max()
    return equal.mean()


def _form_operands(form, rows, dtype, batch=2):
    """``_operands`` of a form of ``FORMS`` and ``rope``'s keywords for it,
    the sections of three components in proportion to the rotating lanes."""
    heads, width, kwargs, components = FORMS[form]
    x, g, positions, _ = _operands(batch, rows, heads, width, dtype, components)
    if components:
        turning = width - kwargs.get("skip", 0)
        kwargs = {
            **kwargs, "sections": tuple(n * turning // 128 for n in SECTIONS)
        }
    return x, g, positions, kwargs


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("form", sorted(FORMS))
def test_every_form_of_the_kernel_is_the_plain_form(form, dtype):
    """Values and gradient bit for bit, but for the elements where XLA's
    CPU backend contracted a product into the sum on one side and not on
    the other, which are held to exactly that
    (``_or_one_product_contracted``; on the chip both are equal bit for bit
    everywhere, ``benchmarks/rope_sweep.py``), and the lanes that pass
    through untouched both ways; 656 rows, no multiple of the row tile."""
    x, g, positions, kwargs = _form_operands(form, 656, dtype)
    skip = kwargs.get("skip", 0)
    interleave = kwargs.get("interleave", False)
    assert rotary.rotate_tile(x.shape, skip) is not None
    assert _calls(rope, x, positions, **kwargs) == [rotary.ROPE_FWD]
    out, d_x = _both_ways(rope, positions, **kwargs)(x, g)
    want, want_d_x = _both_ways(reference_tail, positions, **kwargs)(x, g)
    assert out.dtype == want.dtype == dtype and d_x.dtype == dtype
    for ours, theirs in ((out, want), (d_x, want_d_x)):
        np.testing.assert_array_equal(
            np.asarray(ours[..., :skip], np.float32),
            np.asarray(theirs[..., :skip], np.float32),
        )
    turning = x.shape[-1] - skip
    cos, sin = (
        np.asarray(table)[..., None, :]  # over the heads
        for table in rotary.tables(
            positions, 1e4, turning, kwargs.get("sections", ()), interleave
        )
    )

    def partner(x):
        # the pair's other lane, or the lane half the rotating lanes away
        if interleave:
            pairs = x.reshape(*x.shape[:-1], turning // 2, 2)
            return pairs[..., ::-1].reshape(x.shape)
        return np.roll(x, turning // 2, axis=-1)

    # the values nearly everywhere the plain form's own bits ...
    assert _or_one_product_contracted(
        out[..., skip:], want[..., skip:], x[..., skip:], (cos, sin),
        partner, dtype,
    ) > 0.999
    # ... and the gradient, the same body with S negated, mostly
    assert _or_one_product_contracted(
        d_x[..., skip:], want_d_x[..., skip:], g[..., skip:], (cos, -sin),
        partner, dtype,
    ) > 0.7


@pytest.mark.parametrize("form", sorted(FORMS))
def test_every_form_keeps_the_positions_alone_and_calls_once_a_pass(form):
    x, g, positions, kwargs = _form_operands(form, 512, jnp.bfloat16, batch=1)
    _, pull = jax.vjp(lambda x: rope(x, positions, 1e4, **kwargs), x)
    kept = jax.tree_util.tree_leaves(pull)
    assert max(leaf.size for leaf in kept) <= positions.size
    text = str(jax.make_jaxpr(lambda g: pull(g)[0])(g))
    assert text.count(rotary.ROPE_BWD) == 1 and rotary.ROPE_FWD not in text


def _sp_mesh():
    return on_mesh.attention_mesh_scope(
        MeshConfig.from_string("dp=1,sp=2").create(devices=jax.devices()[:2])
    )


@pytest.mark.parametrize(
    "shape,kwargs,mesh",
    [
        ((2, 1, 4, 128), {}, None),  # a decode step
        ((2, 1, 16, 64), {}, None),
        ((2, 1, 4, 192), {"interleave": True, "skip": 128}, None),
        ((2, 64, 4, 128), {}, None),  # a small model: less than a tile of rows
        ((2, 496, 16, 64), {}, None),
        ((2, 496, 32, 64), {"interleave": True}, None),
        ((2, 496, 32, 192), {"interleave": True, "skip": 128}, None),
        # several heads narrower than a tile: the indexer's queries,
        # LFM2's q and k, a rotary slice handed alone
        ((1, 1024, 16, 64), {}, None),
        ((4, 1024, 8, 64), {}, None),
        ((1, 1024, 32, 64), {"interleave": True}, None),
        ((1, 1024, 2, 192), {}, None),  # a head that is no whole number of tiles
        ((1, 1024, 4, 96), {}, None),  # nor a half tile
        ((1, 1024, 4, 96), {"interleave": True}, None),
        # lanes that pass through and are no whole tiles
        ((1, 1024, 4, 128), {"skip": 64}, None),
        ((1, 1024, 4, 160), {"interleave": True, "skip": 96}, None),
        # a sequence sharded over sp
        ((1, 1024, 1, 64), {}, _sp_mesh),
        ((1, 1024, 1, 64), {"interleave": True}, _sp_mesh),
        ((1, 1024, 32, 192), {"interleave": True, "skip": 128}, _sp_mesh),
    ],
    ids=[
        "decode", "decode_width64", "decode_tail", "few_rows",
        "few_rows_width64", "few_rows_pairs", "few_rows_tail",
        "heads_of_64", "grouped_heads_of_64", "paired_heads_of_64", "width192",
        "width96", "width96_pairs", "skip64", "skip96", "sp_width64",
        "sp_pairs", "sp_tail",
    ],
)
def test_every_other_shape_lowers_to_the_text_it_did(shape, kwargs, mesh):
    """What keeps the plain form lowers to the parent's text: the rotating
    lanes through the lines ``rope`` was before the kernel, a tail sliced
    out, turned and joined back as ``LatentSelfAttention`` did it."""
    x = jnp.zeros(shape, jnp.bfloat16)
    positions = jnp.arange(shape[1])
    with mesh() if mesh else contextlib.nullcontext():
        assert _calls(rope, x, positions, **kwargs) == []
        assert _lowered(rope, x, positions, **kwargs) == _lowered(
            reference_tail, x, positions, **kwargs
        )


def test_components_on_a_narrow_head_lower_to_the_text_they_did():
    """The indexer's queries at two tiles of rows, 16 heads of 64 under
    three components' sections: several heads narrower than a lane tile
    keep the plain form (its one key of 64 takes the kernel:
    ``test_every_form_of_the_kernel_is_the_plain_form``)."""
    x = jnp.zeros((2, 1024, 16, 64), jnp.bfloat16)
    positions = jnp.zeros((2, 3, 1024), jnp.int32)
    kwargs = {"sections": (8, 12, 12)}
    assert _calls(rope, x, positions, **kwargs) == []
    assert _lowered(rope, x, positions, **kwargs) == _lowered(
        reference_rope, x, positions, **kwargs
    )


# sha256 of what ``rope`` and its gradient lowered to at the parent of PR 63
# (f1ce426, interpreted kernels, which are ordinary HLO here), by
# ``_text_both_ways`` below: the shapes that took the kernel before PR 63
# lower to the same text after it
PARENTS_TEXT = {
    "index": "a64e9fd17e2cf81145223cae029cbd34b57a6b77ddd911747bbea6ca642fa714",
    "mrope": "82ed6cd972823faa5da57145f76637e7f0497d47cdd8c06c6e43d4958bc7550c",
    "two_tiles": "5a47c4b61dad8efdb5d188a990be6ca12bef1c3c7f28221e0f00bf52f01c08cb",
}


def _text_both_ways(shape, positions, **kwargs):
    x = jnp.zeros(shape, jnp.bfloat16)

    def run(x, g):
        out, pull = jax.vjp(lambda x: rope(x, positions, 1e4, **kwargs), x)
        return out, pull(g)[0]

    return jax.jit(run).lower(x, x).as_text()


@pytest.mark.parametrize(
    "case,shape,components",
    [
        ("index", (1, 1024, 4, 128), False),
        ("mrope", (2, 1024, 32, 128), True),
        ("two_tiles", (1, 528, 3, 256), False),
    ],
)
def test_whole_tiles_by_halves_lower_to_the_parents_text(
    case, shape, components
):
    positions, kwargs = jnp.arange(shape[1]), {}
    if components:
        positions = jnp.zeros((shape[0], 3, shape[1]), jnp.int32)
        kwargs = {"sections": tuple(n * shape[3] // 128 for n in SECTIONS)}
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    assert _calls(rope, x, positions, **kwargs) == [rotary.ROPE_FWD]
    text = _text_both_ways(shape, positions, **kwargs)
    assert hashlib.sha256(text.encode()).hexdigest() == PARENTS_TEXT[case]


@pytest.mark.parametrize(
    "shape,skip,tile",
    [
        # the cells' shapes since PR 63, (batch, tokens, heads, width)
        ((1, 8192, 32, 192), 128, (512, 4)),  # latent attention's q whole
        ((1, 8192, 1, 64), 0, (512, 1)),  # its one shared rotary key
        ((1, 16384, 1, 64), 0, (512, 1)),  # the indexer's one key
        ((1, 16384, 16, 64), 0, None),  # its queries: several narrow heads
        ((4, 4096, 32, 64), 0, None),  # LFM2's q
        ((4, 4096, 8, 64), 0, None),  # and k
        ((1, 8192, 32, 64), 0, None),  # a rotary slice handed alone
        ((1, 8192, 32, 192), 0, None),  # 192 lanes that all rotate
        ((1, 8192, 32, 192), 64, None),  # lanes passing through: no tile
    ],
)
def test_the_one_chooser_of_what_the_kernel_takes(shape, skip, tile):
    assert rotary.rotate_tile(shape, skip) == tile


@pytest.mark.parametrize("heads", [32, 4])
def test_the_cells_shape_holds_the_kernel(heads):
    x = jax.ShapeDtypeStruct((1, 16384, heads, 128), jnp.bfloat16)
    positions = jnp.arange(16384)
    assert _calls(rope, x, positions) == [rotary.ROPE_FWD]
    assert rotary.rotate_tile(x.shape) == (512, min(heads, 8))

    def pulled(x):
        return jax.vjp(lambda x: rope(x, positions, 1e4), x)[1](x)

    text = str(jax.make_jaxpr(pulled)(x))
    assert text.count(rotary.ROPE_FWD) == text.count(rotary.ROPE_BWD) == 1


def test_the_residuals_are_the_positions_alone():
    """Neither an activation nor a table waits for the backward pass."""
    x = jnp.zeros((1, 1024, 4, 128), jnp.bfloat16)
    positions = jnp.zeros((1, 3, 1024), jnp.int32)
    _, pull = jax.vjp(
        lambda x: rope(x, positions, 1e4, sections=SECTIONS), x
    )
    kept = jax.tree_util.tree_leaves(pull)
    assert kept and max(leaf.size for leaf in kept) <= positions.size
    # (the plain form keeps its cosines and sines)
    _, pull = jax.vjp(
        lambda x: reference_rope(x, positions, 1e4, sections=SECTIONS), x
    )
    kept = jax.tree_util.tree_leaves(pull)
    assert max(leaf.size for leaf in kept) > positions.size


def test_a_sequence_sharded_over_sp_keeps_the_plain_form():
    x = jnp.zeros((1, 1024, 4, 128), jnp.bfloat16)
    mesh = MeshConfig.from_string("dp=1,sp=2").create(
        devices=jax.devices()[:2]
    )
    with on_mesh.attention_mesh_scope(mesh):
        assert _calls(rope, x, jnp.arange(1024)) == []
    assert _calls(rope, x, jnp.arange(1024)) == [rotary.ROPE_FWD]


# ---- a rule that is no power law: YaRN (docs/designs/yarn_rope.md) ---------------

# Mellum2-12B-A2.5B's ``rope_parameters``, as config.json gives them
MELLUM_ROPE = {
    "full_attention": {
        "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
        "original_max_position_embeddings": 8192, "beta_fast": 32,
        "beta_slow": 1, "attention_factor": 1.2772588722239782,
    },
    "sliding_attention": {"rope_type": "default", "rope_theta": 500000},
}
YARN = rotary.rule_of(MELLUM_ROPE["full_attention"])


def test_a_published_group_gives_its_rule():
    assert rotary.rule_of(MELLUM_ROPE["sliding_attention"]) == 500000.0
    assert YARN == rotary.Yarn(500000.0, 16.0, 8192, 32.0, 1.0, 1.2772588722239782)
    hash(YARN)  # a static argument of the kernel's custom_vjp
    # no attention_factor given: 0.1 ln(factor) + 1, which is what this row states
    unstated = rotary.Yarn(500000.0, 16.0, 8192)
    cos, = rotary.scaled(unstated, jnp.ones(()))
    assert float(cos) == pytest.approx(1.2772588722239782, rel=1e-7)
    assert rotary.scaled(1e4, cos) == (cos,)
    with pytest.raises(ValueError, match="rope_type"):
        rotary.rule_of({"rope_type": "longrope", "rope_theta": 1e4})


def test_yarn_frequencies_of_this_row_by_hand():
    """128-wide heads, theta 500,000, factor 16 from 8,192 positions, beta 32
    and 1.  The pair that turns ``r`` times over 8,192 positions is ``128
    ln(8192 / (2 pi r)) / (2 ln 500000)``: 18.08 for 32 turns, 34.98 for
    one, so ``low`` 18 and ``high`` 35.  Pairs 0..18 keep ``500000^(-i/64)``,
    pairs 35..63 take a sixteenth of it, pair ``i`` between blends the two
    with ``g = (i - 18) / 17``: ``rate_i (1 - 15 g / 16)``."""
    ln = np.log(500000.0)
    assert 128 * np.log(8192 / (2 * np.pi * 32)) / (2 * ln) == pytest.approx(18.081, abs=1e-3)
    assert 128 * np.log(8192 / (2 * np.pi * 1)) / (2 * ln) == pytest.approx(34.984, abs=1e-3)
    pair = np.arange(64)
    power_law = np.exp(-pair / 64 * ln)
    g = np.clip((pair - 18) / 17, 0.0, 1.0)
    want = power_law * (1.0 - g * 15 / 16)
    got = np.asarray(rotary.rates(YARN, 64), np.float64)
    np.testing.assert_allclose(got, want, rtol=2e-6)
    # a few of them written out: the last kept, the first blended, the last
    # blended, the first and the last interpolated
    by_hand = {
        0: 1.0, 18: 2.495541e-2, 19: 1.920802e-2, 34: 1.104087e-4,
        35: 4.778106e-5, 63: 1.534463e-7,
    }
    for i, rate in by_hand.items():
        assert got[i] == pytest.approx(rate, rel=2e-6), i
    assert (got[:19] == np.asarray(rotary.rates(500000.0, 64))[:19]).all()
    np.testing.assert_allclose(got[35:] * 16, power_law[35:], rtol=2e-6)
    # the power law itself is the expression it was
    assert (
        np.asarray(rotary.rates(1e4, 64))
        == np.asarray(1e4 ** (-jnp.arange(64, dtype=jnp.float32) / 64))
    ).all()


def yarn_by_the_formulas(x, positions):
    """HF's ``_compute_yarn_parameters`` and ``apply_rotary_pos_emb`` for
    this row, written out: nothing of ``ops/rotary.py``."""
    d = x.shape[-1]
    base = 500000.0 ** (np.arange(0, d, 2, dtype=np.float64) / d)
    low = np.floor(d * np.log(8192 / (32 * 2 * np.pi)) / (2 * np.log(500000.0)))
    high = np.ceil(d * np.log(8192 / (1 * 2 * np.pi)) / (2 * np.log(500000.0)))
    ramp = np.clip((np.arange(d // 2) - low) / (high - low), 0, 1)
    inv_freq = (1 / (16 * base)) * ramp + (1 / base) * (1 - ramp)
    freqs = np.asarray(positions, np.float64)[:, None] * inv_freq[None, :]
    emb = np.concatenate([freqs, freqs], axis=-1)[None, :, None, :]
    x = np.asarray(x, np.float64)
    x1, x2 = np.split(x, 2, axis=-1)
    turned = np.concatenate([-x2, x1], axis=-1)
    factor = 0.1 * np.log(16.0) + 1.0
    return x * np.cos(emb) * factor + turned * np.sin(emb) * factor


@pytest.mark.parametrize(
    "seq,heads", [(64, 4), (656, 32)], ids=["plain", "kernel"]
)
def test_yarn_through_both_forms_is_the_formulas(seq, heads):
    """Positions past the 8,192 the rule extends from, where the blended
    frequencies differ most; float32 angles at positions of 10^4 are good to
    about 1e-3 of a turn, which is the tolerance."""
    x, g, _, _ = _operands(1, seq, heads, 128, jnp.float32, False)
    positions = 9000 + jnp.arange(seq)
    assert (rotary.rotate_tile(x.shape) is not None) == (seq >= 512)
    assert _calls(rope, x, positions, rule=YARN) == (
        [rotary.ROPE_FWD] if seq >= 512 else []
    )
    out, pull = jax.vjp(lambda x: rope(x, positions, YARN), x)
    np.testing.assert_allclose(
        np.asarray(out), yarn_by_the_formulas(x, positions), atol=5e-3
    )
    # the map is linear: its transpose on g against the formulas' own, which
    # is the rotation by the negated angle
    d_x = pull(g)[0]
    want = yarn_by_the_formulas(g, -np.asarray(positions))
    np.testing.assert_allclose(np.asarray(d_x), want, atol=5e-3)
    # without the ramp, and without the factor, it is another function
    plain = rope(x, positions, 500000.0)
    assert float(jnp.max(jnp.abs(out - plain * 1.2772588722239782))) > 0.1
    unscaled = rope(x, positions, YARN._replace(attention_factor=1.0))
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(unscaled) * 1.2772588722239782, rtol=1e-5,
        atol=1e-6,
    )


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_the_kernel_under_yarn_is_the_plain_form_under_yarn(dtype):
    """Values bit for bit and the gradient to a rounding of a product, as
    under the power law: the rule changes the tables, the kernel is the one
    kernel."""
    from elasticdl_tpu.layers.attention import rope_plain

    x, g, _, _ = _operands(2, 656, 32, 128, dtype, False)
    positions = 8000 + jnp.arange(656)

    def both_ways(form):
        def run(x, g):
            out, pull = jax.vjp(lambda x: form(x, positions, YARN), x)
            return out, pull(g)[0]
        return jax.jit(run)(x, g)

    out, d_x = both_ways(rope)
    want, want_d_x = both_ways(rope_plain)
    np.testing.assert_array_equal(
        np.asarray(out, np.float32), np.asarray(want, np.float32)
    )
    g32 = 1.2772588722239782 * np.abs(np.asarray(g, np.float32))
    slack = 2.0**-22 * (g32 + np.roll(g32, 64, axis=-1))
    if dtype == jnp.bfloat16:
        slack = slack + 2.0**-7 * np.abs(np.asarray(want_d_x, np.float32))
    difference = np.abs(
        np.asarray(d_x, np.float32) - np.asarray(want_d_x, np.float32)
    )
    assert (difference <= slack).all(), difference.max()


def test_a_full_layers_scores_carry_the_factor_squared():
    """cos and sin both carry ``attention_factor``, so q and k each do and a
    score carries its square; and a score still depends on ``t - s`` alone."""
    x, y, _, _ = _operands(1, 32, 2, 128, jnp.float32, False)
    unscaled = YARN._replace(attention_factor=1.0)

    def scores(rule, offset):
        positions = offset + jnp.arange(32)
        return jnp.einsum(
            "bqhd,bkhd->bhqk", rope(x, positions, rule), rope(y, positions, rule)
        )

    np.testing.assert_allclose(
        np.asarray(scores(YARN, 0)),
        1.2772588722239782**2 * np.asarray(scores(unscaled, 0)), rtol=2e-5,
        atol=1e-5,
    )
    np.testing.assert_allclose(
        np.asarray(scores(YARN, 0)), np.asarray(scores(YARN, 5000)), atol=0.05
    )


def _two_kind_lm(**fields):
    from elasticdl_tpu.models import long_seq_transformer as zoo

    return zoo.custom_model(**{
        **dict(
            vocab_size=64, embed_dim=32, num_heads=4, num_kv_heads=2,
            head_dim=16, num_layers=4, layer_pattern="w-*-", norm="rmsnorm",
            use_bias=False, positions="rope", rope_theta=100,
            sliding_window=6, qk_norm_per_head=True, mlp="swiglu", mlp_width=48,
            # theta 100 from 16 positions over 8 pairs: the ramp ends at
            # pair 2 (16 ln(16 / 2 pi) / (2 ln 100) = 1.62) and starts at 0
            rope_parameters={
                "full_attention": {
                    **MELLUM_ROPE["full_attention"], "rope_theta": 100,
                    "original_max_position_embeddings": 16,
                },
                "sliding_attention": {"rope_type": "default", "rope_theta": 100},
            },
        ),
        **fields,
    })


def test_the_models_field_gives_each_kind_of_layer_its_rule():
    """``rope_parameters`` reaches the attention parts through
    ``PART_FIELDS``: the window part turns by the base, the full part by
    YaRN; a model that names no rule turns every layer by ``rope_theta``,
    and a kind that is not named does too."""
    tokens = np.random.default_rng(0).integers(64, size=(2, 24)).astype(np.int32)
    model = _two_kind_lm()
    params = model.init(jax.random.PRNGKey(0), {"tokens": tokens})["params"]

    def logits(model):
        return np.asarray(model.apply({"params": params}, {"tokens": tokens}))

    both = logits(model)
    none = logits(_two_kind_lm(rope_parameters=None))
    assert np.abs(both - none).max() > 1e-3
    window_alone = {"sliding_attention": model.rope_parameters["sliding_attention"]}
    np.testing.assert_array_equal(
        logits(_two_kind_lm(rope_parameters=window_alone)), none
    )
    # the full part's rule alone moves what the full part alone computes
    full_alone = {"full_attention": model.rope_parameters["full_attention"]}
    np.testing.assert_array_equal(logits(_two_kind_lm(rope_parameters=full_alone)), both)
    # a rule with nothing to turn, or for a layer that is told not to, is refused
    for fields, refusal in (
        ({"rope_parameters": {"chunked_attention": window_alone["sliding_attention"]}},
         "rope_parameters names"),
        ({"positions": "none"}, "rotary positions alone"),
        ({"full_attention_rope": False}, "gives them none"),
    ):
        with pytest.raises(ValueError, match=refusal):
            _two_kind_lm(**fields).init(jax.random.PRNGKey(0), {"tokens": tokens})


def test_decoding_under_yarn_is_the_full_forward_pass():
    """One token at a time through the caches: the one new position is
    turned by its layer's rule (the tables are made from the decode
    cursor)."""
    model = _two_kind_lm()
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
