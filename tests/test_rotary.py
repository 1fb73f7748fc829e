"""The rotary positions' single-pass kernel (``ops/rotary.py``) against the
plain ``jnp`` form ``layers/attention.py::rope`` was before the kernel
(kept here as the reference, line for line), and the choice between the two
that ``rope`` makes from its input's shape."""

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


def _both_ways(form, positions, sections):
    def run(x, g):
        out, pull = jax.vjp(
            lambda x: form(x, positions, 1e4, sections=sections), x
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


def _calls(form, x, positions, **kwargs):
    """The kernel calls in ``form``'s jaxpr, by name."""
    text = str(
        jax.make_jaxpr(lambda x: form(x, positions, 1e4, **kwargs))(x)
    )
    return [
        name for name in (rotary.ROPE_FWD, rotary.ROPE_BWD) if name in text
    ]


def _lowered(form, x, positions, **kwargs):
    def rotated(x):
        return form(x, positions, 1e4, **kwargs)

    return jax.jit(rotated).lower(x).as_text()


@pytest.mark.parametrize(
    "shape,kwargs",
    [
        ((1, 1024, 16, 64), {}),  # the indexer's: a partner 32 lanes away
        ((1, 1024, 4, 128), {"interleave": True}),  # adjacent pairs
        ((2, 1, 4, 128), {}),  # a decode step
        ((2, 64, 4, 128), {}),  # a small model: less than a tile of rows
        ((1, 1024, 2, 192), {}),  # a head that is no whole number of tiles
    ],
    ids=["width64", "interleave", "decode", "few_rows", "width192"],
)
def test_every_other_shape_lowers_to_the_text_it_did(shape, kwargs):
    x = jnp.zeros(shape, jnp.bfloat16)
    positions = jnp.arange(shape[1])
    assert _calls(rope, x, positions, **kwargs) == []
    assert _lowered(rope, x, positions, **kwargs) == _lowered(
        reference_rope, x, positions, **kwargs
    )


def test_components_on_a_narrow_head_lower_to_the_text_they_did():
    x = jnp.zeros((2, 1024, 16, 64), jnp.bfloat16)
    positions = jnp.zeros((2, 3, 1024), jnp.int32)
    kwargs = {"sections": (8, 12, 12)}
    assert _calls(rope, x, positions, **kwargs) == []
    assert _lowered(rope, x, positions, **kwargs) == _lowered(
        reference_rope, x, positions, **kwargs
    )


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
