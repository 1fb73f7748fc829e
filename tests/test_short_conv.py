"""ops/short_conv.py (the gated short convolution's pass as a kernel pair,
interpreted on the CPU) against its plain form in layers/short_conv.py:
values, all four operands' gradients and the taps', the boundaries between a
batch's sequences, the shapes the kernels refuse, the layer end to end, the
block's letter for it and its refusal to decode."""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elasticdl_tpu.layers import short_conv
from elasticdl_tpu.layers.attention import LAYER_KINDS, TransformerBlock
from elasticdl_tpu.ops import mamba_passes
from elasticdl_tpu.ops import short_conv as short_conv_ops

# float32 differs by the order of its sums; bfloat16 by a rounding of each
# output (tests/test_mamba_passes.py's limits)
LIMIT = {jnp.float32: 2e-5, jnp.bfloat16: 0.03}
NAMES = ("b", "c", "x", "kernel")


def _arrays(batch, steps, channels, dtype, taps=3, seed=0):
    rng = np.random.RandomState(seed)
    streams = [
        jnp.asarray(rng.randn(batch, steps, channels), dtype) for _ in range(3)
    ]
    kernel = jnp.asarray(rng.randn(taps, channels) * 0.5, jnp.float32)
    weigh = jnp.asarray(rng.randn(batch, steps, channels), jnp.float32)
    return (*streams, kernel), weigh


def _value_and_grads(function, weigh, *args):
    """The output and the gradients of a weighed sum of it: d_out is
    ``weigh``, so all five of the backward kernel's results are held."""
    out = function(*args)
    grads = jax.grad(
        lambda *a: jnp.sum(weigh * function(*a).astype(jnp.float32)),
        argnums=tuple(range(len(args))),
    )(*args)
    return out, grads


def _scaled_errors(got, want):
    f32 = jnp.float32
    return [
        float(jnp.max(jnp.abs(g.astype(f32) - w.astype(f32)))
              / jnp.max(jnp.abs(w.astype(f32))))
        for g, w in zip(jax.tree_util.tree_leaves(got),
                        jax.tree_util.tree_leaves(want))
    ]


def _kernel_calls(function, *args):
    return str(jax.make_jaxpr(lambda *a: function(*a))(*args)).count(
        "pallas_call"
    )


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize(
    "batch,steps,channels",
    [(1, 32, 2048), (3, 64, 256), (2, 16, 128)],
    ids=["cell_width_one_tile", "three_sequences", "one_strip_of_16"],
)
def test_the_pass_and_its_gradients_match_the_plain_form(
    batch, steps, channels, dtype
):
    args, weigh = _arrays(batch, steps, channels, dtype)
    assert mamba_passes.conv_tile(steps, channels, 3)
    assert _kernel_calls(short_conv.short_conv, *args) == 1
    got = _value_and_grads(short_conv.short_conv, weigh, *args)
    want = _value_and_grads(short_conv.gated_short_conv, weigh, *args)
    assert got[0].dtype == dtype
    assert [g.dtype for g in got[1]] == [dtype] * 3 + [jnp.float32]
    errors = _scaled_errors(got, want)
    assert max(errors) < LIMIT[dtype], dict(zip(("out",) + NAMES, errors))


@pytest.mark.parametrize(
    "rows,lanes", [(16, 128), (32, 256)], ids=["four_tiles", "two_tiles"]
)
def test_a_sequence_of_several_tiles_sees_its_own_past_and_no_other(
    rows, lanes, monkeypatch
):
    """Batch 3, 64 steps in tiles of 16 or 32 rows: the halo carries a
    tile's last two ``B * X`` rows into the next, forwards, and the
    gradient's first rows back into the tile before; a sequence's first
    steps see zeros, not the end of the sequence before it in the batch (a
    wrong halo between sequences fails both the comparison and the
    perturbation below)."""
    monkeypatch.setattr(mamba_passes, "_CONV_ROWS", rows)
    monkeypatch.setattr(mamba_passes, "_CONV_LANES", lanes)
    channels = 256
    args, weigh = _arrays(3, 64, channels, jnp.float32, seed=1)
    assert mamba_passes.conv_tile(64, channels, 3) == (rows, lanes)
    got = _value_and_grads(short_conv.short_conv, weigh, *args)
    want = _value_and_grads(short_conv.gated_short_conv, weigh, *args)
    errors = _scaled_errors(got, want)
    assert max(errors) < 2e-5, dict(zip(("out",) + NAMES, errors))
    # the first sequence's last steps change: no other sequence's output
    # or gradient does
    b, c, x, kernel = args
    moved = _value_and_grads(
        short_conv.short_conv, weigh, b.at[0, 40:].add(1.0), c, x, kernel
    )
    for after, before in zip(
        [moved[0], *moved[1][:3]], [got[0], *got[1][:3]]
    ):
        np.testing.assert_array_equal(after[1:], before[1:])
    np.testing.assert_array_equal(moved[0][0, :40], got[0][0, :40])
    assert float(jnp.max(jnp.abs(moved[0][0, 40:] - got[0][0, 40:]))) > 0.1
    # a sequence's first output is the last tap alone: zeros lie before it
    first = c[:, 0] * (b[:, 0] * x[:, 0] * kernel[2])
    np.testing.assert_allclose(got[0][:, 0], first, rtol=1e-5, atol=1e-6)
    # and its last step's product reaches no gradient but its own taps'
    d_z_last = weigh[:, -1] * c[:, -1] * kernel[2]
    np.testing.assert_allclose(
        got[1][0][:, -1], d_z_last * x[:, -1], rtol=1e-5, atol=1e-6
    )


def test_the_taps_are_in_the_order_of_a_torch_conv1d():
    """``kernel[k - 1 - s]`` reads ``s`` steps back: an impulse comes out
    at its own step under the last tap, one step later under the middle."""
    channels = 128
    ones = jnp.ones((1, 16, channels), jnp.float32)
    impulse = jnp.zeros((1, 16, channels), jnp.float32).at[0, 4].set(1.0)
    kernel = jnp.asarray(
        np.outer([100.0, 10.0, 1.0], np.ones(channels)), jnp.float32
    )
    for function in (short_conv.short_conv, short_conv.gated_short_conv):
        out = function(impulse, ones, ones, kernel)
        np.testing.assert_array_equal(
            out[0, :, 0], [0, 0, 0, 0, 1, 10, 100] + [0] * 9
        )


@pytest.mark.parametrize(
    "steps,channels,taps",
    [(20, 256, 3), (32, 192, 3), (32, 64, 3), (32, 128, 17)],
    ids=["rows_no_tile_divides", "lanes_and_a_half", "tiny_model",
         "more_taps_than_the_halo"],
)
def test_a_shape_the_kernels_refuse_takes_the_plain_form(steps, channels, taps):
    args, _ = _arrays(2, steps, channels, jnp.float32, taps=taps, seed=3)
    assert mamba_passes.conv_tile(steps, channels, taps) is None
    assert _kernel_calls(short_conv.short_conv, *args) == 0
    np.testing.assert_array_equal(
        short_conv.short_conv(*args), short_conv.gated_short_conv(*args)
    )


def test_the_layer_is_two_projections_around_the_pass(monkeypatch):
    layer = short_conv.ShortConv(taps=3, dtype=jnp.bfloat16)
    u = jnp.asarray(np.random.RandomState(4).randn(2, 32, 128), jnp.float32)
    variables = layer.init(jax.random.PRNGKey(0), u)
    shapes = jax.tree_util.tree_map(np.shape, variables["params"])
    assert shapes == {
        "in_proj": {"kernel": (128, 384)}, "conv_kernel": (3, 128),
        "out_proj": {"kernel": (128, 128)},
    }
    out = layer.apply(variables, u)
    assert out.dtype == jnp.bfloat16 and out.shape == u.shape
    p = variables["params"]
    h = u.astype(jnp.bfloat16)
    b, c, x = jnp.split(h @ p["in_proj"]["kernel"].astype(jnp.bfloat16), 3, -1)
    want = short_conv.gated_short_conv(b, c, x, p["conv_kernel"]) @ p[
        "out_proj"
    ]["kernel"].astype(jnp.bfloat16)
    assert _scaled_errors([out], [want])[0] < 0.03
    # the kernels and the plain form give the same layer
    monkeypatch.setattr(mamba_passes, "conv_tile", lambda *a: None)
    assert _scaled_errors([out], [layer.apply(variables, u)])[0] < 0.03


def test_the_block_has_a_letter_for_it_and_refuses_to_decode():
    assert LAYER_KINDS["c"] == "conv"
    x = jnp.zeros((1, 16, 128), jnp.float32)
    block = TransformerBlock(kind="c", norm="rmsnorm", use_bias=False)
    variables = block.init(jax.random.PRNGKey(0), x)
    assert set(variables["params"]) == {"RMSNorm_0", "conv"}
    assert block.apply(variables, x).shape == x.shape
    with pytest.raises(NotImplementedError, match="short-convolution"):
        TransformerBlock(
            kind="c", norm="rmsnorm", use_bias=False, decode=True,
            max_decode_len=16,
        ).init(jax.random.PRNGKey(0), x[:, :1], decode_pos=jnp.int32(0))


def test_the_kernels_names_are_no_other_kernels():
    names = (short_conv_ops.SHORT_CONV_FWD, short_conv_ops.SHORT_CONV_BWD)
    assert names == ("short_conv_fwd", "short_conv_bwd")
    for name in names:
        assert not name.startswith(
            ("ssd_", "flash_", "expert_gmm_", "mamba_conv", "swa_", "dsa_")
        )
