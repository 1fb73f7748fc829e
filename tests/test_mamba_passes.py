"""ops/mamba_passes.py (the gated group norm and the causal convolution as
single-pass kernels, interpreted on the CPU) against their plain forms in
layers/mamba.py: outputs, every gradient, the sequence boundaries, the shapes
the kernels refuse, and the mixer end to end."""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elasticdl_tpu.layers import mamba
from elasticdl_tpu.ops import mamba_passes

# (batch, steps, d_in, groups, states): the cell's widths at a short sequence
# (z 4,096 wide in 8 groups, xBC 6,144), and a toy of two groups, two
# sequences
SIZES = {
    "cell_widths": (1, 32, 4096, 8, 128),
    "two_groups": (2, 48, 256, 2, 64),
}
# float32 differs by the order of its sums; bfloat16 by a rounding of each
# output (tests/test_ssd.py's limit for bfloat16 inputs)
LIMIT = {jnp.float32: 2e-5, jnp.bfloat16: 0.03}


def _arrays(size, dtype, seed=0):
    batch, steps, inner, groups, states = SIZES[size]
    conv = inner + 2 * groups * states
    rng = np.random.RandomState(seed)
    return {
        "y": jnp.asarray(rng.randn(batch, steps, inner), dtype),
        "z": jnp.asarray(rng.randn(batch, steps, inner), dtype),
        "xbc": jnp.asarray(rng.randn(batch, steps, conv), dtype),
        "scale": jnp.asarray(rng.rand(inner) + 0.5, jnp.float32),
        "kernel": jnp.asarray(rng.randn(4, conv) * 0.5, jnp.float32),
        "bias": jnp.asarray(rng.randn(conv) * 0.1, jnp.float32),
        "weigh_norm": jnp.asarray(rng.randn(batch, steps, inner), jnp.float32),
        "weigh_conv": jnp.asarray(rng.randn(batch, steps, conv), jnp.float32),
    }, (inner, conv, groups)


def _scaled_errors(got, want):
    f32 = jnp.float32
    return [
        float(jnp.max(jnp.abs(g.astype(f32) - w.astype(f32)))
              / jnp.max(jnp.abs(w.astype(f32))))
        for g, w in zip(jax.tree_util.tree_leaves(got),
                        jax.tree_util.tree_leaves(want))
    ]


def _value_and_grads(function, weigh, *args):
    """The output and the gradients of a weighed sum of it."""
    out = jax.jit(function)(*args)
    grads = jax.jit(jax.grad(
        lambda *a: jnp.sum(weigh * function(*a).astype(jnp.float32)),
        argnums=tuple(range(len(args))),
    ))(*args)
    return out, grads


def _no_tiling(monkeypatch):
    monkeypatch.setattr(mamba_passes, "conv_tile", lambda *a: None)
    monkeypatch.setattr(mamba_passes, "gate_norm_tile", lambda *a: None)


def _kernel_calls(function, *args):
    # a new function each time: a trace is kept by the function it was of
    return str(jax.make_jaxpr(lambda *a: function(*a))(*args)).count(
        "pallas_call"
    )


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("size", sorted(SIZES))
def test_gate_norm_and_its_gradients_match_the_plain_form(size, dtype):
    a, (inner, _, groups) = _arrays(size, dtype)
    assert mamba_passes.gate_norm_tile(
        a["y"].shape[0] * a["y"].shape[1], inner, groups
    )

    def kernels(y, z, scale):
        return mamba.gate_norm(y, z, scale, groups, 1e-5)

    def plain(y, z, scale):
        return mamba.gated_group_norm(y, z, scale, groups, 1e-5)

    args = (a["y"], a["z"], a["scale"])
    assert _kernel_calls(kernels, *args) == 1
    got = _value_and_grads(kernels, a["weigh_norm"], *args)
    want = _value_and_grads(plain, a["weigh_norm"], *args)
    assert got[0].dtype == dtype and got[1][1].dtype == dtype
    assert got[1][2].dtype == jnp.float32
    errors = _scaled_errors(got, want)
    assert max(errors) < LIMIT[dtype], errors


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("size", sorted(SIZES))
def test_conv_silu_and_its_gradients_match_the_plain_form(size, dtype):
    a, (inner, conv, _) = _arrays(size, dtype)
    assert mamba_passes.conv_tile(a["y"].shape[1], conv, 4)

    def kernels(x, kernel, bias):
        return mamba.conv_silu(x, kernel, bias)

    def plain(x, kernel, bias):
        # float32 as far as SiLU, as the kernel has it: the plain form in
        # bfloat16 rounds the pre-activation as well
        f32 = jnp.float32
        return nn.silu(mamba.causal_conv(x.astype(f32), kernel, bias)).astype(x.dtype)

    args = (a["xbc"], a["kernel"], a["bias"])
    assert _kernel_calls(kernels, *args) == 1
    got = _value_and_grads(kernels, a["weigh_conv"], *args)
    want = _value_and_grads(plain, a["weigh_conv"], *args)
    assert got[0].dtype == dtype and got[1][0].dtype == dtype
    errors = _scaled_errors(got, want)
    assert max(errors) < LIMIT[dtype], errors
    # within tests/test_ssd.py's bfloat16 limit of the plain form as the
    # mixer ran it before, which rounds twice
    twice = nn.silu(mamba.causal_conv(a["xbc"], a["kernel"], a["bias"]))
    assert _scaled_errors([got[0]], [twice])[0] < 0.03


@pytest.mark.parametrize(
    "rows,lanes", [(16, 128), (32, 512)], ids=["four_tiles", "two_tiles"]
)
def test_a_sequence_of_several_tiles_sees_its_own_past_and_no_other(
    rows, lanes, monkeypatch
):
    """Batch 2, 64 steps in tiles of 16 or 32 rows: the halo carries a
    tile's last ``k - 1`` steps into the next, forwards, and the gradient's
    first rows back into the tile before; a sequence's first steps see
    zeros, not the end of the sequence before it in the batch."""
    monkeypatch.setattr(mamba_passes, "_CONV_ROWS", rows)
    monkeypatch.setattr(mamba_passes, "_CONV_LANES", lanes)
    rng = np.random.RandomState(1)
    conv = 512
    source = jnp.asarray(rng.randn(2, 64, conv), jnp.float32)
    kernel = jnp.asarray(rng.randn(4, conv), jnp.float32)
    bias = jnp.asarray(rng.randn(conv), jnp.float32)
    weigh = jnp.asarray(rng.randn(2, 64, conv), jnp.float32)
    assert mamba_passes.conv_tile(64, conv, 4) == (rows, lanes)

    def kernels(source, kernel, bias):
        return mamba.conv_silu(source, kernel, bias)

    def plain(source, kernel, bias):
        return nn.silu(mamba.causal_conv(source, kernel, bias))

    got = _value_and_grads(kernels, weigh, source, kernel, bias)
    want = _value_and_grads(plain, weigh, source, kernel, bias)
    errors = _scaled_errors(got, want)
    assert max(errors) < 2e-5, errors
    # the first sequence's last steps change: the second's output does not
    moved = kernels(source.at[0, 40:].add(1.0), kernel, bias)
    np.testing.assert_array_equal(moved[1], got[0][1])
    np.testing.assert_array_equal(moved[0, :40], got[0][0, :40])
    assert float(jnp.max(jnp.abs(moved[0, 40:] - got[0][0, 40:]))) > 0.1
    # a step's first output is the last tap alone: zeros lie before it
    first = source[:, 0] * kernel[3] + bias
    np.testing.assert_allclose(got[0][:, 0], nn.silu(first), rtol=1e-5, atol=1e-6)


def test_gate_norm_over_several_row_tiles_adds_up_the_scale_gradient(monkeypatch):
    monkeypatch.setattr(mamba_passes, "_BLOCK", 16 * 128)
    a, (inner, _, groups) = _arrays("two_groups", jnp.float32, seed=2)
    # six row tiles, both groups in a block
    assert mamba_passes.gate_norm_tile(96, inner, groups) == (16, 256)

    def kernels(y, z, scale):
        return mamba.gate_norm(y, z, scale, groups, 1e-5)

    def plain(y, z, scale):
        return mamba.gated_group_norm(y, z, scale, groups, 1e-5)

    args = (a["y"], a["z"], a["scale"])
    errors = _scaled_errors(
        _value_and_grads(kernels, a["weigh_norm"], *args),
        _value_and_grads(plain, a["weigh_norm"], *args),
    )
    assert max(errors) < 2e-5, errors


@pytest.mark.parametrize(
    "steps,inner,groups",
    [(20, 256, 2), (32, 192, 2), (32, 64, 2), (32, 256, 3)],
    ids=["rows_no_tile_divides", "group_of_96_lanes", "tiny_model",
         "groups_that_do_not_divide"],
)
def test_a_shape_the_kernels_refuse_takes_the_plain_form(steps, inner, groups):
    rng = np.random.RandomState(3)
    conv = inner + 32  # no whole number of lane tiles
    assert mamba_passes.gate_norm_tile(2 * steps, inner, groups) is None
    assert mamba_passes.conv_tile(steps, conv, 4) is None
    y = jnp.asarray(rng.randn(2, steps, inner), jnp.float32)
    x = jnp.asarray(rng.randn(2, steps, conv), jnp.float32)
    kernel = jnp.asarray(rng.randn(4, conv), jnp.float32)
    bias = jnp.asarray(rng.randn(conv), jnp.float32)
    assert _kernel_calls(mamba.conv_silu, x, kernel, bias) == 0
    np.testing.assert_array_equal(
        mamba.conv_silu(x, kernel, bias),
        nn.silu(mamba.causal_conv(x, kernel, bias)),
    )
    if inner % groups:
        return  # the plain form itself refuses groups that do not divide
    z = jnp.asarray(rng.randn(2, steps, inner), jnp.float32)
    scale = jnp.asarray(rng.rand(inner) + 0.5, jnp.float32)

    def norm(y, z, scale):
        return mamba.gate_norm(y, z, scale, groups, 1e-5)

    assert _kernel_calls(norm, y, z, scale) == 0
    np.testing.assert_array_equal(
        norm(y, z, scale), mamba.gated_group_norm(y, z, scale, groups, 1e-5)
    )


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
def test_the_mixer_is_unchanged_end_to_end(dtype, monkeypatch):
    """``Mamba2Mixer`` at a size the kernels tile (4 heads of 64 in 2 groups
    of 128 lanes and 128 states, a 768-wide convolution, 2 x 32 steps)
    against the same mixer on the passes' plain forms: the output and every
    parameter's gradient."""
    layer = mamba.Mamba2Mixer(
        num_heads=4, head_dim=64, groups=2, state_size=128, chunk=16,
        dtype=None if dtype == jnp.float32 else dtype,
    )
    rng = np.random.RandomState(4)
    u = jnp.asarray(rng.randn(2, 32, 32), dtype)
    weigh = jnp.asarray(rng.randn(2, 32, 32), jnp.float32)
    params = layer.init(jax.random.PRNGKey(0), u)["params"]
    params["conv_bias"] = jnp.asarray(rng.randn(768) * 0.1, jnp.float32)
    params["norm_scale"] = jnp.asarray(rng.rand(256) + 0.5, jnp.float32)

    def run(params, u):
        return layer.apply({"params": params}, u)

    assert _kernel_calls(run, params, u) == 3  # the two passes and the scan
    got = _value_and_grads(run, weigh, params, u)
    _no_tiling(monkeypatch)
    assert _kernel_calls(run, params, u) == 1
    want = _value_and_grads(run, weigh, params, u)
    errors = _scaled_errors(got, want)
    # (leaves in key order: ``A_log``'s gradient first, a small difference
    # of large sums that tests/test_ssd.py allows 0.08 in bfloat16)
    a_log, rest = errors[1], errors[:1] + errors[2:]
    assert a_log < (1e-4 if dtype == jnp.float32 else 0.08), errors
    assert max(rest) < (1e-4 if dtype == jnp.float32 else 0.03), errors


def test_kernels_carry_their_own_names_and_none_of_the_scan_or_flash_kernels():
    names = (
        mamba_passes.GATE_NORM_FWD, mamba_passes.GATE_NORM_BWD,
        mamba_passes.MAMBA_CONV_FWD, mamba_passes.MAMBA_CONV_BWD,
    )
    assert names == (
        "gate_norm_fwd", "gate_norm_bwd", "mamba_conv_fwd", "mamba_conv_bwd"
    )
    assert not any(
        name.startswith(("ssd_", "flash_", "expert_gmm_")) for name in names
    )
    a, (inner, _, groups) = _arrays("two_groups", jnp.float32)

    def both(y, z, scale, xbc, kernel, bias):
        out = mamba.gate_norm(y, z, scale, groups, 1e-5)
        return jnp.sum(out) + jnp.sum(mamba.conv_silu(xbc, kernel, bias))

    text = str(jax.make_jaxpr(jax.grad(both, argnums=tuple(range(6))))(
        a["y"], a["z"], a["scale"], a["xbc"], a["kernel"], a["bias"]
    ))
    for name in names:
        assert f"name={name}" in text, name
