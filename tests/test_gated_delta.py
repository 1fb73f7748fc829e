"""ops/gated_delta.py (the two delta-rule kernels, interpreted on the CPU, and
the plain chunked form of the shapes they do not tile) against the recurrence
one step at a time; the solve; the Gated DeltaNet part's own pieces
(layers/gated_delta.py); and the rotary positions on a head's leading lanes."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elasticdl_tpu.layers import attention as attention_layers
from elasticdl_tpu.layers import gated_delta as layer
from elasticdl_tpu.ops import gated_delta

ARGS = ("q", "k", "v", "g", "beta")
# (key heads, value heads, dk, dv): a shape the kernels tile and one they
# leave to the plain form
KERNELS = (1, 2, 128, 128)
PLAIN = (2, 4, 16, 8)


@pytest.fixture(autouse=True)
def _float32_products():
    with jax.default_matmul_precision("highest"):
        yield


def sequential(q, k, v, g, beta):
    """``S_t = exp(g_t) S_{t-1} + beta_t k_t (v_t - (exp(g_t) S_{t-1})^T
    k_t)^T``, ``o_t = S_t^T q_t`` by ``lax.scan`` over time, float32."""
    f32 = jnp.float32
    per = v.shape[2] // k.shape[2]
    q, k = (jnp.repeat(x.astype(f32), per, axis=2) for x in (q, k))
    v = v.astype(f32)

    def step(state, at):
        q_t, k_t, v_t, g_t, beta_t = at
        state = jnp.exp(g_t)[..., None, None] * state
        missing = v_t - jnp.einsum("bhkv,bhk->bhv", state, k_t)
        state = state + jnp.einsum("bh,bhk,bhv->bhkv", beta_t, k_t, missing)
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t)

    start = jnp.zeros((q.shape[0], v.shape[2], q.shape[-1], v.shape[-1]), f32)
    _, o = jax.lax.scan(
        step, start, tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta))
    )
    return jnp.moveaxis(o, 0, 1)


def inputs(steps, layout=KERNELS, dtype=jnp.float32, decay=1.0, alike=0.0, seed=0):
    """batch 2 of ``layout``; ``decay`` scales ``g``; ``alike`` adds one
    direction to every key of a head before it is normalised."""
    keys, values, dk, dv = layout
    rng = np.random.RandomState(seed)

    def unit(x):
        return x / np.sqrt((x**2).sum(-1, keepdims=True) + 1e-6)

    q = unit(rng.randn(2, steps, keys, dk)) * dk**-0.5
    k = unit(rng.randn(2, steps, keys, dk) + alike * rng.randn(2, 1, keys, dk))
    v = rng.randn(2, steps, values, dv)
    g = -np.log1p(np.exp(rng.randn(2, steps, values))) * 0.1 * decay
    beta = 1 / (1 + np.exp(-rng.randn(2, steps, values)))
    f32 = jnp.float32
    return tuple(
        jnp.asarray(x, d) for x, d in zip((q, k, v, g, beta), (dtype,) * 3 + (f32,) * 2)
    )


def value_and_grads(function, args):
    out = jax.jit(function)(*args)
    weigh = jnp.asarray(np.random.RandomState(5).randn(*out.shape), jnp.float32)
    grads = jax.jit(jax.grad(
        lambda *a: jnp.sum(function(*a).astype(jnp.float32) * weigh),
        argnums=tuple(range(len(args))),
    ))(*args)
    return out, grads


def assert_close(got, want, tolerance):
    for name, ours, theirs in zip(
        ("o", *ARGS), jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)
    ):
        ours, theirs = (np.asarray(x, np.float32) for x in (ours, theirs))
        scale = max(float(np.max(np.abs(theirs))), 1e-3)
        assert float(np.max(np.abs(ours - theirs))) <= tolerance * scale, name


@pytest.mark.parametrize("chunk", [8, 16])
def test_the_plain_chunked_form_is_the_recurrence(chunk):
    """Values and the five gradients, 40 steps (no whole number of chunks of
    16: padded with steps that decay nothing and write nothing)."""
    assert not gated_delta.scan_tile(*PLAIN[2:], chunk)
    args = inputs(40, PLAIN)
    chunked = functools.partial(gated_delta.gated_delta_chunked, chunk=chunk)
    assert_close(value_and_grads(chunked, args), value_and_grads(sequential, args), 2e-5)


@pytest.mark.parametrize("chunk", [32])
def test_the_kernels_are_the_recurrence_in_float32(chunk):
    """Interpreted, 100 steps (padded to four chunks; the cases below run
    chunks of 64), two value heads a key head: values and gradients against the step-by-step recurrence; the
    solve's float32 products are three bfloat16 passes, 2^-16 a term."""
    assert gated_delta.scan_tile(*KERNELS[2:], chunk)
    args = inputs(100)
    kernels = functools.partial(
        gated_delta.gated_delta_chunked, chunk=chunk, interpret=True
    )
    assert_close(value_and_grads(kernels, args), value_and_grads(sequential, args), 2e-5)


def test_the_kernels_are_the_plain_form_in_bfloat16():
    """The same roundings on both sides (``T`` by a triangular solve in the
    plain form, by the finite products in the kernel): a bfloat16 rounding of
    the results apart."""
    args = inputs(128, dtype=jnp.bfloat16)

    def plain(q, k, v, g, beta):
        flat = [x.reshape(*x.shape[:2], -1) for x in (q, k, v)]
        gamma = jnp.cumsum(g.reshape(2, -1, 64, 2), axis=2).reshape(g.shape)
        return gated_delta._chunked_plain(*flat, gamma, beta, 1, 64).reshape(v.shape)

    kernels = functools.partial(
        gated_delta.gated_delta_chunked, chunk=64, interpret=True
    )
    assert_close(value_and_grads(kernels, args), value_and_grads(plain, args), 1e-2)


@pytest.mark.parametrize("case", ["strong_decay", "keys_alike"])
def test_the_kernels_hold_at_the_edges(case):
    """A decay that underflows any product of decays inside a chunk (``g``
    about -20 a step: differences of running sums are exact where a
    cumulative product is 0), and keys that are nearly one direction (``A``
    near a triangle of ``beta``: the powers of one 64 x 64 product form would
    grow by binomials of 63 before they cancel; blocks of 16 do not)."""
    args = inputs(128, **({"decay": 300.0} if case == "strong_decay" else {"alike": 5.0}))
    kernels = functools.partial(
        gated_delta.gated_delta_chunked, chunk=64, interpret=True
    )
    got, want = value_and_grads(kernels, args), value_and_grads(sequential, args)
    assert all(
        bool(jnp.all(jnp.isfinite(x))) for x in jax.tree_util.tree_leaves(got)
    )
    assert_close(got, want, 2e-4)


@pytest.mark.parametrize("length", [16, 32, 64, 128])
def test_the_solve_inverts_a_unit_lower_triangle(length):
    rng = np.random.RandomState(length)
    a = np.tril(rng.randn(length, length) * 0.3, -1).astype(np.float32)
    got = gated_delta._unit_lower_inverse(jnp.asarray(a))
    want = np.linalg.inv(np.eye(length) + a.astype(np.float64))
    assert float(np.max(np.abs(np.asarray(got) - want))) <= 2e-4 * np.max(np.abs(want))


def test_shapes_that_do_not_fit_are_refused():
    q, k, v, g, beta = inputs(16, PLAIN)
    with pytest.raises(ValueError, match="value heads over"):
        gated_delta.gated_delta_chunked(q, k, v[:, :, :3], g[..., :3], beta[..., :3])
    assert not gated_delta.scan_tile(128, 128, 48)  # three blocks of 16
    assert not gated_delta.scan_tile(64, 128, 64)


def test_the_parts_own_pieces():
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(2, 5, 3, 8), jnp.float32)
    unit = layer.l2_normalised(x)
    np.testing.assert_allclose(
        np.asarray(unit), np.asarray(x) / np.sqrt((np.asarray(x) ** 2).sum(-1, keepdims=True) + 1e-6),
        rtol=1e-5,
    )
    z, scale = jnp.asarray(rng.randn(2, 5, 3, 8), jnp.float32), jnp.asarray(rng.rand(8) + 0.5, jnp.float32)
    got = layer.normed_then_gated(x, z, scale, 1e-6)
    normed = np.asarray(x) / np.sqrt((np.asarray(x) ** 2).mean(-1, keepdims=True) + 1e-6)
    np.testing.assert_allclose(
        np.asarray(got), normed * np.asarray(scale) * np.asarray(jax.nn.silu(z)), rtol=1e-5
    )
    # the norm BEFORE the gate: not Mamba-2's gated norm
    from elasticdl_tpu.layers import mamba

    other = mamba.gated_group_norm(x, z, scale, 1, 1e-6)
    assert float(jnp.max(jnp.abs(other - got))) > 0.1


def test_a_delta_layer_refuses_to_decode():
    from elasticdl_tpu.models import long_seq_transformer as zoo

    model = zoo.custom_model(
        vocab_size=32, embed_dim=16, num_heads=2, num_layers=1, layer_pattern="d",
        linear_key_heads=1, linear_value_heads=2, linear_key_dim=8,
        linear_value_dim=8, delta_chunk=8, decode=True, max_decode_len=4,
    )
    with pytest.raises(NotImplementedError, match="gated-delta-rule"):
        model.init(jax.random.PRNGKey(0), {"tokens": jnp.zeros((1, 1), jnp.int32)})


def test_rope_turns_a_heads_leading_lanes_and_passes_the_rest():
    """``lead=4`` of 16: lanes 0..3 turn as a 4-wide head would (pairs (0, 2)
    and (1, 3), rates theta^0 and theta^(-1/2)), lanes 4..15 are untouched;
    a rotating head and a rotating tail at once are refused."""
    x = jnp.asarray(np.random.RandomState(0).randn(2, 6, 3, 16), jnp.float32)
    positions = jnp.arange(6)
    got = attention_layers.rope(x, positions, 100.0, lead=4)
    np.testing.assert_array_equal(np.asarray(got[..., 4:]), np.asarray(x[..., 4:]))
    np.testing.assert_allclose(
        np.asarray(got[..., :4]),
        np.asarray(attention_layers.rope_plain(x[..., :4], positions, 100.0)),
        rtol=1e-6,
    )
    angle = np.arange(6)[None, :, None] * 100.0 ** -0.5
    np.testing.assert_allclose(
        np.asarray(got[..., 1]),
        np.asarray(x[..., 1]) * np.cos(angle) - np.asarray(x[..., 3]) * np.sin(angle),
        rtol=1e-5, atol=1e-6,
    )
    with pytest.raises(ValueError, match="at once"):
        attention_layers.rope(x, positions, 100.0, skip=8, lead=4)
