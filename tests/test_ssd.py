"""ops/ssd.py (the two chunked-scan kernels, interpreted on the CPU, and the
plain form of the shapes they do not tile) against the recurrence one step at
a time; the Mamba-2 mixer's other parts (layers/mamba.py); and the flash
kernels at nemotron_h's 16 query heads a key/value head."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elasticdl_tpu.layers import mamba
from elasticdl_tpu.ops import attention as attention_ops
from elasticdl_tpu.ops import ssd

ARGS = ("x", "dt", "a", "b", "c", "d")

# (heads, P, groups, N): shapes the kernels tile, by heads a group and head
# width, and one they leave to the plain form
LAYOUTS = {
    "two_heads_of_64_a_group": (4, 64, 2, 128),
    "four_heads_of_64_a_group": (4, 64, 1, 128),
    "eight_heads_of_64_a_group": (8, 64, 1, 128),
    "heads_of_128": (2, 128, 2, 128),
    "heads_of_256": (2, 256, 1, 128),
}
KERNELS = LAYOUTS["two_heads_of_64_a_group"]
PLAIN = (4, 16, 2, 16)


def sequential(x, dt, a, b, c, d):
    """``h_t = exp(dt_t A) h_{t-1} + dt_t x_t (x) B_t``, ``y_t = C_t . h_t +
    D x_t`` by ``lax.scan`` over time, float32."""
    f32 = jnp.float32
    x, dt, b, c = (v.astype(f32) for v in (x, dt, b, c))
    heads, groups = x.shape[2], b.shape[2]
    b, c = (jnp.repeat(v, heads // groups, axis=2) for v in (b, c))

    def step(h, inputs):
        x_t, dt_t, b_t, c_t = inputs
        h = jnp.exp(dt_t * a)[..., None, None] * h + jnp.einsum(
            "bh,bhn,bhp->bhnp", dt_t, b_t, x_t
        )
        return h, jnp.einsum("bhn,bhnp->bhp", c_t, h)

    h0 = jnp.zeros((x.shape[0], heads, b.shape[-1], x.shape[-1]), f32)
    _, y = jax.lax.scan(
        step, h0, tuple(jnp.moveaxis(v, 1, 0) for v in (x, dt, b, c))
    )
    return jnp.moveaxis(y, 0, 1) + d[:, None] * x


def as_xbc(x, b, c):
    """``x`` (batch, T, heads, P) and ``b``, ``c`` (batch, T, groups, N)
    laid side by side as the layer's ``xBC``."""
    return jnp.concatenate(
        [v.reshape(*x.shape[:2], -1) for v in (x, b, c)], axis=-1
    )


def chunked(x, dt, a, b, c, d, *, chunk, interpret=None):
    """``ssd.ssd_chunked`` with ``sequential``'s arguments, and ``y`` back
    by head."""
    y = ssd.ssd_chunked(
        as_xbc(x, b, c), dt, a, d, groups=b.shape[2], states=b.shape[3],
        chunk=chunk, interpret=interpret,
    )
    return y.reshape(x.shape)


def inputs(steps=24, dtype=jnp.float32, decay=1.0, seed=0, layout=KERNELS):
    """batch 2 of ``layout``'s (heads, P, groups, N); ``decay`` scales both
    ``dt`` and ``A``."""
    heads, width, groups, states = layout
    rng = np.random.RandomState(seed)
    x = jnp.asarray(rng.randn(2, steps, heads, width), dtype)
    dt = jnp.asarray(
        np.log1p(np.exp(rng.randn(2, steps, heads))) * decay, jnp.float32
    )
    a = -jnp.asarray(np.exp(rng.rand(heads) * 2) * decay, jnp.float32)
    b = jnp.asarray(rng.randn(2, steps, groups, states), dtype)
    c = jnp.asarray(rng.randn(2, steps, groups, states), dtype)
    d = jnp.asarray(rng.randn(heads), jnp.float32)
    weigh = jnp.asarray(rng.randn(2, steps, heads, width), jnp.float32)
    return (x, dt, a, b, c, d), weigh


def value_and_grads(scan, args, weigh):
    return jax.jit(jax.value_and_grad(
        lambda *args: jnp.sum(weigh * scan(*args).astype(jnp.float32)),
        argnums=tuple(range(6)),
    ))(*args)


def scaled_errors(got, want):
    return {
        name: float(jnp.max(jnp.abs(g - w)) / jnp.max(jnp.abs(w)))
        for name, g, w in zip(ARGS, got, want)
    }


def takes_the_kernels(args, chunk=8):
    return "pallas_call" in str(jax.make_jaxpr(
        lambda *a: chunked(*a, chunk=chunk, interpret=False)
    )(*args))


@pytest.mark.parametrize(
    "steps,chunk,layout",
    [(24, 8, KERNELS), (32, 16, KERNELS), (21, 8, KERNELS)]
    + [(16, 8, layout) for layout in list(LAYOUTS.values())[1:]]
    + [(24, 8, PLAIN), (21, 8, PLAIN)],
    ids=["three_chunks", "two_chunks_of_16", "padded_to_three_chunks"]
    + list(LAYOUTS)[1:] + ["plain_three_chunks", "plain_padded"],
)
def test_chunked_scan_and_every_gradient_match_the_recurrence(
    steps, chunk, layout
):
    """float32 against float32, the layer's layout in and out: the two
    differ by the order of their sums (measured at 16 states: the weighed
    sum of the output, whose terms cancel, 6e-6; gradients under 4e-6 of
    their largest entry).  A shape the kernels tile takes them and any other
    the plain form."""
    args, weigh = inputs(steps, layout=layout)
    assert takes_the_kernels(args, chunk) is (layout is not PLAIN)
    got = value_and_grads(
        lambda *a: chunked(*a, chunk=chunk), args, weigh
    )
    want = value_and_grads(sequential, args, weigh)
    np.testing.assert_allclose(got[0], want[0], rtol=3e-5)
    errors = scaled_errors(got[1], want[1])
    assert max(errors.values()) < 2e-5, errors


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=str)
def test_plain_form_agrees_with_the_kernels_where_both_run(dtype):
    """At a shape the kernels tile: the same products in the same
    precisions, rounded at the same points, so float32 differs by the order
    of the sums and bfloat16 by a rounding of the output."""
    args, weigh = inputs(dtype=dtype)

    def plain(x, dt, a, b, c, d):
        cum = jnp.cumsum((dt * a).reshape(2, -1, 8, 4), axis=2).reshape(dt.shape)
        return ssd._chunked_plain(
            as_xbc(x, b, c), dt, cum, d, 2, 128, 8
        ).reshape(x.shape)

    got = value_and_grads(lambda *a: chunked(*a, chunk=8), args, weigh)
    want = value_and_grads(plain, args, weigh)
    exact = dtype == jnp.float32
    np.testing.assert_allclose(got[0], want[0], rtol=3e-5 if exact else 5e-3)
    errors = scaled_errors(got[1], want[1])
    # ``A``'s gradient is all ``dcum``, which the kernels form as a
    # difference of sums and JAX, for the plain form, term by term: in
    # bfloat16 they differ as either does from the recurrence
    assert errors.pop("a") < (2e-5 if exact else 0.3), errors
    assert max(errors.values()) < (2e-5 if exact else 0.03), errors


@pytest.mark.parametrize("layout", [KERNELS, PLAIN], ids=["kernels", "plain"])
def test_a_decay_that_underflows_a_cumulative_product_is_exact(layout):
    """``dt A`` down to -60 a step: the product of a chunk's decays is 0 in
    float32 after two such steps (exp(-120) < 1e-45), and so is any use of
    its inverse; the kernels take differences of running sums before the
    exponential and lose nothing.  The gradient of ``A`` is a small difference
    of large sums here (``dcum``), hence its looser limit (measured 7e-4 of
    its largest entry, 1e-5 of the gradient of ``dt``'s)."""
    args, weigh = inputs(decay=3.0, layout=layout)
    assert float(jnp.min(args[1] * args[2])) < -60
    got = value_and_grads(lambda *a: chunked(*a, chunk=8), args, weigh)
    want = value_and_grads(sequential, args, weigh)
    assert np.isfinite(float(got[0]))
    np.testing.assert_allclose(got[0], want[0], rtol=3e-5)
    errors = scaled_errors(got[1], want[1])
    assert errors.pop("a") < 5e-3, errors
    assert max(errors.values()) < 5e-5, errors


@pytest.mark.parametrize("layout", [KERNELS, PLAIN], ids=["kernels", "plain"])
def test_bfloat16_inputs_stay_within_a_rounding_of_the_recurrence(layout):
    """Products in bfloat16, accumulation, decays and the carried state in
    float32: 3 decimal digits an operand (output 0.6%, gradients 0.1..2.3%
    of their largest entry).  ``A``'s gradient is all ``dcum``, a difference
    of sums that nearly cancel, and reads higher, by the terms a sum has.

    Its readings (``scaled_errors`` of this test's arrays, ``inputs(dtype=
    bfloat16)`` at chunk 8, against ``sequential``; interpreted on the CPU,
    PR 54): at ``KERNELS`` (64 channels, 128 states) **0.20227** from this
    tree's kernels and **0.20227** from the (batch, groups, heads, T, P)
    kernels of the parent ``704db64`` (its ``ssd_chunked(x, dt, a, b, c, d,
    chunk=8)`` on the same arrays, the tree unpacked with ``git archive``);
    the limit of 0.3 is 1.5 times that, and a tighter ``dcum`` has 0.202 to
    beat.  At ``PLAIN`` (16 channels, 16 states) the plain form reads 0.0032
    where the parent's kernels read 0.0393; 0.08 is the limit that shape has
    had."""
    args, weigh = inputs(dtype=jnp.bfloat16, layout=layout)
    got = value_and_grads(lambda *a: chunked(*a, chunk=8), args, weigh)
    want = value_and_grads(sequential, args, weigh)
    np.testing.assert_allclose(got[0], want[0], rtol=0.02)
    errors = scaled_errors(got[1], want[1])
    assert errors.pop("a") < (0.08 if layout is PLAIN else 0.3), errors
    assert max(errors.values()) < 0.03, errors


@pytest.mark.parametrize("layout", [KERNELS, PLAIN], ids=["kernels", "plain"])
def test_no_step_sees_the_future_through_the_scan(layout):
    (x, dt, a, b, c, d), _ = inputs(layout=layout)
    y = chunked(x, dt, a, b, c, d, chunk=8)
    later = chunked(
        x.at[:, 13:].add(1.0), dt.at[:, 13:].mul(2.0), a,
        b.at[:, 13:].add(1.0), c.at[:, 13:].add(1.0), d, chunk=8,
    )
    np.testing.assert_array_equal(y[:, :13], later[:, :13])
    assert float(jnp.max(jnp.abs(y[:, 13:] - later[:, 13:]))) > 0.1


def test_kernels_carry_the_names_the_benchmark_reads():
    """``perf/ssd_rooflines.py`` finds the kernels on the op line by these
    names: one forward call and one backward call, nothing else of
    theirs."""
    assert (ssd.SSD_FWD, ssd.SSD_BWD) == ("ssd_fwd", "ssd_bwd")
    args, weigh = inputs()
    text = str(jax.make_jaxpr(lambda *a: value_and_grads(
        lambda *a: chunked(*a, chunk=8, interpret=False), a, weigh
    ))(*args))
    assert text.count("name=ssd_fwd") == text.count("name=ssd_bwd") == 1
    assert text.count("pallas_call") == 2
    with pytest.raises(ValueError, match="groups"):
        ssd.ssd_chunked(
            jnp.zeros((1, 8, 3 * 16 + 2 * 2 * 16)), jnp.ones((1, 8, 3)),
            -jnp.ones(3), jnp.ones(3), groups=2, states=16, chunk=8,
        )


@pytest.mark.parametrize(
    "layout,tile", [
        ((64, 64, 8, 128), (2, 128)), ((4, 64, 2, 128), (2, 128)),
        ((2, 128, 2, 128), (1, 128)), ((2, 256, 1, 256), (1, 256)),
        ((4, 32, 1, 128), (4, 128)),
        ((4, 16, 2, 16), None),      # 32 lanes a group
        ((8, 64, 2, 64), None),      # half a tile of states
        ((2, 64, 2, 128), None),     # one head of 64 a group
        ((6, 64, 2, 128), None),     # three heads of 64 a group
        ((4, 96, 1, 128), None),     # heads that neither divide nor fill tiles
        ((2, 64, 1, 256), None),     # B's window starts half a block in
    ],
)
def test_the_shape_alone_says_which_form_runs(layout, tile):
    assert ssd.scan_tile(*layout) == tile


def test_convolution_is_causal_and_depthwise():
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(2, 12, 6), jnp.float32)
    kernel = jnp.asarray(rng.randn(4, 6), jnp.float32)
    bias = jnp.asarray(rng.randn(6), jnp.float32)
    y = mamba.causal_conv(x, kernel, bias)
    want = np.zeros((2, 12, 6), np.float32) + np.asarray(bias)
    for t in range(12):
        for tap in range(4):
            if t - 3 + tap >= 0:
                want[:, t] += np.asarray(x[:, t - 3 + tap] * kernel[tap])
    np.testing.assert_allclose(y, want, rtol=1e-5, atol=1e-6)
    later = mamba.causal_conv(x.at[:, 7:].add(1.0), kernel, bias)
    np.testing.assert_array_equal(y[:, :7], later[:, :7])


def test_gate_comes_before_the_norm_and_groups_norm_apart():
    rng = np.random.RandomState(2)
    y = jnp.asarray(rng.randn(3, 8), jnp.float32)
    z = jnp.asarray(rng.randn(3, 8), jnp.float32)
    scale = jnp.asarray(rng.rand(8) + 0.5, jnp.float32)
    got = mamba.gated_group_norm(y, z, scale, groups=2, eps=1e-5)
    gated = np.asarray(y * jax.nn.silu(z)).reshape(3, 2, 4)
    want = gated / np.sqrt((gated ** 2).mean(-1, keepdims=True) + 1e-5)
    np.testing.assert_allclose(got, want.reshape(3, 8) * scale, rtol=1e-5)
    one_group = mamba.gated_group_norm(y, z, scale, groups=1, eps=1e-5)
    assert float(jnp.max(jnp.abs(got - one_group))) > 0.05


def test_mixer_builds_the_published_shapes_at_a_small_size():
    layer = mamba.Mamba2Mixer(
        num_heads=4, head_dim=16, groups=2, state_size=16, chunk=8
    )
    u = jnp.asarray(np.random.RandomState(3).randn(2, 16, 32), jnp.float32)
    params = layer.init(jax.random.PRNGKey(0), u)["params"]
    shapes = jax.tree_util.tree_map(lambda p: p.shape, params)
    assert shapes == {
        "in_proj": {"kernel": (32, 64 + 64 + 2 * 2 * 16 + 4)},
        "conv_kernel": (4, 64 + 2 * 2 * 16), "conv_bias": (64 + 2 * 2 * 16,),
        "dt_bias": (4,), "A_log": (4,), "D": (4,), "norm_scale": (64,),
        "out_proj": {"kernel": (64, 32)},
    }
    a = np.exp(np.asarray(params["A_log"]))
    dt = np.log1p(np.exp(np.asarray(params["dt_bias"])))
    assert (a >= 1).all() and (a <= 16).all()
    assert (dt >= 1e-3 * 0.999).all() and (dt <= 0.1 * 1.001).all()
    assert layer.apply({"params": params}, u).shape == u.shape


def test_flash_kernels_at_sixteen_query_heads_a_key_value_head():
    """nemotron_h's rate (32 : 2): output and all three gradients against
    the materialised reference."""
    rng = np.random.RandomState(4)
    q = jnp.asarray(rng.randn(1, 64, 32, 16), jnp.float32)
    k = jnp.asarray(rng.randn(1, 64, 2, 16), jnp.float32)
    v = jnp.asarray(rng.randn(1, 64, 2, 16), jnp.float32)

    def loss(attend, q, k, v):
        return jnp.sum(jnp.sin(attend(q, k, v, causal=True)))

    got = jax.value_and_grad(
        lambda *a: loss(attention_ops.flash_attention, *a), argnums=(0, 1, 2)
    )(q, k, v)
    want = jax.value_and_grad(
        lambda *a: loss(attention_ops.mha_reference, *a), argnums=(0, 1, 2)
    )(q, k, v)
    for a, b in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-5)


def test_hybrid_stack_trains_on_a_dp_ep_mesh():
    """Mamba-2, expert and attention layers by pattern through SPMDTrainer on
    ``dp=2,ep=2``: the held experts shard over ``ep``, the scan and flash
    kernels are mapped over ``dp``, the first loss equals the one-device
    step's, the selection bias moves outside the gradient, and the router's
    counters tell held pairs from absent ones with none dropped."""
    import optax

    from elasticdl_tpu.models import long_seq_transformer as lm
    from elasticdl_tpu.parallel.distributed import SPMDTrainer
    from elasticdl_tpu.parallel.mesh import MeshConfig
    from elasticdl_tpu.telemetry import router_load
    from elasticdl_tpu.trainer.state import TrainState, init_model
    from elasticdl_tpu.trainer.step import build_train_step

    rng = np.random.RandomState(0)
    feats = {"tokens": rng.randint(0, 64, (4, 32)).astype(np.int32)}
    labels = rng.randint(0, 64, (4, 32)).astype(np.int32)
    model = lm.custom_model(
        vocab_size=64, embed_dim=32, num_heads=4, num_kv_heads=2, head_dim=16,
        num_layers=3, layer_pattern="ME*", norm="rmsnorm", use_bias=False,
        positions="none", num_experts=8, experts_per_token=2, expert_width=16,
        norm_topk_prob=True, router_scoring="sigmoid", selection_bias=True,
        routed_scaling=2.5, expert_kind="relu2", shared_expert_width=24,
        experts_held=4, first_expert=2, router_aux_weight=1e-4,
        router_z_weight=0.0, mamba_heads=4, mamba_head_dim=8, ssm_groups=2,
        ssm_state=8, ssd_chunk=8, remat_layers=True,
    )
    mesh = MeshConfig.from_string("dp=2,ep=2").create(devices=jax.devices()[:4])
    trainer = SPMDTrainer(
        mesh, model, lm.loss, optax.adam(3e-3), feats,
        rules=tuple(lm.sharding_rules(mesh)),
    )
    moe = trainer.state.params["block_1"]["moe"]
    assert moe["w_up"].shape == (4, 32, 16) and "w_gate" not in moe
    assert "ep" in str(moe["w_up"].sharding.spec)

    params, model_state = jax.jit(lambda: init_model(model, feats))()
    one_device = build_train_step(lm.loss, compute_dtype=None)(
        TrainState.create(model.apply, params, optax.adam(3e-3), model_state),
        feats, labels,
    )[1]["loss"]
    losses = [
        float(trainer.train_step(
            trainer.place_batch(feats), trainer.place_batch(labels)
        )["loss"])
        for _ in range(4)
    ]
    np.testing.assert_allclose(losses[0], float(one_device), rtol=2e-5)
    assert losses[-1] < losses[0], losses
    load = router_load.read()
    assert load["pairs"] == 4 * 32 * 2 and load["dropped_pairs"] == 0
    assert load["held_pairs"] + load["absent_pairs"] == load["pairs"]
    assert 0 < load["held_pairs"] < load["pairs"]
    bias = trainer.state.model_state["router_stats"]["block_1"]["moe"]["selection_bias"]
    assert float(jnp.max(jnp.abs(bias))) == pytest.approx(4 * 0.001, rel=1e-4)
