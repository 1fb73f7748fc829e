"""ops/ssd.py (the two chunked-scan kernels, interpreted on the CPU) against
the recurrence one step at a time; the Mamba-2 mixer's other parts
(layers/mamba.py); and the flash kernels at nemotron_h's 16 query heads a
key/value head."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elasticdl_tpu.layers import mamba
from elasticdl_tpu.ops import attention as attention_ops
from elasticdl_tpu.ops import ssd

ARGS = ("x", "dt", "a", "b", "c", "d")


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


def inputs(steps=24, dtype=jnp.float32, decay=1.0, seed=0):
    """batch 2, 4 heads of 16 in 2 groups, 16 states; ``decay`` scales both
    ``dt`` and ``A``."""
    rng = np.random.RandomState(seed)
    x = jnp.asarray(rng.randn(2, steps, 4, 16), dtype)
    dt = jnp.asarray(np.log1p(np.exp(rng.randn(2, steps, 4))) * decay, jnp.float32)
    a = -jnp.asarray(np.exp(rng.rand(4) * 2) * decay, jnp.float32)
    b = jnp.asarray(rng.randn(2, steps, 2, 16), dtype)
    c = jnp.asarray(rng.randn(2, steps, 2, 16), dtype)
    d = jnp.asarray(rng.randn(4), jnp.float32)
    weigh = jnp.asarray(rng.randn(2, steps, 4, 16), jnp.float32)
    return (x, dt, a, b, c, d), weigh


def value_and_grads(scan, args, weigh):
    return jax.value_and_grad(
        lambda *args: jnp.sum(weigh * scan(*args).astype(jnp.float32)),
        argnums=tuple(range(6)),
    )(*args)


def scaled_errors(got, want):
    return {
        name: float(jnp.max(jnp.abs(g - w)) / jnp.max(jnp.abs(w)))
        for name, g, w in zip(ARGS, got, want)
    }


@pytest.mark.parametrize(
    "steps,chunk", [(24, 8), (32, 16), (21, 8)],
    ids=["three_chunks", "two_chunks_of_16", "padded_to_three_chunks"],
)
def test_chunked_scan_and_every_gradient_match_the_recurrence(steps, chunk):
    """float32 against float32: the two differ by the order of their sums
    (measured: the weighed sum of the output, whose terms cancel, 6e-6;
    gradients under 4e-6 of their largest entry)."""
    args, weigh = inputs(steps)
    got = value_and_grads(
        lambda *a: ssd.ssd_chunked(*a, chunk=chunk), args, weigh
    )
    want = value_and_grads(sequential, args, weigh)
    np.testing.assert_allclose(got[0], want[0], rtol=3e-5)
    errors = scaled_errors(got[1], want[1])
    assert max(errors.values()) < 2e-5, errors


def test_a_decay_that_underflows_a_cumulative_product_is_exact():
    """``dt A`` down to -60 a step: the product of a chunk's decays is 0 in
    float32 after two such steps (exp(-120) < 1e-45), and so is any use of
    its inverse; the kernels take differences of running sums before the
    exponential and lose nothing.  The gradient of ``A`` is a small difference
    of large sums here (``dcum``), hence its looser limit (measured 7e-4 of
    its largest entry, 1e-5 of the gradient of ``dt``'s)."""
    args, weigh = inputs(decay=3.0)
    assert float(jnp.min(args[1] * args[2])) < -60
    got = value_and_grads(lambda *a: ssd.ssd_chunked(*a, chunk=8), args, weigh)
    want = value_and_grads(sequential, args, weigh)
    assert np.isfinite(float(got[0]))
    np.testing.assert_allclose(got[0], want[0], rtol=3e-5)
    errors = scaled_errors(got[1], want[1])
    assert errors.pop("a") < 5e-3, errors
    assert max(errors.values()) < 5e-5, errors


def test_bfloat16_inputs_stay_within_a_rounding_of_the_recurrence():
    """Products in bfloat16, accumulation, decays and the carried state in
    float32: 3 decimal digits an operand (measured: output 0.6%, gradients
    0.1..0.8% of their largest entry, and 3.9% for ``A``'s, which is all
    ``dcum``: a difference of sums that nearly cancel)."""
    args, weigh = inputs(dtype=jnp.bfloat16)
    got = value_and_grads(lambda *a: ssd.ssd_chunked(*a, chunk=8), args, weigh)
    want = value_and_grads(sequential, args, weigh)
    np.testing.assert_allclose(got[0], want[0], rtol=0.02)
    errors = scaled_errors(got[1], want[1])
    assert errors.pop("a") < 0.08, errors
    assert max(errors.values()) < 0.03, errors


def test_no_step_sees_the_future_through_the_scan():
    (x, dt, a, b, c, d), _ = inputs()
    y = ssd.ssd_chunked(x, dt, a, b, c, d, chunk=8)
    later = ssd.ssd_chunked(
        x.at[:, 13:].add(1.0), dt.at[:, 13:].mul(2.0), a,
        b.at[:, 13:].add(1.0), c.at[:, 13:].add(1.0), d, chunk=8,
    )
    np.testing.assert_array_equal(y[:, :13], later[:, :13])
    assert float(jnp.max(jnp.abs(y[:, 13:] - later[:, 13:]))) > 0.1


def test_kernels_carry_the_names_the_benchmark_reads():
    """``perf/ssd_rooflines.py`` finds the kernels on the op line by these
    names."""
    assert (ssd.SSD_FWD, ssd.SSD_BWD) == ("ssd_fwd", "ssd_bwd")
    args, weigh = inputs()
    text = str(jax.make_jaxpr(lambda *a: value_and_grads(
        lambda *a: ssd.ssd_chunked(*a, chunk=8, interpret=False), a, weigh
    ))(*args))
    assert "name=ssd_fwd" in text and "name=ssd_bwd" in text
    with pytest.raises(ValueError, match="groups"):
        ssd.ssd_chunked(*args[:3], args[3][:, :, :1].repeat(3, 2), *args[4:], chunk=8)


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

    params, model_state = init_model(model, feats)
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
