"""Attention stack: pallas flash kernel, ring attention over sp, and the
long-context transformer model (no reference counterpart — long-context
sequence parallelism is a first-class TPU-build capability).

All kernel tests compare against the jnp oracle ``mha_reference``; ring
attention runs on the virtual 8-device mesh with the sequence sharded
over sp (the pallas kernel runs in interpreter mode on CPU — same code
path the TPU compiles).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elasticdl_tpu.ops.attention import (
    attention,
    flash_attention,
    flash_layout,
    mha_reference,
    set_attention_mesh,
)
from elasticdl_tpu.ops.ring_attention import ring_attention
from elasticdl_tpu.parallel.mesh import MeshConfig


@pytest.fixture(autouse=True)
def _reset_attention_mesh():
    yield
    set_attention_mesh(None)


def _qkv(b=2, s=128, h=2, d=32, seed=0):
    rng = np.random.RandomState(seed)
    mk = lambda: rng.randn(b, s, h, d).astype(np.float32)  # noqa: E731
    return mk(), mk(), mk()


@pytest.mark.parametrize("causal", [False, True])
def test_flash_matches_reference(causal):
    q, k, v = _qkv()
    ref = mha_reference(q, k, v, causal=causal)
    out = flash_attention(q, k, v, causal=causal, block_q=32, block_k=32)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5
    )


def test_flash_gradients_match_reference():
    """custom_vjp: pallas kernels in both directions must produce the
    same gradients as differentiating the oracle directly."""
    q, k, v = _qkv(b=1, s=64, h=2, d=16)

    def loss_fl(q, k, v):
        return (flash_attention(q, k, v, causal=True) ** 2).sum()

    def loss_ref(q, k, v):
        return (mha_reference(q, k, v, causal=True) ** 2).sum()

    g_fl = jax.grad(loss_fl, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_fl, g_ref):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-4, rtol=1e-4
        )


@pytest.mark.parametrize(
    "s,h,kvh,causal,bq,bk",
    [
        (64, 4, 4, False, 32, 32),   # multi-block, MHA
        (64, 4, 4, True, 32, 32),    # causal block skipping (both kernels)
        (128, 4, 2, True, 32, 32),   # GQA group 2: dk/dv group-sum
        (96, 6, 2, False, 32, 32),   # GQA group 3, non-pow2 seq
        (64, 2, 1, True, 32, 16),    # MQA, uneven q/k blocks
    ],
)
def test_flash_backward_kernels_blockwise(s, h, kvh, causal, bq, bk):
    """The dQ and dK/dV pallas kernels against jax.vjp of the oracle —
    per-cotangent (not just a scalar loss), across block layouts and
    GQA groupings.  Tolerances span the kernels' matmul-precision
    envelope (same order as the forward's)."""
    rng = np.random.RandomState(7)
    d = 16
    q = jnp.asarray(rng.randn(2, s, h, d), jnp.float32)
    k = jnp.asarray(rng.randn(2, s, kvh, d), jnp.float32)
    v = jnp.asarray(rng.randn(2, s, kvh, d), jnp.float32)
    g = jnp.asarray(rng.randn(2, s, h, d), jnp.float32)

    _, vjp_fl = jax.vjp(
        lambda q, k, v: flash_attention(
            q, k, v, causal, None, bq, bk
        ),
        q, k, v,
    )
    _, vjp_ref = jax.vjp(
        lambda q, k, v: mha_reference(q, k, v, causal), q, k, v
    )
    for got, want, name in zip(vjp_fl(g), vjp_ref(g), "qkv"):
        np.testing.assert_allclose(
            np.asarray(got),
            np.asarray(want),
            atol=2e-2,
            rtol=2e-2,
            err_msg=f"d{name} s={s} h={h} kvh={kvh} causal={causal}",
        )


def test_flash_handles_non_divisible_blocks():
    # seq 96 with preferred block 128 -> _pick_block falls back to a divisor
    q, k, v = _qkv(s=96)
    out = flash_attention(q, k, v)
    ref = mha_reference(q, k, v)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5
    )


def test_gqa_all_implementations_agree():
    """Grouped-query attention (kv heads < q heads): flash, ring, and
    ulysses all match the oracle computed with repeated KV heads."""
    from elasticdl_tpu.ops.ulysses import ulysses_attention

    rng = np.random.RandomState(3)
    q = rng.randn(2, 64, 8, 16).astype(np.float32)
    k = rng.randn(2, 64, 2, 16).astype(np.float32)  # 2 kv heads, group 4
    v = rng.randn(2, 64, 2, 16).astype(np.float32)
    ref = mha_reference(q, k, v, causal=True)

    fl = flash_attention(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(fl), np.asarray(ref), atol=2e-5, rtol=2e-5
    )
    mesh = MeshConfig.from_string("dp=2,sp=4").create()
    ring = ring_attention(q, k, v, mesh=mesh, causal=True)
    np.testing.assert_allclose(
        np.asarray(ring), np.asarray(ref), atol=2e-5, rtol=2e-5
    )
    uly = ulysses_attention(q, k, v, mesh=mesh, causal=True)
    np.testing.assert_allclose(
        np.asarray(uly), np.asarray(ref), atol=2e-5, rtol=2e-5
    )

    # ulysses' small-kv path: kv heads divide sp, so the un-repeated kv
    # rides the all_to_all and flash's GQA indexing runs per shard
    k4 = rng.randn(2, 64, 4, 16).astype(np.float32)
    v4 = rng.randn(2, 64, 4, 16).astype(np.float32)
    ref4 = mha_reference(q, k4, v4, causal=True)
    uly4 = ulysses_attention(q, k4, v4, mesh=mesh, causal=True)
    np.testing.assert_allclose(
        np.asarray(uly4), np.asarray(ref4), atol=2e-5, rtol=2e-5
    )


def test_gqa_gradients_and_transformer_on_sp_mesh():
    """GQA flash gradients match differentiating the oracle, and a GQA
    transformer trains end-to-end with ring attention on an sp mesh."""
    rng = np.random.RandomState(4)
    q = rng.randn(1, 32, 4, 8).astype(np.float32)
    k = rng.randn(1, 32, 2, 8).astype(np.float32)
    v = rng.randn(1, 32, 2, 8).astype(np.float32)
    g_fl = jax.grad(
        lambda q, k, v: (flash_attention(q, k, v, causal=True) ** 2).sum(),
        argnums=(0, 1, 2),
    )(q, k, v)
    g_ref = jax.grad(
        lambda q, k, v: (mha_reference(q, k, v, causal=True) ** 2).sum(),
        argnums=(0, 1, 2),
    )(q, k, v)
    for a, b in zip(g_fl, g_ref):
        assert a.shape == b.shape  # kv grads keep the GQA shape
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-4, rtol=1e-4
        )

    import optax

    from elasticdl_tpu.models import long_seq_transformer as lm
    from elasticdl_tpu.parallel.distributed import SPMDTrainer

    feats = {"tokens": rng.randint(0, 64, (4, 32)).astype(np.int32)}
    labels = rng.randint(0, 64, (4, 32)).astype(np.int32)
    mesh = MeshConfig.from_string("dp=2,sp=4").create()
    trainer = SPMDTrainer(
        mesh,
        lm.custom_model(
            vocab_size=64,
            num_layers=1,
            embed_dim=32,
            num_heads=4,
            num_kv_heads=2,
        ),
        lm.loss,
        optax.adam(3e-3),
        feats,
    )
    losses = [
        float(
            trainer.train_step(
                trainer.place_batch(feats), trainer.place_batch(labels)
            )["loss"]
        )
        for _ in range(4)
    ]
    assert all(np.isfinite(l) for l in losses)
    assert losses[-1] < losses[0], losses


def test_gqa_rejects_indivisible_heads():
    q, k, v = _qkv(h=4)
    bad_k = k[:, :, :3]  # 4 q heads, 3 kv heads
    with pytest.raises(ValueError):
        flash_attention(q, bad_k, v[:, :, :3])


def test_gqa_layer_shrinks_kv_projection():
    import flax.linen as nn  # noqa: F401
    import jax.numpy as jnp

    from elasticdl_tpu.layers.attention import MultiHeadSelfAttention

    layer = MultiHeadSelfAttention(num_heads=4, num_kv_heads=2, causal=True)
    x = jnp.asarray(np.random.RandomState(0).randn(2, 16, 32), jnp.float32)
    variables = layer.init(jax.random.PRNGKey(0), x)
    assert variables["params"]["query"]["kernel"].shape == (32, 4, 8)
    assert variables["params"]["key"]["kernel"].shape == (32, 2, 8)
    out = layer.apply(variables, x)
    assert out.shape == x.shape and np.isfinite(np.asarray(out)).all()


@pytest.mark.parametrize("causal", [False, True])
def test_ring_matches_reference_on_sp_mesh(causal):
    q, k, v = _qkv()
    mesh = MeshConfig.from_string("dp=2,sp=4").create()
    ref = mha_reference(q, k, v, causal=causal)
    out = ring_attention(q, k, v, mesh=mesh, axis_name="sp", causal=causal)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5
    )


def test_ring_with_sharded_inputs_under_jit():
    """Ring attention composes with GSPMD: seq-sharded inputs go in, the
    shard_map runs inside jit, and no all-gather of the full sequence is
    needed for correctness."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    q, k, v = _qkv(b=4, s=256)
    mesh = MeshConfig.from_string("dp=2,sp=4").create()
    sh = NamedSharding(mesh, P("dp", "sp", None, None))
    qs, ks, vs = (jax.device_put(x, sh) for x in (q, k, v))

    @jax.jit
    def run(q, k, v):
        return ring_attention(q, k, v, mesh=mesh, causal=True)

    out = run(qs, ks, vs)
    ref = mha_reference(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5
    )


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_matches_reference(causal):
    """The all-to-all sequence-parallel alternative: heads reshard over
    sp, full-sequence flash per head group, reshard back."""
    from elasticdl_tpu.ops.ulysses import ulysses_attention

    q, k, v = _qkv(h=4)  # heads must divide sp
    mesh = MeshConfig.from_string("dp=2,sp=4").create()
    ref = mha_reference(q, k, v, causal=causal)
    out = ulysses_attention(q, k, v, mesh=mesh, causal=causal)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5
    )


def test_ulysses_rejects_indivisible_heads():
    from elasticdl_tpu.ops.ulysses import ulysses_attention

    q, k, v = _qkv(h=2)
    mesh = MeshConfig.from_string("sp=4").create()
    with pytest.raises(ValueError):
        ulysses_attention(q, k, v, mesh=mesh)


def test_attention_dispatch_honors_sp_impl():
    """set_attention_mesh(..., sp_impl='ulysses') routes dispatch through
    the all-to-all implementation; both agree with the oracle."""
    q, k, v = _qkv(h=4)
    ref = mha_reference(q, k, v, causal=True)
    mesh = MeshConfig.from_string("dp=2,sp=4").create()
    set_attention_mesh(mesh, sp_impl="ulysses")
    out = attention(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5
    )


def test_sp_impl_validation_and_scope_preservation():
    """A typo'd sp_impl raises; the trainer's step scopes (sp_impl=None)
    preserve a globally selected implementation instead of resetting it
    to ring."""
    from elasticdl_tpu.ops.attention import (
        attention_mesh_scope,
        get_attention_mesh,
    )

    mesh = MeshConfig.from_string("sp=4").create()
    with pytest.raises(ValueError):
        set_attention_mesh(mesh, sp_impl="ulyses")  # typo

    set_attention_mesh(mesh, sp_impl="ulysses")
    with attention_mesh_scope(mesh):  # what SPMDTrainer does per step
        assert get_attention_mesh()[2] == "ulysses"
    assert get_attention_mesh()[2] == "ulysses"


def test_transformer_trains_with_ulysses(tmp_path):
    """End-to-end: global ulysses selection survives SPMDTrainer's
    scoping and the jitted step trains."""
    import optax

    from elasticdl_tpu.models import long_seq_transformer as lm
    from elasticdl_tpu.parallel.distributed import SPMDTrainer

    rng = np.random.RandomState(0)
    feats = {"tokens": rng.randint(0, 64, (4, 32)).astype(np.int32)}
    labels = rng.randint(0, 64, (4, 32)).astype(np.int32)
    mesh = MeshConfig.from_string("dp=2,sp=4").create()
    set_attention_mesh(mesh, sp_impl="ulysses")
    trainer = SPMDTrainer(
        mesh,
        lm.custom_model(
            vocab_size=64, num_layers=1, embed_dim=32, num_heads=4
        ),
        lm.loss,
        optax.adam(3e-3),
        feats,
    )
    losses = [
        float(
            trainer.train_step(
                trainer.place_batch(feats), trainer.place_batch(labels)
            )["loss"]
        )
        for _ in range(4)
    ]
    assert all(np.isfinite(l) for l in losses)
    assert losses[-1] < losses[0], losses


def test_attention_dispatch_uses_ring_on_sp_mesh():
    """attention() picks ring on an sp>1 mesh and flash otherwise; both
    agree with the oracle, so dispatch is observable via the mesh rules
    (ring requires seq % sp == 0 — exercised by construction)."""
    q, k, v = _qkv()
    ref = mha_reference(q, k, v, causal=True)

    set_attention_mesh(None)
    out_local = attention(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(out_local), np.asarray(ref), atol=2e-5, rtol=2e-5
    )

    mesh = MeshConfig.from_string("sp=8").create()
    set_attention_mesh(mesh)
    out_ring = attention(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(out_ring), np.asarray(ref), atol=2e-5, rtol=2e-5
    )


def test_transformer_trains_on_sp_mesh(tmp_path):
    """End-to-end: the transformer LM trains through SPMDTrainer on a
    dp=2,sp=4 mesh — sequence-sharded batches, ring attention inside the
    jitted step — and the loss drops."""
    import optax

    from elasticdl_tpu.data.dataset import Dataset
    from elasticdl_tpu.data.recordio_gen import synthetic
    from elasticdl_tpu.data.recordio_reader import RecordIODataReader
    from elasticdl_tpu.models import long_seq_transformer as lm
    from elasticdl_tpu.parallel.distributed import SPMDTrainer
    from elasticdl_tpu.trainer.state import Modes

    data_dir = synthetic.gen_sequence(
        str(tmp_path / "seq"),
        num_records=64,
        num_shards=1,
        seq_len=64,
        seed=0,
    )
    reader = RecordIODataReader(data_dir=data_dir)
    shards = reader.create_shards()
    name, (start, count) = next(iter(shards.items()))
    task = type(
        "T", (), {"shard_name": name, "start": start, "end": start + count}
    )
    ds = lm.dataset_fn(
        Dataset.from_generator(lambda: reader.read_records(task)),
        Modes.TRAINING,
        reader.metadata,
    )
    batches = list(ds.batch(16))

    mesh = MeshConfig.from_string("dp=2,sp=4").create()
    model = lm.custom_model(num_layers=1, embed_dim=64, num_heads=2)
    trainer = SPMDTrainer(
        mesh, model, lm.loss, optax.adam(3e-3), batches[0][0]
    )
    losses = []
    for _ in range(3):
        for feats, labels in batches:
            m = trainer.train_step(
                trainer.place_batch(feats), trainer.place_batch(labels)
            )
            losses.append(float(m["loss"]))
    assert all(np.isfinite(l) for l in losses)
    assert losses[-1] < losses[0], (losses[0], losses[-1])

    # the sequence dim really is sharded over sp on device
    placed = trainer.place_batch(batches[0][0])
    spec = placed["tokens"].sharding.spec
    assert spec[1] == "sp", spec


def test_transformer_tp_sp_mesh(tmp_path):
    """Full 3-D parallelism: dp=2 x tp=2 x sp=2 — tp shards QKV by head
    (megatron-style, ring keeps heads tp-sharded), sp shards the
    sequence.  The jitted step must compile, run, and match a replicated
    single-device step's loss on the same batch."""
    import optax

    from elasticdl_tpu.models import long_seq_transformer as lm
    from elasticdl_tpu.parallel.distributed import SPMDTrainer

    rng = np.random.RandomState(0)
    feats = {"tokens": rng.randint(0, 256, (4, 64)).astype(np.int32)}
    labels = rng.randint(0, 256, (4, 64)).astype(np.int32)
    model = lm.custom_model(num_layers=1, embed_dim=64, num_heads=4)

    mesh3d = MeshConfig.from_string("dp=2,tp=2,sp=2").create()
    trainer3d = SPMDTrainer(
        mesh3d,
        model,
        lm.loss,
        optax.sgd(0.0),  # lr 0: loss compares pre-update params
        feats,
        rules=tuple(lm.sharding_rules(mesh3d)),
    )
    # the tp rules actually took: a QKV kernel is head-sharded
    qkv = trainer3d.state.params["block_0"]["attn"]["query"]["kernel"]
    assert "tp" in str(qkv.sharding.spec), qkv.sharding.spec

    mesh1 = MeshConfig.from_string("dp=1").create([jax.devices()[0]])
    trainer1 = SPMDTrainer(
        mesh1, model, lm.loss, optax.sgd(0.0), feats
    )
    m3 = trainer3d.train_step(
        trainer3d.place_batch(feats), trainer3d.place_batch(labels)
    )
    m1 = trainer1.train_step(
        trainer1.place_batch(feats), trainer1.place_batch(labels)
    )
    np.testing.assert_allclose(
        float(m3["loss"]), float(m1["loss"]), rtol=1e-4
    )


def test_transformer_spec_contract():
    """The model module satisfies the model-zoo spec surface."""
    from elasticdl_tpu.utils.model_utils import get_model_spec

    spec = get_model_spec(
        "", "long_seq_transformer.long_seq_transformer.custom_model"
    )
    assert spec.build_model() is not None
    assert spec.loss is not None and spec.dataset_fn is not None
    assert spec.eval_metrics_fn is not None


def test_flash_non_power_of_two_blocks_chunking():
    """Regression: chunk size must stay a multiple of the block size —
    a chunk smaller than the block ran ZERO in-chunk sub-blocks and
    emitted all-NaN output (0/0) silently."""
    rng = np.random.RandomState(0)
    q = rng.randn(1, 2304, 2, 32).astype(np.float32)
    k = rng.randn(1, 2304, 2, 32).astype(np.float32)
    v = rng.randn(1, 2304, 2, 32).astype(np.float32)
    out = np.asarray(
        flash_attention(q, k, v, causal=True, block_q=384, block_k=384)
    )
    ref = np.asarray(mha_reference(q, k, v, causal=True))
    assert not np.isnan(out).any()
    np.testing.assert_allclose(out, ref, atol=2e-3, rtol=2e-3)


# ---- PR 28: the kernels' causal block structure and operand dtypes ---------

# chip_smoke.py's rule for the compiled kernels on bf16 inputs:
#   max|flash - ref| <= KERNEL_TOL * max(1, max|ref|)
KERNEL_TOL = 2e-2


def _weighted_grads(fn, w):
    return jax.grad(
        lambda q, k, v: jnp.sum(fn(q, k, v).astype(jnp.float32) * w),
        argnums=(0, 1, 2),
    )


def _scaled_errors(got, want):
    errors = []
    for a, b in zip(got, want):
        a = np.asarray(a, np.float32)
        b = np.asarray(b, np.float32)
        errors.append(np.max(np.abs(a - b)) / max(1.0, np.max(np.abs(b))))
    return errors


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("heads,kv_heads", [(2, 2), (4, 1)])
def test_flash_bf16_forward_and_gradients_within_the_chip_rule(
    causal, heads, kv_heads
):
    """bf16 in: bf16 blocks on the MXU, float32 accumulation.  A 4 x 4
    block grid: four blocks the diagonal crosses and six it does not."""
    rng = np.random.RandomState(11)

    def mk(h):
        return jnp.asarray(rng.randn(2, 256, h, 32), jnp.bfloat16)

    q, k, v = mk(heads), mk(kv_heads), mk(kv_heads)
    w = jnp.asarray(rng.randn(2, 256, heads, 32), jnp.float32)

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=causal, block_q=64, block_k=64)

    def ref(q, k, v):
        return mha_reference(q, k, v, causal=causal)

    got = (flash(q, k, v), *_weighted_grads(flash, w)(q, k, v))
    want = (ref(q, k, v), *_weighted_grads(ref, w)(q, k, v))
    assert [g.dtype for g in got] == [jnp.bfloat16] * 4
    assert [g.shape for g in got] == [w.shape for w in want]
    for name, err in zip(("out", "dq", "dk", "dv"), _scaled_errors(got, want)):
        assert err <= KERNEL_TOL, (name, err)


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


def _kernel_calls(fn, *args):
    """The ``pallas_call`` equations of ``fn``'s jaxpr, by kernel name."""
    return {
        eqn.params["name"]: eqn
        for eqn in _eqns(jax.make_jaxpr(fn)(*args).jaxpr)
        if eqn.primitive.name == "pallas_call"
    }


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("kernel", ["flash_fwd", "flash_dq", "flash_dkv"])
def test_flash_kernel_dtypes_follow_the_input(kernel, dtype):
    """Matmul operands in the input's dtype (float32 in computes in
    float32), every accumulator, the softmax statistics, ``lse`` and the
    exponent's argument in float32."""
    dtype = jnp.dtype(dtype)
    x = jax.ShapeDtypeStruct((1, 256, 2, 64), dtype)

    def loss(q, k, v):
        out = flash_attention(q, k, v, causal=True, interpret=False)
        return out.astype(jnp.float32).sum()

    call = _kernel_calls(jax.grad(loss, argnums=(0, 1, 2)), x, x, x)[kernel]
    body = call.params["jaxpr"]
    scratch = body.invars[-call.params["grid_mapping"].num_scratch_operands:]
    assert scratch and all(
        ref.aval.dtype == jnp.float32 for ref in scratch
    ), scratch
    outs = call.params["out_avals"]
    if kernel == "flash_fwd":
        out, lse = outs
        assert out.dtype == dtype
        # compact and lane-major: one row of seq_q float32 a head
        assert (lse.dtype, lse.shape) == (jnp.float32, (2, 1, 256))
    elif kernel == "flash_dq":
        dq, delta = outs
        assert dq.dtype == dtype
        # rowsum(dO * O) for dK/dV, in the lse's layout
        assert (delta.dtype, delta.shape) == (jnp.float32, (2, 1, 256))
    else:
        assert all(o.dtype == dtype for o in outs)
    dots = [e for e in _eqns(body) if e.primitive.name == "dot_general"]
    # two loop bodies a kernel (blocks the diagonal crosses, blocks it does
    # not), each with the kernel's two, three or four products a piece
    products = {"flash_fwd": 2, "flash_dq": 3, "flash_dkv": 4}[kernel]
    assert len(dots) >= 2 * products and len(dots) % products == 0
    for dot in dots:
        assert [v.aval.dtype for v in dot.invars] == [dtype, dtype], dot
        assert dot.outvars[0].aval.dtype == jnp.float32, dot
        # no transposed left-hand side: nothing contracts a first axis
        (lhs_contract, _), _ = dot.params["dimension_numbers"]
        assert lhs_contract == (1,), dot
    exps = [e for e in _eqns(body) if e.primitive.name == "exp"]
    assert exps and all(
        e.invars[0].aval.dtype == jnp.float32 for e in exps
    )


def _brute_force_plan(seq_q, seq_k, block_q, block_k):
    live = masked = 0
    for r0 in range(0, seq_q, block_q):
        for c0 in range(0, seq_k, block_k):
            seen = [
                r >= c
                for r in range(r0, r0 + block_q)
                for c in range(c0, c0 + block_k)
            ]
            live += any(seen)
            masked += any(seen) and not all(seen)
    total = (seq_q // block_q) * (seq_k // block_k)
    return live, masked, total - live


@pytest.mark.parametrize(
    "seq_q,seq_k,block_q,block_k",
    [
        (128, 128, 16, 16),
        (128, 128, 32, 8),   # several k-blocks on the diagonal of a q-block
        (128, 128, 8, 32),
        (64, 128, 16, 16),   # seq_q != seq_k
        (128, 64, 16, 32),
        (2304, 2304, 384, 384),
        (96, 96, 96, 96),    # one block
        (96, 96, 1, 1),      # every diagonal block is wholly visible
    ],
)
def test_flash_block_plan_matches_a_brute_force_count(
    seq_q, seq_k, block_q, block_k
):
    from elasticdl_tpu.ops.attention import (
        _q_blocks_visible,
        flash_block_plan,
    )

    want = _brute_force_plan(seq_q, seq_k, block_q, block_k)
    assert flash_block_plan(seq_q, seq_k, block_q, block_k, True) == want
    total = (seq_q // block_q) * (seq_k // block_k)
    assert flash_block_plan(seq_q, seq_k, block_q, block_k, False) == (
        total, 0, 0
    )
    # dK/dV walks the same grid by columns, with bounds of its own
    num_q = seq_q // block_q
    live = masked = 0
    for c0 in range(0, seq_k, block_k):
        first, full_from = _q_blocks_visible(c0, block_k, 0, block_q, num_q)
        assert 0 <= first <= full_from <= num_q
        live += num_q - first
        masked += full_from - first
    assert (live, masked, total - live) == want


def test_flash_block_plan_at_the_long_cell():
    from elasticdl_tpu.ops.attention import flash_block_plan

    assert flash_block_plan(8192, 8192, 512, 512, True) == (136, 16, 120)


@pytest.mark.parametrize(
    "block_q,block_k", [(32, 32), (64, 16), (16, 64), (32, 128), (128, 32)]
)
def test_flash_masks_every_block_the_diagonal_crosses(block_q, block_k):
    """Scores of 6 * (column - row): huge above the diagonal, so one
    unmasked element there takes a row's whole softmax, in the forward
    and in each backward kernel."""
    seq = 128
    position = np.arange(seq, dtype=np.float32)
    q = np.zeros((1, seq, 1, 8), np.float32)
    k = np.zeros((1, seq, 1, 8), np.float32)
    q[0, :, 0, 0], q[0, :, 0, 1] = 1.0, -position
    k[0, :, 0, 0], k[0, :, 0, 1] = 6.0 * position, 6.0
    rng = np.random.RandomState(5)
    v = rng.randn(1, seq, 1, 8).astype(np.float32)
    w = jnp.asarray(rng.randn(1, seq, 1, 8), jnp.float32)

    def flash(q, k, v):
        return flash_attention(
            q, k, v, True, 1.0, block_q, block_k
        )

    def ref(q, k, v):
        return mha_reference(q, k, v, causal=True, sm_scale=1.0)

    got = (flash(q, k, v), *_weighted_grads(flash, w)(q, k, v))
    want = (ref(q, k, v), *_weighted_grads(ref, w)(q, k, v))
    # row r sees column r with weight ~1: nothing from above leaks in
    np.testing.assert_allclose(
        np.asarray(got[0]), v, atol=3e-2, rtol=0
    )
    for a, b in zip(got, want):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=2e-3, rtol=2e-3
        )


@pytest.mark.parametrize("causal", [True, False])
def test_flash_bf16_scale_that_is_no_power_of_two(causal):
    """``d_head`` 128: 1/sqrt(128) is not exact in bf16, so the scale
    meets the float32 scores, never a q rounded back to bf16.  On peaked
    softmaxes (scores of tens) the bf16 result then stays within one bf16
    unit, at the output's scale, of float32 arithmetic on the same
    inputs; a scaled q rounded to bf16 is five such units out."""
    rng = np.random.RandomState(9)
    q, k, v = (
        jnp.asarray(3.0 * rng.randn(1, 128, 2, 128), jnp.bfloat16)
        for _ in range(3)
    )
    out = flash_attention(q, k, v, causal=causal, block_q=32, block_k=64)
    ref = np.asarray(
        mha_reference(
            q.astype(jnp.float32),
            k.astype(jnp.float32),
            v.astype(jnp.float32),
            causal=causal,
        )
    )
    assert out.dtype == jnp.bfloat16
    err = np.max(np.abs(np.asarray(out, np.float32) - ref))
    assert err <= 2.0 ** -8 * np.max(np.abs(ref)), err


# (batch, tokens, heads, kv heads, width of q and k, width of v, causal,
# block, the layout the shapes must choose)
_LAYOUT_CASES = {
    # 64 wide: a block of 128 lanes is two heads, a grid cell does both
    "12x64_causal": (2, 256, 12, 12, 64, 64, True, 64, "lanes"),
    "12x64_full": (2, 256, 12, 12, 64, 64, False, 64, "lanes"),
    # the default 512-blocks: one on the diagonal worked in quarters, one
    # below it, and the saved rows read by half-block
    "12x64_seq1024": (1, 1024, 12, 12, 64, 64, True, 512, "lanes"),
    # at 128 XLA writes the projections folded and nothing is won in lanes
    "16x128": (1, 256, 16, 16, 128, 128, True, 128, "folded"),
    # ... with the sum over a key/value head's sixteen query heads
    "32x128_kv2": (1, 256, 32, 2, 128, 128, True, 128, "folded"),
    # what no block of lanes reaches stays folded
    "192_beside_128": (1, 256, 4, 4, 192, 128, True, 128, "folded"),
    "3x64_odd": (1, 256, 3, 3, 64, 64, True, 128, "folded"),
    "4x64_kv2": (1, 256, 4, 2, 64, 64, True, 128, "folded"),
}


@pytest.mark.parametrize("case", sorted(_LAYOUT_CASES))
def test_flash_layouts_forward_and_gradients_match_reference(case):
    """Every form of addressing a head against ``mha_reference``, forward
    and all three gradients, under the chip's rule for bf16; in the
    ``"lanes"`` forms nothing around the kernels turns a 4-D array."""
    b, s, h, kvh, d, d_v, causal, block, layout = _LAYOUT_CASES[case]
    rng = np.random.RandomState(len(case))

    def mk(heads, width):
        return jnp.asarray(rng.randn(b, s, heads, width), jnp.bfloat16)

    q, k, v = mk(h, d), mk(kvh, d), mk(kvh, d_v)
    w = jnp.asarray(rng.randn(b, s, h, d_v), jnp.float32)
    assert flash_layout(q, k, v) == layout

    def flash(q, k, v):
        return flash_attention(
            q, k, v, causal=causal, block_q=block, block_k=block
        )

    def ref(q, k, v):
        return mha_reference(q, k, v, causal=causal)

    got = (flash(q, k, v), *_weighted_grads(flash, w)(q, k, v))
    want = (ref(q, k, v), *_weighted_grads(ref, w)(q, k, v))
    assert [g.dtype for g in got] == [jnp.bfloat16] * 4
    assert [g.shape for g in got] == [w.shape for w in want]
    for name, err in zip(("out", "dq", "dk", "dv"), _scaled_errors(got, want)):
        assert err <= KERNEL_TOL, (name, err)
    turned = [
        eqn
        for eqn in _eqns(
            jax.make_jaxpr(_weighted_grads(flash, w))(q, k, v).jaxpr
        )
        if eqn.primitive.name == "transpose"
        and eqn.invars[0].aval.ndim == 4
    ]
    assert bool(turned) == (layout == "folded"), turned


@pytest.mark.parametrize(
    "cell,shape,kv_heads,d_v,layout",
    [
        ("gpt2s_seq1024", (8, 1024, 12, 64), 12, 64, "lanes"),
        ("gpt2s_seq1024_dp4", (8, 1024, 12, 64), 12, 64, "lanes"),
        ("gpt2s_seq8192", (1, 8192, 12, 64), 12, 64, "lanes"),
        ("olmoe_1b7b_seq4096", (2, 4096, 16, 128), 16, 128, "folded"),
        ("nemotron_twotower_seq8192", (1, 8192, 32, 128), 2, 128, "folded"),
        ("joyai_flash_seq8192", (1, 8192, 32, 192), 32, 128, "folded"),
    ],
)
def test_flash_layout_of_each_benchmark_cell(
    cell, shape, kv_heads, d_v, layout
):
    """What a device of each LM cell hands the kernels (on ``dp4`` each
    chip its own 8 sequences), from shapes alone."""
    q = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    k = jax.ShapeDtypeStruct(shape[:2] + (kv_heads, shape[3]), jnp.bfloat16)
    v = jax.ShapeDtypeStruct(shape[:2] + (kv_heads, d_v), jnp.bfloat16)
    assert flash_layout(q, k, v) == layout


@pytest.mark.parametrize("use_bias", [True, False])
@pytest.mark.parametrize(
    "kwargs,shape",
    [
        (dict(features=(4, 8)), (2, 16, 32)),  # into heads
        (dict(features=32, axis=(-2, -1)), (2, 16, 4, 8)),  # out of them
    ],
)
def test_heads_dense_owns_dense_generals_parameters(kwargs, shape, use_bias):
    """``HeadsDense`` computes one 2-D product (so that no 4-D intermediate
    stands between a projection and the kernels' rows) over parameters of
    ``nn.DenseGeneral``'s names, shapes and seeded initial values: a
    checkpoint and a seed mean what they meant."""
    import flax.linen as nn

    from elasticdl_tpu.layers.attention import HeadsDense

    rng = np.random.RandomState(5)
    x = jnp.asarray(rng.randn(*shape), jnp.float32)
    mine = HeadsDense(use_bias=use_bias, name="p", **kwargs)
    flax = nn.DenseGeneral(use_bias=use_bias, name="p", **kwargs)
    key = jax.random.PRNGKey(7)
    params, want = mine.init(key, x), flax.init(key, x)
    structure = jax.tree_util.tree_structure
    assert structure(params) == structure(want)
    for a, b in zip(
        jax.tree_util.tree_leaves(params), jax.tree_util.tree_leaves(want)
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # a bias that is not zero, so that its place in the sum shows
    params = jax.tree_util.tree_map(lambda p: p + 0.25, params)
    np.testing.assert_allclose(
        np.asarray(mine.apply(params, x)),
        np.asarray(flax.apply(params, x)),
        atol=1e-5,
        rtol=1e-5,
    )
    jaxpr = jax.make_jaxpr(lambda p, x: mine.apply(p, x))(params, x)
    dots = [e for e in _eqns(jaxpr.jaxpr) if e.primitive.name == "dot_general"]
    # one product, whose result is (batch, tokens, merged features)
    assert [e.outvars[0].aval.ndim for e in dots] == [3]
