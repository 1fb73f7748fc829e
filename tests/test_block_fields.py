"""The one block's fields (layers/attention.py, models/long_seq_transformer.py):
RMSNorm, RoPE and QK-norm each against a few lines of ``jax.numpy``, SwiGLU,
decoding with RoPE, and a pin of what the defaults (GPT-2-small's) build."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from elasticdl_tpu.layers import attention as layers
from elasticdl_tpu.models import long_seq_transformer as lm
from elasticdl_tpu.ops.attention import mha_reference
from elasticdl_tpu.trainer.state import TrainState, init_model
from elasticdl_tpu.trainer.step import build_train_step
from elasticdl_tpu.utils.args import parse_params_dict


def test_rmsnorm_is_x_over_root_mean_square_times_scale():
    x = jnp.asarray(np.random.RandomState(0).randn(3, 5, 16), jnp.float32)
    norm = layers.make_norm("rmsnorm", 1e-5, None)
    variables = norm.init(jax.random.PRNGKey(0), x)
    scale = jnp.linspace(0.5, 1.5, 16)
    got = norm.apply({"params": {"scale": scale}}, x)
    want = x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-5) * scale
    assert set(variables["params"]) == {"scale"}
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    with pytest.raises(ValueError, match="unknown norm"):
        layers.make_norm("batchnorm", 1e-5, None)


def test_rope_is_the_rotate_half_form_over_the_whole_head():
    x = jnp.asarray(np.random.RandomState(1).randn(2, 6, 3, 8), jnp.float32)
    got = layers.rope(x, jnp.arange(6), 10000.0)
    angle = jnp.arange(6)[:, None] * 10000.0 ** (-jnp.arange(0, 8, 2) / 8)
    emb = jnp.concatenate([angle, angle], -1)[None, :, None, :]
    rotated = jnp.concatenate([-x[..., 4:], x[..., :4]], -1)
    np.testing.assert_allclose(
        got, x * jnp.cos(emb) + rotated * jnp.sin(emb), rtol=1e-5, atol=1e-6
    )
    # position 0 is the identity, and q.k depends on the distance alone
    np.testing.assert_allclose(got[:, 0], x[:, 0], atol=1e-6)
    q = jnp.broadcast_to(x[:1, :1], (1, 6, 3, 8))
    r = layers.rope(q, jnp.arange(6), 10000.0)
    dots = jnp.einsum("bqhd,bkhd->bhqk", r, r)[0, 0]
    np.testing.assert_allclose(dots[1, 3], dots[2, 4], rtol=1e-5)


def test_qk_norm_is_an_rmsnorm_over_the_whole_projection_before_the_heads():
    x = jnp.asarray(np.random.RandomState(2).randn(2, 8, 16), jnp.float32)
    attn = layers.MultiHeadSelfAttention(
        num_heads=2, causal=True, use_bias=False, qk_norm=True,
        norm_eps=1e-5, rope_theta=10000.0,
    )
    variables = attn.init(jax.random.PRNGKey(0), x)
    p = jax.tree_util.tree_map(
        lambda v: v + 0.1 * jnp.cos(jnp.arange(v.size, dtype=jnp.float32)).reshape(v.shape),
        variables["params"],
    )
    assert set(p) == {"query", "key", "value", "out", "q_norm", "k_norm"}
    assert "bias" not in p["query"] and p["q_norm"]["scale"].shape == (16,)

    def normed(name, scale):
        y = jnp.einsum("bse,ehd->bshd", x, p[name]["kernel"]).reshape(2, 8, 16)
        y = y / jnp.sqrt(jnp.mean(y * y, -1, keepdims=True) + 1e-5) * scale
        return layers.rope(y.reshape(2, 8, 2, 8), jnp.arange(8), 10000.0)

    q, k = normed("query", p["q_norm"]["scale"]), normed("key", p["k_norm"]["scale"])
    v = jnp.einsum("bse,ehd->bshd", x, p["value"]["kernel"])
    want = jnp.einsum(
        "bshd,hde->bse", mha_reference(q, k, v, causal=True), p["out"]["kernel"]
    )
    np.testing.assert_allclose(
        attn.apply({"params": p}, x), want, rtol=2e-5, atol=2e-5
    )


def test_swiglu_mlp_is_down_of_silu_gate_times_up():
    x = jnp.asarray(np.random.RandomState(3).randn(2, 4, 16), jnp.float32)
    block = layers.TransformerBlock(
        norm="rmsnorm", use_bias=False, mlp="swiglu", mlp_width=24,
        attention_fields=(("num_heads", 2),),
    )
    p = block.init(jax.random.PRNGKey(0), x)["params"]
    assert p["mlp_gate"]["kernel"].shape == p["mlp_up"]["kernel"].shape == (16, 24)
    assert p["mlp_down"]["kernel"].shape == (24, 16)
    assert {"RMSNorm_0", "RMSNorm_1"} <= set(p)
    after_attention = block.apply(
        {"params": {**p, "mlp_down": {"kernel": jnp.zeros((24, 16))}}}, x
    )
    y = after_attention / jnp.sqrt(
        jnp.mean(after_attention**2, -1, keepdims=True) + 1e-6
    ) * p["RMSNorm_1"]["scale"]
    want = after_attention + (
        jax.nn.silu(y @ p["mlp_gate"]["kernel"]) * (y @ p["mlp_up"]["kernel"])
    ) @ p["mlp_down"]["kernel"]
    np.testing.assert_allclose(block.apply({"params": p}, x), want, rtol=2e-5, atol=2e-5)
    with pytest.raises(ValueError, match="unknown mlp"):
        block.clone(mlp="relu").init(jax.random.PRNGKey(0), x)


def test_decoding_with_rope_applies_the_position_of_the_cursor():
    """Greedy decoding through the KV cache equals the full forward's
    argmax: the cached keys were rotated at their own positions."""
    fields = dict(
        vocab_size=32, embed_dim=32, num_heads=2, num_layers=2,
        norm="rmsnorm", use_bias=False, positions="rope", qk_norm=True,
    )
    model = lm.custom_model(**fields)
    prompt = np.random.RandomState(4).randint(0, 32, (2, 5)).astype(np.int32)
    params = model.init(jax.random.PRNGKey(1), {"tokens": prompt})["params"]
    generated = lm.generate(params, prompt, 4, model=model)
    tokens = prompt
    for _ in range(4):
        logits = model.apply({"params": params}, {"tokens": tokens})
        tokens = np.concatenate([tokens, np.argmax(logits[:, -1:], -1)], 1)
    np.testing.assert_array_equal(np.asarray(generated), tokens)
    with pytest.raises(ValueError, match="unknown positions"):
        lm.custom_model(**{**fields, "positions": "alibi"}).init(
            jax.random.PRNGKey(0), {"tokens": prompt}
        )


def test_model_params_parser_carries_booleans_floats_and_strings():
    got = parse_params_dict(
        "norm=rmsnorm;norm_eps=1e-05;use_bias=False;positions=rope;"
        "rope_theta=10000.0;qk_norm=True;num_experts=64;dtype=bfloat16"
    )
    assert got == {
        "norm": "rmsnorm", "norm_eps": 1e-5, "use_bias": False,
        "positions": "rope", "rope_theta": 10000.0, "qk_norm": True,
        "num_experts": 64, "dtype": "bfloat16",
    }
    lm.custom_model(**got)


# what commit 0420dfb (the parent of the PR that added the fields) built
# from these arguments and read as the first three steps' losses
GPT2_DEFAULT_LEAVES = {
    "LayerNorm_0/bias": (32,), "LayerNorm_0/scale": (32,),
    "lm_head/bias": (64,), "lm_head/kernel": (32, 64),
    "tok_embed/embedding": (64, 32),
    **{
        f"block_{i}/{leaf}": shape
        for i in range(2)
        for leaf, shape in {
            "LayerNorm_0/bias": (32,), "LayerNorm_0/scale": (32,),
            "LayerNorm_1/bias": (32,), "LayerNorm_1/scale": (32,),
            "attn/key/bias": (2, 16), "attn/key/kernel": (32, 2, 16),
            "attn/out/bias": (32,), "attn/out/kernel": (2, 16, 32),
            "attn/query/bias": (2, 16), "attn/query/kernel": (32, 2, 16),
            "attn/value/bias": (2, 16), "attn/value/kernel": (32, 2, 16),
            "mlp_down/bias": (32,), "mlp_down/kernel": (128, 32),
            "mlp_up/bias": (128,), "mlp_up/kernel": (32, 128),
        }.items()
    },
}
GPT2_DEFAULT_LOSSES = [4.637887001037598, 4.274214267730713, 4.061611175537109]


def test_the_defaults_still_build_gpt2_smalls_block():
    """Every new field at its default: the parameter tree's leaf names and
    shapes, no sown collection, and the first steps' losses on a seeded
    batch equal the parent commit's."""
    rng = np.random.RandomState(0)
    feats = {"tokens": rng.randint(0, 64, (4, 16)).astype(np.int32)}
    labels = rng.randint(0, 64, (4, 16)).astype(np.int32)
    model = lm.custom_model(vocab_size=64, num_layers=2, embed_dim=32, num_heads=2)
    params, model_state = init_model(model, feats)
    leaves = {
        "/".join(k.key for k in path): tuple(leaf.shape)
        for path, leaf in jax.tree_util.tree_leaves_with_path(params)
    }
    assert leaves == GPT2_DEFAULT_LEAVES
    assert model_state == {}
    state = TrainState.create(model.apply, params, optax.adam(3e-3), model_state)
    step = build_train_step(lm.loss, compute_dtype=None)
    losses = []
    for _ in range(3):
        state, metrics = step(state, feats, labels)
        losses.append(float(metrics["loss"]))
    np.testing.assert_allclose(losses, GPT2_DEFAULT_LOSSES, rtol=1e-6)


# ---- a part's field: declared by the part, named once by the model -------------


def _parts():
    from elasticdl_tpu.layers.gated_delta import GatedDeltaNet
    from elasticdl_tpu.layers.mamba import Mamba2Mixer
    from elasticdl_tpu.layers.moe import MoEMLP

    return {
        "attention": layers.MultiHeadSelfAttention,
        "latent": layers.LatentSelfAttention,
        "moe": MoEMLP,
        "mamba": Mamba2Mixer,
        "delta": GatedDeltaNet,
    }


def _declared(module_class):
    """A flax module's own dataclass fields."""
    return set(module_class.__dataclass_fields__) - {"parent", "name"}


# what the block itself decides or refuses, and hands to every part that has
# the field: not a part's own
_HANDED_TO_ALL = {
    "dtype", "norm_eps", "use_bias", "causal", "decode", "max_decode_len"
}
# the model's own and the block's
_MODEL_OWN = {
    "vocab_size", "embed_dim", "num_layers", "positions", "layer_pattern",
    "remat_layers", "mtp_depth", "mtp_weight", "scale_embedding",
    "dropout_rate", "decode", "max_decode_len", "dtype", "norm", "norm_eps",
    "norm_outputs", "use_bias", "mlp", "mlp_width", "full_attention_rope",
    "rope_parameters", "tie_embedding", "loop_steps", "exit_entropy_weight",
}


@pytest.mark.parametrize("part", ["attention", "latent", "moe", "mamba", "delta"])
def test_the_models_table_names_declared_fields_of_the_part(part):
    """Every target of ``PART_FIELDS`` is a field the part's module
    declares, once a part, and with what the block hands to all of them the
    part has every field it needs."""
    targets = list(lm.PART_FIELDS[part].values())
    declared = _declared(_parts()[part])
    assert targets and len(set(targets)) == len(targets)
    assert set(targets) <= declared, set(targets) - declared
    assert not set(targets) & _HANDED_TO_ALL
    required = {
        name
        for name, field in _parts()[part].__dataclass_fields__.items()
        if name not in ("parent", "name")
        and field.default is field.default_factory  # both MISSING
    }
    assert required <= set(targets), required - set(targets)


def test_a_models_field_is_its_own_or_in_the_table_and_the_block_declares_no_parts():
    fields = _declared(lm.TransformerLM)
    named = [name for group in lm.PART_FIELDS.values() for name in group]
    # PR 65: the delta part's five, partial_rotary_factor, shared_expert_gate
    assert len(fields) == 71
    assert set(named) | _MODEL_OWN == fields
    assert not set(named) & _MODEL_OWN
    # once, but for what the two kinds of attention part share and the taps
    # of the two parts that convolve
    assert {
        name for name in named if named.count(name) > 1
    } == {"num_heads", "rope_theta", "conv_kernel"}
    assert set(lm.PART_FIELDS) == set(_parts())
    block = _declared(layers.TransformerBlock)
    assert len(block) <= 19  # PR 65: delta_fields
    groups = {part + "_fields" for part in _parts()}
    assert groups <= block
    for part, module_class in _parts().items():
        assert (block - groups) & _declared(module_class) <= _HANDED_TO_ALL, part
    # what the block is handed by name is the model's own
    assert block - groups - {"kind", "causal", "mlp_ratio"} <= _MODEL_OWN


def test_a_field_of_a_part_reaches_it_under_the_parts_name():
    """The groups the model builds, read back from the block it makes."""
    model = lm.custom_model(
        embed_dim=32, num_heads=2, num_layers=3, layer_pattern="wME",
        positions="rope", rope_theta=500.0, sliding_window=8, mlp_width=48,
        num_experts=4, router_scoring="sigmoid", shared_expert_width=16,
        router_aux_weight=0.02, mamba_heads=2, mamba_head_dim=16,
        ssm_state=16, ssd_chunk=8, mrope_section=[4, 2, 2],
    )
    tokens = np.zeros((1, 16), np.int32)
    blocks = _blocks_of(model, tokens)
    attention = dict(blocks["block_0"].attention_fields)
    assert attention["window"] == 8 and attention["rope_theta"] == 500.0
    assert attention["mrope_section"] == (4, 2, 2)
    assert blocks["block_0"].latent_fields == ()
    experts = dict(blocks["block_2"].moe_fields)
    assert experts["scoring"] == "sigmoid" and experts["shared_width"] == 16
    # 0: the dense MLP's width; the weight over the one expert layer
    assert experts["expert_width"] == 48
    assert experts["aux_loss_weight"] == 0.02
    mixer = dict(blocks["block_1"].mamba_fields)
    assert mixer == {
        "num_heads": 2, "head_dim": 16, "groups": 1, "state_size": 16,
        "conv_kernel": 4, "chunk": 8,
    }
    # the delta part's group, the rotating lanes and the shared expert's gate
    hybrid = lm.custom_model(
        embed_dim=32, num_heads=2, num_layers=3, layer_pattern="d*E",
        positions="rope", head_dim=16, partial_rotary_factor=0.25,
        num_experts=4, shared_expert_width=16, shared_expert_gate=True,
        linear_key_heads=1, linear_value_heads=2, linear_key_dim=8,
        linear_value_dim=8, delta_chunk=8,
    )
    blocks = _blocks_of(hybrid, tokens)
    assert dict(blocks["block_0"].delta_fields) == {
        "num_key_heads": 1, "num_value_heads": 2, "key_dim": 8, "value_dim": 8,
        "conv_kernel": 4, "chunk": 8,
    }
    assert dict(blocks["block_1"].attention_fields)["rotary_dim"] == 4
    assert dict(blocks["block_2"].moe_fields)["shared_gated"] is True
    assert dict(blocks["block_0"].attention_fields)["rotary_dim"] == 4
    # positions that are not rotary reach no part, and no group, no part
    plain = lm.custom_model(embed_dim=32, num_heads=2, num_layers=1)
    blocks = _blocks_of(plain, tokens)
    assert dict(blocks["block_0"].attention_fields)["rope_theta"] == 0.0
    assert blocks["block_0"].moe_fields == ()
    assert blocks["block_0"].delta_fields == ()
    assert dict(blocks["block_0"].attention_fields)["rotary_dim"] == 0


def _blocks_of(model, tokens):
    """The model's blocks by name, as it builds them."""
    import flax.linen as nn

    blocks = {}

    def keep(next_fun, args, kwargs, context):
        if isinstance(context.module, layers.TransformerBlock):
            blocks[context.module.name] = context.module
        return next_fun(*args, **kwargs)

    with nn.intercept_methods(keep):
        jax.eval_shape(
            lambda: model.init(jax.random.PRNGKey(0), {"tokens": tokens})
        )
    return blocks
