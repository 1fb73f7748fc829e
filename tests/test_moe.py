"""The routed expert layer (layers/moe.py): its equations against a dense
per-expert loop, no token dropped at any routing, both auxiliary losses
joining the train loss, the router's counters, and expert parallelism over
``ep`` on the virtual mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from elasticdl_tpu.layers.moe import MoEMLP, moe_sharding_rules, routed_experts
from elasticdl_tpu.models import long_seq_transformer as lm
from elasticdl_tpu.parallel.distributed import SPMDTrainer
from elasticdl_tpu.parallel.mesh import MeshConfig
from elasticdl_tpu.telemetry import MetricsRegistry, router_load
from elasticdl_tpu.trainer.state import TrainState, init_model
from elasticdl_tpu.trainer.step import build_train_step

COLLECTIONS = ["losses", router_load.ROUTER_STATS]


def dense_loop(variables, x, experts_per_token, norm_topk_prob=False):
    """OLMoE's block as a loop over every expert on every token."""
    p = variables["params"]
    tokens = x.reshape(-1, x.shape[-1])
    probs = jax.nn.softmax(tokens @ p["router"]["kernel"], axis=-1)
    top, chosen = jax.lax.top_k(probs, experts_per_token)
    if norm_topk_prob:
        top = top / top.sum(-1, keepdims=True)
    y = jnp.zeros_like(tokens)
    for e in range(p["w_gate"].shape[0]):
        weight = jnp.sum(jnp.where(chosen == e, top, 0.0), axis=-1)
        hidden = jax.nn.silu(tokens @ p["w_gate"][e]) * (tokens @ p["w_up"][e])
        y = y + weight[:, None] * (hidden @ p["w_down"][e])
    return y.reshape(x.shape)


def init_layer(x, **fields):
    layer = MoEMLP(**{"num_experts": 8, "expert_width": 32, **fields})
    return layer, layer.init(jax.random.PRNGKey(0), x, training=False)


@pytest.mark.parametrize(
    "fields",
    [
        {"experts_per_token": 2},
        {"experts_per_token": 2, "norm_topk_prob": True},
        {"experts_per_token": 1},
        {"num_experts": 64, "experts_per_token": 8},
    ],
    ids=["top2", "top2_normed", "top1", "top8_of_64"],
)
def test_moe_is_the_dense_per_expert_loop(fields):
    """Output and every gradient against the loop, float32: the two differ
    by the order of their sums (measured 2e-7)."""
    x = jnp.asarray(np.random.RandomState(0).randn(2, 24, 16), jnp.float32)
    layer, variables = init_layer(x, **fields)
    normed = fields.get("norm_topk_prob", False)

    def ours(params, x):
        y, _ = layer.apply({**variables, "params": params}, x, mutable=COLLECTIONS)
        return jnp.sum(jnp.sin(y))

    def loop(params, x):
        y = dense_loop({"params": params}, x, fields["experts_per_token"], normed)
        return jnp.sum(jnp.sin(y))

    got = jax.value_and_grad(ours, argnums=(0, 1))(variables["params"], x)
    want = jax.value_and_grad(loop, argnums=(0, 1))(variables["params"], x)
    for a, b in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("tile_rows", [8, 16, 64])
def test_no_token_is_dropped_when_every_token_picks_one_expert(tile_rows):
    """All 48 tokens to expert 3 with weight 1: every row equals that
    expert's MLP of it, whatever the tile, and the dispatch counts 48 rows."""
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(48, 16), jnp.float32)
    stacks = [jnp.asarray(rng.randn(4, *s), jnp.float32) * 0.3
              for s in ((16, 8), (16, 8), (8, 16))]
    chosen = jnp.full((48, 1), 3, jnp.int32)
    y, held = routed_experts(
        x, chosen, jnp.ones((48, 1)), *stacks, tile_rows=tile_rows
    )
    want = (jax.nn.silu(x @ stacks[0][3]) * (x @ stacks[1][3])) @ stacks[2][3]
    np.testing.assert_allclose(y, want, rtol=1e-5, atol=1e-6)
    assert int(held) == 48
    assert np.abs(np.asarray(y)).sum(-1).min() > 0


def test_moe_aux_losses_join_train_loss_and_counters_ride_out():
    """Both sown losses reach the training loss through ``forward_loss``,
    and the router's counts leave the step in the state, unread."""
    rng = np.random.RandomState(0)
    feats = {"tokens": rng.randint(0, 64, (4, 16)).astype(np.int32)}
    labels = rng.randint(0, 64, (4, 16)).astype(np.int32)
    model = lm.custom_model(
        vocab_size=64, num_layers=2, embed_dim=32, num_heads=2,
        num_experts=4, experts_per_token=2,
    )
    params, model_state = init_model(model, feats)
    assert set(model_state) == set(COLLECTIONS)

    # before the train step: it donates the original state buffers
    plain = float(lm.loss(labels, model.apply(
        {"params": params, **model_state}, feats, training=False
    )))
    state = TrainState.create(
        model.apply, params, optax.sgd(0.0), model_state
    )
    train_step = build_train_step(lm.loss, compute_dtype=None)
    state, metrics = train_step(state, feats, labels)
    sown = state.model_state["losses"]
    names = {k for block in sown.values() for k in block["moe"]}
    assert names == {"moe_load_balance", "moe_router_z"}
    aux = float(sum(np.asarray(a).sum() for a in jax.tree_util.tree_leaves(sown)))
    assert aux > 0
    # dropout=0, lr=0: train loss = plain forward loss + both losses
    np.testing.assert_allclose(float(metrics["loss"]), plain + aux, rtol=2e-4)
    # a uniform router gives a load-balance loss of k = 2 a layer, weighed
    # 0.01 over the mean of two layers: near 0.02 at the init
    balance = sum(float(b["moe"]["moe_load_balance"]) for b in sown.values())
    assert 0.02 <= balance < 0.03

    load = router_load.read(state.model_state)
    assert load["layers"] == 2 and load["pairs"] == 2 * 4 * 16 * 2
    assert load["dropped_pairs"] == 0
    assert load["max_over_mean"] >= 1.0
    registry = MetricsRegistry()
    assert router_load.publish(registry, state.model_state) == load
    assert "elasticdl_router_max_over_mean" in registry.exposition()
    # a dense model has nothing to read
    assert router_load.read({}) is None


@pytest.mark.parametrize("mesh_shape", ["dp=2,ep=2,sp=2", "dp=2,ep=4"])
def test_moe_transformer_trains_on_ep_mesh(mesh_shape):
    """Experts sharded over ep, batch over dp (sequence over sp): the
    jitted step runs, equals the one-device step's loss, and the loss drops;
    the trainer is the one ``router_load.read`` looks at."""
    rng = np.random.RandomState(0)
    feats = {"tokens": rng.randint(0, 64, (4, 32)).astype(np.int32)}
    labels = rng.randint(0, 64, (4, 32)).astype(np.int32)
    mesh = MeshConfig.from_string(mesh_shape).create()
    model = lm.custom_model(
        vocab_size=64, num_layers=1, embed_dim=32, num_heads=2,
        num_experts=4, experts_per_token=2, positions="rope",
    )
    trainer = SPMDTrainer(
        mesh, model, lm.loss, optax.adam(3e-3), feats,
        rules=tuple(lm.sharding_rules(mesh)),
    )
    for name in ("w_gate", "w_up", "w_down"):
        spec = trainer.state.params["block_0"]["moe"][name].sharding.spec
        assert "ep" in str(spec), spec

    params, model_state = init_model(model, feats)
    one_device = build_train_step(lm.loss, compute_dtype=None)(
        TrainState.create(model.apply, params, optax.adam(3e-3), model_state),
        feats, labels,
    )[1]["loss"]

    losses = []
    for _ in range(6):
        m = trainer.train_step(
            trainer.place_batch(feats), trainer.place_batch(labels)
        )
        losses.append(float(m["loss"]))
    np.testing.assert_allclose(losses[0], float(one_device), rtol=1e-5)
    assert all(np.isfinite(l) for l in losses)
    assert losses[-1] < losses[0], losses
    load = router_load.read()
    assert load["pairs"] == 4 * 32 * 2 and load["dropped_pairs"] == 0


def test_moe_sharding_rules_match_paths():
    rules = moe_sharding_rules()
    for leaf in ("w_gate", "w_up", "w_down"):
        assert any(r.matches(f"block_0/moe/{leaf}") for r in rules)
    assert not any(r.matches("block_0/moe/router/kernel") for r in rules)
    assert not any(r.matches("block_0/mlp_up/kernel") for r in rules)


def test_moe_refuses_more_slots_than_experts():
    x = jnp.zeros((1, 4, 8))
    with pytest.raises(ValueError, match="experts_per_token"):
        MoEMLP(num_experts=2, experts_per_token=3).init(
            jax.random.PRNGKey(0), x
        )
