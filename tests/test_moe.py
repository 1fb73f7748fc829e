"""The routed expert layer (layers/moe.py): its equations against a dense
per-expert loop, no token dropped at any routing, both auxiliary losses
joining the train loss, the router's counters, and expert parallelism over
``ep`` on the virtual mesh."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from elasticdl_tpu.layers import moe
from elasticdl_tpu.layers.moe import MoEMLP, moe_sharding_rules, routed_experts
from elasticdl_tpu.models import long_seq_transformer as lm
from elasticdl_tpu.ops import grouped_matmul as gmm_ops
from elasticdl_tpu.parallel.distributed import SPMDTrainer
from elasticdl_tpu.parallel.mesh import MeshConfig
from elasticdl_tpu.telemetry import router_load
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
    """The layer and its variables, the init (interpreted kernels) one program."""
    layer = MoEMLP(**{"num_experts": 8, "expert_width": 32, **fields})
    return layer, jax.jit(layer.init, static_argnames="training")(
        jax.random.PRNGKey(0), x, training=False
    )


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

    got, want = (
        jax.jit(jax.value_and_grad(f, argnums=(0, 1)))(variables["params"], x)
        for f in (ours, loop)
    )
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
    y, held, _ = routed_experts(
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
    params, model_state = jax.jit(lambda: init_model(model, feats))()
    assert set(model_state) == set(COLLECTIONS)

    # before the train step: it donates the original state buffers
    plain = float(jax.jit(lambda variables: lm.loss(labels, model.apply(
        variables, feats, training=False
    )))({"params": params, **model_state}))
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

    params, model_state = jax.jit(lambda: init_model(model, feats))()
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


# --- a cut whose routers take no step (``router_trains``) ---

CUT = dict(
    num_experts=16, experts_per_token=4, expert_width=16, norm_topk_prob=True,
    experts_held=4, aux_loss_weight=0.0, z_loss_weight=0.0,
)


def cut_layer_loss(x, target, **fields):
    layer, variables = init_layer(x, **{**CUT, **fields})
    params = variables["params"]

    def loss(p, x):
        y, stats = layer.apply(
            {"params": p}, x, training=True, mutable=[router_load.ROUTER_STATS]
        )
        counts = stats[router_load.ROUTER_STATS]["expert_counts"]
        return jnp.mean(jnp.square(y - target)), (y, counts)

    return params, loss


def test_a_router_that_does_not_train_routes_alike_and_takes_no_gradient():
    """``router_trains=False``: the same output to the bit, the experts'
    gradients those of the trained form, a zero gradient on the router's
    weights, and at the layer's input what is left when the logits are
    constants (the trained form adds the router's transpose to it)."""
    x, target = (
        jnp.asarray(np.random.RandomState(s).randn(2, 64, 32), jnp.float32)
        for s in (0, 1)
    )
    grads, outputs = {}, {}
    for trains in (True, False):
        params, loss = cut_layer_loss(x, target, router_trains=trains)
        (_, (outputs[trains], _)), grads[trains] = jax.value_and_grad(
            loss, argnums=(0, 1), has_aux=True
        )(params, x)
    np.testing.assert_array_equal(outputs[True], outputs[False])
    (trained, trained_x), (constant, constant_x) = grads[True], grads[False]
    assert float(jnp.abs(trained["router"]["kernel"]).max()) > 0
    np.testing.assert_array_equal(constant["router"]["kernel"], 0.0)
    for name in ("w_gate", "w_up", "w_down"):
        np.testing.assert_array_equal(trained[name], constant[name])
    assert float(jnp.abs(trained_x - constant_x).max()) > 1e-6


def test_a_cuts_trained_router_sends_the_pairs_to_the_held_experts():
    """Why the field exists (PERF.md section 7, From PR 61 (c)): 4 of 16
    experts held, no balancing term, plain Adam on one batch.  Trained, the
    router moves the pairs to the experts that carry a gradient (27% of them
    at the seeded weights, 36% forty steps on); left alone it sends every
    step's pairs where it sent the first step's."""
    x, target = (
        jnp.asarray(np.random.RandomState(s).randn(2, 64, 32), jnp.float32)
        for s in (0, 1)
    )

    def held_shares(trains):
        params, loss = cut_layer_loss(x, target, router_trains=trains)
        optimizer = optax.adam(1e-2)

        @jax.jit
        def step(params, state):
            (_, (_, counts)), g = jax.value_and_grad(loss, has_aux=True)(params, x)
            updates, state = optimizer.update(g, state)
            return optax.apply_updates(params, updates), state, counts

        state, shares = optimizer.init(params), []
        for _ in range(40):
            params, state, counts = step(params, state)
            shares.append(float(counts[: CUT["experts_held"]].sum() / counts.sum()))
        return shares

    trained, constant = held_shares(True), held_shares(False)
    assert trained[0] == constant[0] and set(constant) == {constant[0]}
    assert trained[-1] > trained[0] + 0.05, trained


# --- the ladder of row buffers (layers/moe.py, ops/grouped_matmul.py) ---

TILE = 8


def routing(tokens, slots, routed, held_sizes, seed=0):
    """(tokens, slots) distinct experts a token: expert ``e`` of the held
    ones (0..len(held_sizes)-1) on ``held_sizes[e]`` tokens, every other
    slot on an absent expert."""
    rng = np.random.RandomState(seed)
    held = len(held_sizes)
    top = np.stack([
        held + rng.choice(routed - held, slots, replace=False)
        for _ in range(tokens)
    ]).astype(np.int32)
    free = [list(rng.permutation(tokens)) for _ in range(slots)]
    for expert, size in enumerate(held_sizes):
        slot = free[expert % slots]  # a token's experts stay distinct
        assert size <= len(slot)
        top[[slot.pop() for _ in range(size)], expert % slots] = expert
    return jnp.asarray(top)


def expert_inputs(tokens, slots, held, kind, seed=0, embed=16, width=8):
    rng = np.random.RandomState(seed)
    shapes = [(embed, width)] * (2 if kind == "swiglu" else 1) + [(width, embed)]
    return (
        jnp.asarray(rng.randn(tokens, embed), jnp.float32),
        jnp.asarray(rng.rand(tokens, slots) + 0.1, jnp.float32),
        tuple(jnp.asarray(rng.randn(held, *s), jnp.float32) * 0.3 for s in shapes),
    )


def value_and_grads(experts, x, weights, stacks):
    """``y`` and the gradients of ``sum(sin(y))`` in x, weights, stacks."""
    def loss(x, weights, stacks):
        y = experts(x, weights, stacks)
        return jnp.sum(jnp.sin(y)), y

    (_, y), grads = jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True)(
        x, weights, stacks
    )
    return jax.tree_util.tree_leaves((y, grads))


SHARES = {
    # routed experts, held, slots, pairs a held expert gets
    # (a sixteenth held, top-6: the one ladder of three rungs here;
    # ``ops/grouped_matmul.py::ladder`` sizes the low rung by that share)
    "2_of_32": (32, 2, 6, [5, 9]),
    "4_of_16": (16, 4, 2, [9, 0, 17, 3]),
    "2_of_8": (8, 2, 2, [11, 6]),
}


@pytest.mark.parametrize("kind", ["swiglu", "relu2"])
@pytest.mark.parametrize("share", SHARES.values(), ids=SHARES.keys())
def test_low_rung_and_full_rung_agree(share, kind):
    """One routing through the low rung and through the full one: the same
    rows through the same kernels, so with the pair-indexed gathers on both
    ``y``, ``d_x``, ``d_weights`` and the stacks' gradients are equal to the
    last bit; the low rung's own form adds a token's rows in row order where
    the gathers add them in slot order, a float32 rounding of a sum of at
    most ``slots`` terms apart.  ``routed_experts`` takes the low rung by
    itself and says so."""
    routed, held, slots, sizes = share
    tokens = 64
    top = routing(tokens, slots, routed, sizes)
    x, weights, stacks = expert_inputs(tokens, slots, held, kind)
    low, *_, full = gmm_ops.ladder(tokens * slots, held, routed, TILE)
    group_ids = jnp.where(top < held, top, held).reshape(-1)
    order = gmm_ops.group_order(group_ids, held)
    assert int(gmm_ops.tiles_needed(order.sizes, TILE)) * TILE <= low < full

    def at(rows, by_rows):
        # an absent pair weighs nothing (its weight's gradient reads row 0)
        return lambda x, weights, stacks: moe._experts_at(
            rows, by_rows, TILE, None, x, jnp.where(top < held, weights, 0.0),
            group_ids, order, stacks,
        )[0]

    def chosen(x, weights, stacks):
        y, rows_held, buffer_rows = routed_experts(
            x, top, weights, *stacks, num_experts=routed, tile_rows=TILE
        )
        chosen.counts = (rows_held, buffer_rows)
        return y

    want = value_and_grads(at(full, False), x, weights, stacks)
    for got in value_and_grads(at(low, False), x, weights, stacks), want:
        for a, b in zip(got, want):
            np.testing.assert_array_equal(a, b)
    # (one program each, where the calls above run their products one by one)
    by_rows = jax.jit(functools.partial(value_and_grads, at(low, True)))(
        x, weights, stacks
    )
    laddered = value_and_grads(chosen, x, weights, stacks)
    for a, b, c in zip(by_rows, laddered, want):
        bound = 4e-7 * float(jnp.abs(c).max())
        np.testing.assert_allclose(a, c, rtol=0, atol=bound)
        np.testing.assert_allclose(b, c, rtol=0, atol=bound)
    assert int(chosen.counts[0]) == sum(sizes)
    assert list(np.asarray(chosen.counts[1])) == [low, full]


CROSSINGS = {
    # pairs a held expert gets (8 of 128, top-6, 64 tokens: the low rung is
    # 14 tiles of 8 rows, the next 28, the full one 56) -> the rung that
    # must be taken
    "fits_the_low_rung_exactly": ([56, 0, 0, 0, 0, 0, 0, 0], 0),
    "one_tile_more_than_the_low_rung": ([57, 0, 0, 0, 0, 0, 0, 0], 1),
    "fits_the_middle_rung_exactly": ([64, 64, 56, 0, 0, 0, 0, 0], 1),
    "one_tile_more_than_the_middle_rung": ([64, 64, 57, 0, 0, 0, 0, 0], 2),
    "every_token_on_every_held_expert": ([64, 64, 64, 64, 64, 64], -1),
    "no_pair_here": ([0] * 8, 0),
}


_routed_of_128 = jax.jit(
    lambda *a: routed_experts(*a, num_experts=128, tile_rows=TILE)
)


@pytest.mark.parametrize("kind", ["swiglu", "relu2"])
@pytest.mark.parametrize("case", CROSSINGS.values(), ids=CROSSINGS.keys())
def test_rung_follows_the_routing_and_nothing_is_dropped(case, kind):
    """The smallest rung that holds the routing's tiles is taken, the last
    of which holds any routing: the
    dispatch's own count of rows equals the held pairs, and the output is
    the dense per-expert sum."""
    sizes, rung = case
    held = len(sizes)
    tokens, slots, routed = 64, 6, 128
    top = routing(tokens, slots, routed, sizes, seed=3)
    x, weights, stacks = expert_inputs(tokens, slots, held, kind, seed=3)
    rungs = gmm_ops.ladder(tokens * slots, held, routed, TILE)
    y, rows_held, buffer_rows = _routed_of_128(x, top, weights, *stacks)
    assert int(rows_held) == sum(sizes) == int((np.asarray(top) < held).sum())
    assert list(np.asarray(buffer_rows)) == [rungs[rung], rungs[-1]]
    want = jnp.zeros_like(x)
    for e in range(held):
        weight = jnp.sum(jnp.where(top == e, weights, 0.0), axis=-1)
        if kind == "swiglu":
            hidden = jax.nn.silu(x @ stacks[0][e]) * (x @ stacks[1][e])
        else:
            hidden = jnp.square(jax.nn.relu(x @ stacks[0][e]))
        want = want + weight[:, None] * (hidden @ stacks[-1][e])
    np.testing.assert_allclose(y, want, rtol=1e-5, atol=1e-5)


def conditionals(jaxpr):
    """``cond`` equations of a jaxpr outside its Pallas kernels' bodies (a
    kernel's ``pl.when`` is a ``cond`` of its own)."""
    found = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            continue
        found += eqn.primitive.name == "cond"
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += conditionals(sub)
    return found


@pytest.mark.parametrize(
    "fields, choices",
    [
        ({"num_experts": 8}, 0),
        ({"num_experts": 64, "experts_per_token": 8}, 0),
        ({"num_experts": 16, "experts_held": 8}, 0),  # twice a half is all
        ({"num_experts": 16, "experts_held": 4}, 2),
        ({"num_experts": 16, "experts_held": 4, "expert_kind": "relu2"}, 2),
    ],
    ids=["all_8", "all_64", "half", "4_of_16", "4_of_16_relu2"],
)
def test_a_layer_that_holds_all_its_experts_has_no_conditional(fields, choices):
    """Where every routed expert is laid out there is one rung and the
    program is the one it was: no ``cond`` in forward or backward.  A layer
    with a ladder chooses once in each."""
    x = jnp.asarray(np.random.RandomState(0).randn(1, 1024, 16), jnp.float32)
    layer, variables = init_layer(x, **{"experts_per_token": 2, **fields})

    def loss(params, x):
        y, _ = layer.apply({**variables, "params": params}, x, mutable=COLLECTIONS)
        return jnp.sum(y)

    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(variables["params"], x)
    assert conditionals(jaxpr.jaxpr) == choices


def test_router_load_reads_the_rung_each_layer_took():
    """``buffer_rows`` / ``buffer_share`` of ``router_load.read()`` follow
    the routing across a rung: a router that sends every pair to the four
    held experts of sixteen walks the full buffer (share 1.0), one that
    sends them away walks the low rung; a layer that holds all its experts
    reads 1.0 whatever the routing."""
    x = jnp.ones((1, 1024, 16), jnp.float32)
    layer, variables = init_layer(
        x, num_experts=16, experts_held=4, experts_per_token=2
    )
    low, *_, full = gmm_ops.ladder(2048, 4, 16, gmm_ops.TILE_ROWS)

    @jax.jit
    def counted(kernel):
        params = {**variables["params"], "router": {"kernel": kernel}}
        return layer.apply(
            {**variables, "params": params}, x, mutable=COLLECTIONS
        )[1]

    def read(favoured):
        return router_load.read(
            counted(jnp.zeros((16, 16)).at[:, favoured].set(1.0))
        )

    here, away = read(jnp.array([0, 1])), read(jnp.array([14, 15]))
    assert (here["held_pairs"], here["buffer_rows"], here["buffer_share"]) == (
        2048, full, 1.0
    )
    assert (away["held_pairs"], away["buffer_rows"]) == (0, low)
    assert away["buffer_share"] == low / full
    assert here["dropped_pairs"] == away["dropped_pairs"] == 0

    whole, variables = init_layer(x, num_experts=16, experts_per_token=2)
    _, state = jax.jit(lambda v: whole.apply(v, x, mutable=COLLECTIONS))(variables)
    load = router_load.read(state)
    assert load["buffer_share"] == 1.0
    assert load["buffer_rows"] == gmm_ops.num_rows(2048, 16, gmm_ops.TILE_ROWS)


def test_ep_ranks_take_the_low_rung_and_train_like_one_device():
    """Eight experts over ``ep=4``: each rank lays out its two experts'
    pairs on a ladder of share 1/4, a balanced routing takes the low rung
    on every rank (``buffer_share`` under 1), and the step's loss is the
    one-device step's, which has one rung and the pair-indexed gathers."""
    rng = np.random.RandomState(0)
    feats = {"tokens": rng.randint(0, 64, (4, 512)).astype(np.int32)}
    labels = rng.randint(0, 64, (4, 512)).astype(np.int32)
    mesh = MeshConfig.from_string("dp=2,ep=4").create()
    model = lm.custom_model(
        vocab_size=64, num_layers=1, embed_dim=32, num_heads=2,
        num_experts=8, experts_per_token=2, positions="rope",
    )
    trainer = SPMDTrainer(
        mesh, model, lm.loss, optax.adam(3e-3), feats,
        rules=tuple(lm.sharding_rules(mesh)),
    )
    params, model_state = jax.jit(lambda: init_model(model, feats))()
    one_device = build_train_step(lm.loss, compute_dtype=None)(
        TrainState.create(model.apply, params, optax.adam(3e-3), model_state),
        feats, labels,
    )[1]["loss"]
    losses = [
        float(trainer.train_step(
            trainer.place_batch(feats), trainer.place_batch(labels)
        )["loss"])
        for _ in range(3)
    ]
    np.testing.assert_allclose(losses[0], float(one_device), rtol=1e-5)
    assert losses[-1] < losses[0], losses
    load = router_load.read()
    low, *_, full = gmm_ops.ladder(2 * 512 * 2, 2, 8, gmm_ops.TILE_ROWS)
    assert load["dropped_pairs"] == 0 and load["pairs"] == 4 * 512 * 2
    # eight devices, each with a buffer of its own
    assert load["buffer_rows"] == 8 * low
    assert load["buffer_share"] == low / full


def test_kernels_of_the_ladders_backward_keep_their_op_names():
    """The backward of the ladder differentiates the taken rung inside its
    branch.  A kernel under a differentiation with no scope inside it is
    named ``jvp(expert_gmm_fwd)`` on the device's op line, where ``perf/``
    looks for ``expert_gmm_fwd``: so each of them sits under the scope
    ``rung``, which takes the transform's brackets instead."""
    top = routing(64, 6, 128, [5, 0, 9, 1, 3, 8, 2, 4])
    x, weights, stacks = expert_inputs(64, 6, 8, "relu2")

    def loss(x, weights, stacks):
        return jnp.sum(routed_experts(
            x, top, weights, *stacks, num_experts=128, tile_rows=TILE,
            interpret=False,
        )[0])

    def kernels(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                yield str(eqn.source_info.name_stack)
            else:
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    yield from kernels(sub)

    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(x, weights, stacks)
    scopes = list(kernels(jaxpr.jaxpr))
    # three rungs, each two forward kernels and, in the backward, those two
    # again, two input gradients and two weight gradients; the two rungs
    # below the full one add their rows into the tokens by a kernel: the
    # combine, the combine again in the backward's forward, and the
    # dispatch's transpose
    assert len(gmm_ops.ladder(64 * 6, 8, 128, TILE)) == 3
    assert len(scopes) == 3 * (2 + 6) + 2 * 3
    names = {gmm_ops.GMM_FWD, gmm_ops.GMM_DX, gmm_ops.GMM_DW, gmm_ops.ROWS_SUM}
    assert all(s.rsplit("/", 1)[-1] in names for s in scopes), scopes
    assert sum(s.endswith(gmm_ops.ROWS_SUM) for s in scopes) == 2 * 3
    assert sum("jvp(rung)" in s for s in scopes) == 3 * 4 + 2
    # and no scatter of activations is left on any rung: what is scattered
    # is a scalar an entry (the sort's counts, the full rung's layout, the
    # weights' gradient put at its pair)
    def scatters(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name.startswith("scatter"):
                yield eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from scatters(sub)

    assert all(eqn.outvars[0].aval.ndim == 1 for eqn in scatters(jaxpr.jaxpr))


BY_ROWS = {
    # tokens, routed experts, slots, pairs a held expert gets, tile rows
    "8_of_128": (64, 128, 6, [5, 0, 9, 1, 3, 8, 2, 4], 8),
    "a_token_on_every_held_expert": (48, 16, 4, [48, 48, 48, 48], 16),
    "tokens_that_end_mid_tile": (200, 8, 2, [150, 77], 8),
}


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "f32"])
@pytest.mark.parametrize("case", BY_ROWS.values(), ids=BY_ROWS.keys())
def test_by_rows_forms_and_their_gradients_are_the_scatter_adds(case, dtype):
    """``_combine_by_rows`` and ``_dispatch_by_rows`` on a low rung's layout
    against the plain forms they are written from: the combine as a float32
    scatter-add of weighted rows and its two gradients by autodiff, the
    dispatch's transpose as the scatter-add of the rows' cotangents."""
    tokens, routed, slots, sizes, tile = case
    held = len(sizes)
    top = routing(tokens, slots, routed, sizes, seed=3)
    group_ids = jnp.where(top < held, top, held).reshape(-1)
    # a rung that holds them, with two tiles of no group after the last
    rows = gmm_ops.num_rows(sum(sizes), held, tile) + 2 * tile
    order = gmm_ops.group_order(group_ids, held)
    layout = gmm_ops.group_layout(group_ids, held, tile, rows, order, True)
    spans = gmm_ops.token_spans(group_ids, order.sizes, tokens, tile)
    assert int(jnp.sum(layout.row_pair < tokens * slots)) == sum(sizes)
    rng = np.random.RandomState(4)
    x = jnp.asarray(rng.randn(tokens, 32), dtype)
    buffer = jnp.asarray(rng.randn(rows, 32), dtype)
    weights = jnp.where(
        top < held, jnp.asarray(rng.rand(tokens, slots) + 2.0**-20, jnp.float32), 0.0
    )
    d_y = jnp.asarray(rng.randn(tokens, 32), dtype)
    d_buffer = jnp.asarray(rng.randn(rows, 32), dtype)
    row_weight, row_token = moe._rows_of(weights, layout.row_pair)

    def scatter_add(values):
        return jnp.zeros((tokens, 32), jnp.float32).at[row_token].add(
            values.astype(jnp.float32), mode="drop"
        )

    def plain_combine(buffer, weights):
        row_weight, _ = moe._rows_of(weights, layout.row_pair)
        return scatter_add(
            buffer.astype(jnp.float32) * row_weight[:, None]
        ).astype(dtype)

    def close(got, want):
        got, want = (np.asarray(v, np.float32) for v in (got, want))
        if dtype == jnp.bfloat16 and got.ndim == 2:
            # one rounding on either side of sums whose terms' order differs
            np.testing.assert_allclose(got, want, rtol=2.0**-7, atol=0)
            assert np.mean(got == want) > 0.99
        else:
            np.testing.assert_allclose(
                got, want, rtol=0, atol=4e-7 * np.abs(want).max()
            )

    y, vjp = jax.vjp(
        lambda b, w: moe._combine_by_rows(b, w, layout.row_pair, spans, None),
        buffer, weights,
    )
    want_y, want_vjp = jax.vjp(plain_combine, buffer, weights)
    close(y, want_y)
    for got, want in zip(vjp(d_y), want_vjp(d_y)):
        assert got.dtype == want.dtype
        close(got, want)

    dispatched, vjp = jax.vjp(
        lambda x: moe._dispatch_by_rows(x, row_token, spans, tokens, None), x
    )
    np.testing.assert_array_equal(
        dispatched, x[jnp.minimum(row_token, tokens - 1)]
    )
    (d_x,) = vjp(d_buffer)
    assert d_x.dtype == dtype
    close(d_x, scatter_add(d_buffer).astype(dtype))
