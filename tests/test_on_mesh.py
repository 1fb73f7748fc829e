"""``ops/on_mesh.py``: the one decision of how a per-device function (a
compiled kernel and what surrounds it) meets the registered mesh, held to
every caller on the 4-device virtual mesh: mapped, values and gradients are
the unmapped ones; inside a caller's own per-device region nothing is mapped
again."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from elasticdl_tpu.layers import mamba, moe, short_conv
from elasticdl_tpu.layers.attention import rope
from elasticdl_tpu.ops import gated_delta, mamba_passes, on_mesh, rotary, ssd
from elasticdl_tpu.ops.attention import attention, flash_layout
from elasticdl_tpu.parallel.mesh import MeshConfig


@pytest.fixture(autouse=True)
def _no_mesh_left_registered():
    yield
    on_mesh.set_attention_mesh(None)


def _randn(seed, *shape, dtype=jnp.float32):
    return jnp.asarray(np.random.RandomState(seed).randn(*shape), dtype)


def _regions(jaxpr, found=None):
    """Every ``shard_map`` equation of a jaxpr, the nested ones too."""
    found = [] if found is None else found
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "shard_map":
            found.append(eqn)
        for value in eqn.params.values():
            for inner in value if isinstance(value, (list, tuple)) else [value]:
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    _regions(inner, found)
    return found


# ---- the callers: ``(function of float arrays, the arrays, check)`` ------------
# ``check(regions)`` holds what a caller promises of its mapped region


def _flash(heads, kv_heads, width):
    q = _randn(0, 4, 128, heads, width)
    k, v = (_randn(s, 4, 128, kv_heads, width) for s in (1, 2))
    lanes = flash_layout(q, k, v) == "lanes"
    assert lanes == (width == 64)

    def merged_rows(regions):
        # heads read out of lanes: the region takes and gives (batch,
        # tokens, heads * 64) rows (a 4-D array at its boundary brought
        # back every copy the lanes form removes, in the compiled dp=4 step)
        assert all(
            var.aval.ndim == (3 if lanes else 4)
            for eqn in regions for var in eqn.invars + eqn.outvars
        )

    return functools.partial(attention, causal=True), (q, k, v), merged_rows


def _scan(width, states):
    """4 heads of ``width`` in 2 groups of ``states`` states, the layer's
    ``xBC``: heads of 64 at 128 states are what the two scan kernels tile
    (tests/test_ssd.py's ``KERNELS``), heads of 16 take the plain form."""
    xbc = _randn(0, 4, 24, 4 * width + 2 * 2 * states)
    dt = jnp.log1p(jnp.exp(_randn(3, 4, 24, 4)))
    a = -jnp.exp(_randn(4, 4))
    kernels = ssd.scan_tile(4, width, 2, states) is not None
    assert kernels == (width == 64)

    def kernels_inside(regions):
        # the custom_vjp's forward kernel is what the mapped region holds
        # (its backward appears once differentiated: the comparison below)
        assert all(
            ("pallas_call" in str(eqn.params["jaxpr"])) == kernels
            for eqn in regions
        )

    return (
        functools.partial(ssd.ssd_scan, groups=2, states=states, chunk=8),
        (xbc, dt, a, _randn(5, 4)), kernels_inside,
    )


def _delta_rule():
    """One key head of 128 serving two value heads of 128, 48 steps in
    chunks of 32 (the last padded): what the two delta-rule kernels tile."""
    q, k = (_randn(s, 4, 48, 1, 128) * 0.1 for s in (0, 1))
    v = _randn(2, 4, 48, 2, 128)
    g = -jnp.log1p(jnp.exp(_randn(3, 4, 48, 2))) * 0.1
    beta = jax.nn.sigmoid(_randn(4, 4, 48, 2))
    assert gated_delta.scan_tile(128, 128, 32)

    def kernels_inside(regions):
        assert all("pallas_call" in str(eqn.params["jaxpr"]) for eqn in regions)

    return (
        functools.partial(gated_delta.gated_delta_scan, chunk=32),
        (q, k, v, g, beta), kernels_inside,
    )


def _conv_silu():
    args = (_randn(0, 4, 48, 384), _randn(1, 4, 384) * 0.5, _randn(2, 384) * 0.1)
    assert mamba_passes.conv_tile(48, 384, 4)
    return mamba.conv_silu, args, None


def _short_conv():
    args = (*(_randn(s, 4, 48, 256) for s in range(3)), _randn(3, 3, 256) * 0.5)
    assert mamba_passes.conv_tile(48, 256, 3)
    return short_conv.short_conv, args, None


def _gate_norm():
    args = (_randn(0, 4, 48, 256), _randn(1, 4, 48, 256), _randn(2, 256) + 2.0)
    assert mamba_passes.gate_norm_tile(4 * 48, 256, 2)
    return functools.partial(mamba.gate_norm, groups=2, eps=1e-5), args, None


def _rope(components):
    x = _randn(0, 4, 528, 4, 128)
    assert rotary.rotate_tile(x.shape)
    positions, sections = jnp.arange(528), ()
    if components:
        sections = (16, 24, 24)
        positions = jnp.asarray(
            np.random.RandomState(1).randint(0, 528, (4, 3, 528)), jnp.int32
        )
    return (
        lambda x: rope(x, positions, 1e4, sections=sections), (x,), None
    )


def _experts(first_expert, held):
    """8 routed experts of which ``held`` are here, two a token."""
    x = _randn(0, 4, 16, 32)
    top = jnp.asarray(
        np.random.RandomState(1).randint(0, 8, (4, 16, 2)), jnp.int32
    )
    weights = jax.nn.softmax(_randn(2, 4, 16, 2))
    stacks = tuple(
        _randn(s, held, *shape) * 0.2
        for s, shape in ((3, (32, 16)), (4, (32, 16)), (5, (16, 32)))
    )

    def experts(x, weights, *stacks):
        # (the third result is the rows of the ladder's rung, which follows
        # how many experts a device holds)
        return moe._experts_on_mesh(
            x, top, weights, stacks, first_expert, 8
        )[:2]

    return experts, (x, weights, *stacks), None


CALLERS = {
    "flash_lanes-dp=4": lambda: _flash(4, 4, 64),
    "flash_lanes-dp=2,tp=2": lambda: _flash(4, 4, 64),
    "flash_folded_grouped_heads-dp=2,tp=2": lambda: _flash(4, 2, 128),
    "scan_kernels-dp=4": lambda: _scan(64, 128),
    "scan_plain-dp=4": lambda: _scan(16, 16),
    "delta_rule_kernels-dp=4": _delta_rule,
    "conv_silu-dp=4": _conv_silu,
    "short_conv-dp=4": _short_conv,
    "gate_norm-dp=2": _gate_norm,
    "rope-dp=4": lambda: _rope(False),
    "rope_mrope-dp=4": lambda: _rope(True),
    "experts-dp=4": lambda: _experts(0, 8),
    "experts_some_held-dp=4": lambda: _experts(2, 4),
    "experts_over_ep-dp=2,ep=2": lambda: _experts(0, 8),
}


def _value_and_grads(call, args, weigh):
    def weighed(*args):
        out = call(*args)
        first = out[0] if isinstance(out, tuple) else out
        return jnp.sum(weigh * first.astype(jnp.float32)), out

    # (not the weighed sum itself: its float32 rounding follows the order
    # of a hundred thousand terms)
    (_, out), grads = jax.value_and_grad(
        weighed, argnums=tuple(range(len(args))), has_aux=True
    )(*args)
    return out, grads


def _assert_close(got, want):
    for ours, theirs in zip(
        jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)
    ):
        ours, theirs = (np.asarray(v, np.float32) for v in (ours, theirs))
        scale = max(1.0, float(np.max(np.abs(theirs))))
        assert float(np.max(np.abs(ours - theirs))) <= 2e-5 * scale


@pytest.mark.parametrize("caller", sorted(CALLERS))
def test_a_caller_is_mapped_once_and_computes_what_it_computes_unmapped(caller):
    call, args, check = CALLERS[caller]()
    out = jax.eval_shape(call, *args)
    weigh = _randn(9, *(out[0] if isinstance(out, tuple) else out).shape)
    assert _regions(jax.make_jaxpr(call)(*args).jaxpr) == []  # no mesh
    # (one program, as on the mesh below)
    want = jax.jit(functools.partial(_value_and_grads, call, weigh=weigh))(args)

    layout = caller.split("-")[1]
    devices = int(np.prod([int(axis.split("=")[1]) for axis in layout.split(",")]))
    mesh = MeshConfig.from_string(layout).create(devices=jax.devices()[:devices])
    with mesh, on_mesh.attention_mesh_scope(mesh):
        assert on_mesh.resolve() == (True, mesh)
        regions = _regions(jax.make_jaxpr(call)(*args).jaxpr)
        assert regions
        if check:
            check(regions)
        _assert_close(
            jax.jit(functools.partial(_value_and_grads, call, weigh=weigh))(args),
            want,
        )

        # a caller's own per-device region: the function runs as it stands
        # on what the device holds (here everything), mapped by nobody
        def inside(*args):
            assert on_mesh.resolve() == (True, None)
            return call(*args)

        region = jax.shard_map(
            inside, mesh=mesh, in_specs=P(), out_specs=P(), check_vma=False
        )
        assert len(_regions(jax.make_jaxpr(region)(*args).jaxpr)) == 1
        _assert_close(jax.jit(region)(*args), want[0])


def test_one_device_or_no_mesh_calls_the_function_as_it_stands():
    call, args, _ = _scan(64, 128)
    assert on_mesh.resolve() == (on_mesh.default_interpret(), None)
    mesh = MeshConfig.from_string("dp=1").create(devices=jax.devices()[:1])
    with mesh, on_mesh.attention_mesh_scope(mesh):
        assert on_mesh.resolve() == (True, None)
        assert _regions(jax.make_jaxpr(call)(*args).jaxpr) == []
    # the specs as a function of the mesh are asked for only where they are
    # used; values are taken as they are
    assert on_mesh.mapped(
        lambda x, interpret: (x, interpret), (1,), specs=None
    ) == (1, on_mesh.default_interpret())
    with pytest.raises(ValueError, match="'tpu' and interpreted on 'cpu'"):
        on_mesh.kernel_interpret("gpu")
