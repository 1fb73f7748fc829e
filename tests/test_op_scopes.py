"""``telemetry/op_scopes.py``: the compiled program's op -> scope map.

The rules on hand-written name stacks and a hand-written module; then, for a
tiny model of each family through ``build_train_step`` on the CPU, what the
map of the real compiled step says — every region has a name (a module's, a
``named_scope``'s of the sources, a kernel's), the scopes change nothing but metadata, and the map of a program
that went through the program store's serialisation is the built one's."""

import contextlib
import functools
import glob
import hashlib
import json
import os
import re

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from elasticdl_tpu.models import long_seq_transformer as lm
from elasticdl_tpu.models import resnet50_model
from elasticdl_tpu.ops import attention as attention_ops
from elasticdl_tpu.ops import grouped_matmul as gmm_ops
from elasticdl_tpu.ops import mamba_passes
from elasticdl_tpu.ops import short_conv as short_conv_ops
from elasticdl_tpu.ops import gated_delta as gated_delta_ops
from elasticdl_tpu.ops import sparse_attention as sparse_ops
from elasticdl_tpu.ops import ssd as ssd_ops
from elasticdl_tpu.parallel.distributed import SPMDTrainer
from elasticdl_tpu.parallel.mesh import MeshConfig
from elasticdl_tpu.telemetry import compile_tracker, op_scopes
from elasticdl_tpu.trainer.state import TrainState
from elasticdl_tpu.trainer.step import build_train_step

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
KERNELS = {
    attention_ops.FLASH_FWD, attention_ops.FLASH_DQ, attention_ops.FLASH_DKV,
    gmm_ops.GMM_FWD, gmm_ops.GMM_DX, gmm_ops.GMM_DW,
    ssd_ops.SSD_FWD, ssd_ops.SSD_BWD,
    mamba_passes.GATE_NORM_FWD, mamba_passes.GATE_NORM_BWD,
    mamba_passes.MAMBA_CONV_FWD, mamba_passes.MAMBA_CONV_BWD,
    attention_ops.SELECTED_FWD, attention_ops.SELECTED_DQ,
    attention_ops.SELECTED_DKV, attention_ops.WINDOW_FWD,
    attention_ops.WINDOW_DQ, attention_ops.WINDOW_DKV, sparse_ops.INDEX_SELECT,
    sparse_ops.INDEX_SELECT_HINTED, sparse_ops.INDEXER_KL,
    short_conv_ops.SHORT_CONV_FWD, short_conv_ops.SHORT_CONV_BWD,
    gated_delta_ops.GDN_FWD, gated_delta_ops.GDN_BWD,
}
# modules that hold other modules: an op directly under one of these is in
# a region nobody named
MIXERS = {"attn", "moe", "mamba", "conv", "gdn"}

STEP = "jit(train_step)/"
BLOCK = "block_11/block_11._residual/block_11._attention/attn/"


@pytest.mark.parametrize(
    "op_name,part,phase",
    [
        # flax's method scopes (``block_11._attention``) go, counters fold
        (
            STEP + "jvp(TransformerLM)/" + BLOCK + "query/dot_general",
            "block/attn/query", "forward",
        ),
        # the backward pass: a ``transpose(`` anywhere in the stack
        (
            STEP + "transpose(jvp(TransformerLM))/" + BLOCK + "rope/mul",
            "block/attn/rope", "backward",
        ),
        # a recomputed forward inside the backward is a recompute
        (
            STEP + "transpose(jvp(TransformerLM))/jvp(TransformerLM)/"
            "checkpoint/rematted_computation/" + BLOCK
            + "flash_fwd/pallas_call",
            "block/attn/flash_fwd", "recompute",
        ),
        # ... and the backward of a recomputed layer is the backward
        (
            STEP + "transpose(jvp(TransformerLM))/jvp(TransformerLM)/"
            "checkpoint/" + BLOCK + "out/dot_general",
            "block/attn/out", "backward",
        ),
        # control flow and a function's own jit name no region
        (
            STEP + "jvp(TransformerLM)/block_3/block_3._experts/moe/dispatch/"
            "jit(searchsorted)/vmap()/closed_call/while/body/"
            "cond/branch_1_fun/jit(_where)/select_n",
            "block/moe/dispatch", "forward",
        ),
        # a function two layers call, compiled once: the stacks strung
        # together, the first taken
        (
            STEP + "jvp(TransformerLM)/block_8/moe/dispatch/jit(searchsorted)/"
            + STEP + "jvp(TransformerLM)/block_6/moe/dispatch/jit(searchsorted)",
            "block/moe/dispatch", "forward",
        ),
        # a scope entered again inside itself counts once
        (
            STEP + "transpose(jvp(TransformerLM))/block_1/moe/rung/cond/"
            "branch_0_fun/jvp(rung)/experts/expert_gmm_dw/pallas_call",
            "block/moe/rung/experts/expert_gmm_dw", "backward",
        ),
        # an einsum's specification is no identifier
        (
            STEP + "jvp(TransformerLM)/block_0/moe/combine/nkd,nk->nd/"
            "dot_general",
            "block/moe/combine", "forward",
        ),
        # the multi-token-prediction module's names
        (
            STEP + "jvp(TransformerLM)/mtp_1_block/mtp_1_block._residual/"
            "mtp_1_block._attention/attn/join/concatenate",
            "mtp/block/attn/join", "forward",
        ),
        (STEP + "jvp(TransformerLM)/mtp_1_proj/dot_general", "mtp/proj", "forward"),
        # two counters, an anonymous child, the root alone
        (
            STEP + "jvp(ResNet50)/identity_block_2_1/bn_a/reduce_sum",
            "identity_block/bn_a", "forward",
        ),
        (STEP + "jvp(TransformerLM)/LayerNorm_0/rsqrt", "LayerNorm", "forward"),
        (STEP + "jvp(TransformerLM)/add", "model", "forward"),
        # the step's own regions
        (STEP + "jvp(loss)/vmap()/reduce_max", "loss", "forward"),
        (STEP + "transpose(jvp(loss))/vmap()/mul", "loss", "backward"),
        (STEP + "optimizer/sqrt", "optimizer", "optimizer"),
        (
            "jit(scan_steps)/while/body/jit(train_step)/optimizer/add",
            "optimizer", "optimizer",
        ),
        # an empty stack names nothing
        (STEP + "mul", None, "forward"),
        (STEP + "transpose(jvp())/mul", None, "backward"),
    ],
)
def test_canonical_part_and_phase(op_name, part, phase):
    assert op_scopes.canonical(op_name) == (part, phase)


MODULE = """HloModule jit_train_step, is_scheduled=true

%region_0.1 (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %add.0 = f32[] add(%a, %b)
}

%fused_computation.1 (param_0: f32[8,64], param_1: f32[64,512]) -> f32[64,512] {
  %param_0 = f32[8,64]{1,0} parameter(0)
  %param_1 = f32[64,512]{1,0} parameter(1)
  %dot.small = f32[8,8]{1,0} dot(%param_0, %param_0), metadata={op_name="jit(train_step)/transpose(jvp(TransformerLM))/block_0/block_0._mlp/mlp/mlp_up/dot_general" stack_frame_id=3}
  %dot.big = f32[64,512]{1,0} dot(%param_0, %param_1), metadata={op_name="jit(train_step)/transpose(jvp(TransformerLM))/lm_head/dot_general" stack_frame_id=4}
  %mul.1 = f32[64,512]{1,0} multiply(%dot.big, %param_1), metadata={op_name="jit(train_step)/optimizer/mul"}
  ROOT %add.1 = f32[64,512]{1,0} add(%mul.1, %param_1), metadata={op_name="jit(train_step)/optimizer/add"}
}

%fused_computation.2 (param_0.1: f32[8,64]) -> bf16[8,64] {
  %param_0.1 = f32[8,64]{1,0} parameter(0)
  %exp.1 = f32[8,64]{1,0} exponential(%param_0.1), metadata={op_name="jit(train_step)/jvp(TransformerLM)/block_1/block_1._attention/attn/rope/exp"}
  %convert.9 = bf16[8,64]{1,0} convert(%exp.1)
  ROOT %bitcast.9 = bf16[8,64]{1,0} bitcast(%convert.9)
}

%body.1 (p: (s32[], f32[8,64])) -> (s32[], f32[8,64]) {
  %p = (s32[], f32[8,64]{1,0}) parameter(0)
  %gte.1 = f32[8,64]{1,0} get-tuple-element(%p), index=1
  %copy.7 = f32[8,64]{0,1} copy(%gte.1)
  %neg.1 = f32[8,64]{1,0} negate(%gte.1), metadata={op_name="jit(train_step)/jvp(TransformerLM)/block_0/moe/dispatch/while/body/neg"}
  ROOT %tuple.1 = (s32[], f32[8,64]{1,0}) tuple(%gte.1, %neg.1)
}

%cond.1 (p.1: (s32[], f32[8,64])) -> pred[] {
  %p.1 = (s32[], f32[8,64]{1,0}) parameter(0)
  ROOT %lt.1 = pred[] constant(true)
}

ENTRY %main.9 (state: f32[8,64], w: f32[64,512]) -> f32[64,512] {
  %state = f32[8,64]{1,0} parameter(0), metadata={op_name="state.params['w']"}
  %w = f32[64,512]{1,0} parameter(1)
  %copy.1 = f32[8,64]{0,1:T(8,128)} copy(%state)
  %fusion.2 = bf16[8,64]{1,0} fusion(%copy.1), kind=kLoop, calls=%fused_computation.2
  %flash_fwd.3 = (bf16[8,64]{1,0:T(8,128)(2,1)}, f32[8,1,64]{2,1,0}) custom-call(%fusion.2), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/jvp(TransformerLM)/block_1/block_1._attention/attn/flash_fwd/pallas_call" stack_frame_id=9}, backend_config={"custom_call_config":{"body":"TUzvUg=="}}
  %concat.1 = f32[8,64]{1,0} custom-call(%state), custom_call_target="ConcatBitcast", metadata={op_name="jit(train_step)/jvp(TransformerLM)/tok_embed/concatenate"}
  %tuple.9 = (s32[], /*index=1*/f32[8,64]{1,0}) tuple(%state, %concat.1)
  %while.1 = (s32[], f32[8,64]{1,0}) while(%tuple.9), condition=%cond.1, body=%body.1, metadata={op_name="jit(train_step)/jvp(TransformerLM)/block_0/moe/dispatch/while"}
  %all-reduce.1 = f32[8,64]{1,0} all-reduce(%concat.1), to_apply=%region_0.1, metadata={op_name="jit(train_step)/transpose(jvp(TransformerLM))/block_0/block_0._mlp/mlp/mlp_up/dot_general"}
  %orphan.1 = f32[8,64]{1,0} iota(), iota_dimension=0
  ROOT %fusion.1 = f32[64,512]{1,0} fusion(%all-reduce.1, %w), kind=kOutput, calls=%fused_computation.1, metadata={op_name="jit(train_step)/optimizer/add"}
}
"""


def test_the_anchor_of_a_fusion_and_what_else_was_fused_into_it():
    scopes = op_scopes._scope_of_text(MODULE)
    # the largest dot inside decides, whatever the root says; the other
    # top-level parts are listed and the time is never split
    assert scopes["fusion.1"] == (
        "lm_head", "backward", "matmul", ("block", "optimizer")
    )
    # no dot inside and a root without metadata: the named instruction
    # nearest to the root
    assert scopes["fusion.2"] == ("block/attn/rope", "forward", "other", ())
    # a compiled kernel is a kernel, XLA's own custom-call is not
    assert scopes["flash_fwd.3"] == (
        "block/attn/flash_fwd", "forward", "kernel", ()
    )
    assert scopes["concat.1"] == ("tok_embed", "forward", "other", ())
    assert scopes["all-reduce.1"] == (
        "block/mlp/mlp_up", "backward", "collective", ()
    )
    # XLA's own copy of an argument is what reads it; one inside a loop's
    # body with neither is the loop's
    assert scopes["copy.1"] == scopes["fusion.2"]
    assert scopes["copy.7"] == ("block/moe/dispatch", "forward", "other", ())
    assert scopes["while.1"][0] == "block/moe/dispatch"
    assert scopes["orphan.1"] == (None, "forward", "other", ())
    # parameters, tuples and reduction regions are on no op line
    assert not {"state", "w", "tuple.9", "add.0", "dot.big", "p"} & set(scopes)


def test_attribute_sums_by_scope_and_says_what_it_could_not_place():
    scopes = op_scopes._scope_of_text(MODULE)
    other = dict(scopes, **{"fusion.2": ("optimizer", "optimizer", "other", ())})
    times = {
        "fusion.1": 0.5, "fusion.2": 0.25, "flash_fwd.3": 1.0,
        "orphan.1": 0.125, "fusion.99": 0.0625, "copy.1": 0.25,
    }
    found = op_scopes.attribute(times, [scopes])
    assert found["scopes"] == {
        ("lm_head", "backward", "matmul"): 0.5,
        ("block/attn/rope", "forward", "other"): 0.5,
        ("block/attn/flash_fwd", "forward", "kernel"): 1.0,
    }
    # held without a part, and held by no map
    assert found["unattributed"] == 0.125 + 0.0625
    assert found["fused_across"] == 0.5
    assert [name for name, _ in found["unattributed_ops"]] == [
        "orphan.1", "fusion.99"
    ]
    assert sum(found["scopes"].values()) + found["unattributed"] == sum(
        times.values()
    )
    # two programs that ran in the window and disagree on an op
    both = op_scopes.attribute(times, [scopes, other])
    assert both["unattributed"] == found["unattributed"] + 0.25
    text = op_scopes.table(found, 2.1875, depth=2, steps=2)
    rows = [line.split() for line in text.splitlines()]
    assert rows[1] == ["block/attn", "forward", "kernel", "500.000", "45.71"]
    assert rows[-3][0] == "unattributed"
    assert rows[-2] == ["total", "1093.750", "100.00"]


@functools.cache
def _source_scopes():
    """Every ``jax.named_scope`` name of the package, read from the sources:
    a literal where it is used, or a module's constant (``_FOLD``)."""
    call = re.compile(r"named_scope\(\s*([^)]*?)\s*\)")
    literal = re.compile(r"^([\"'])([^\"']*)\1$")
    constants = re.compile(r"^(_[A-Z_]+) = \"([^\"]*)\"$", re.M)
    used, by_constant = set(), {}
    for path in glob.glob(
        os.path.join(ROOT, "elasticdl_tpu", "**", "*.py"), recursive=True
    ):
        with open(path) as f:
            source = f.read()
        defined = constants.findall(source)
        named = dict(defined)
        for argument in call.findall(source):
            quoted = literal.match(argument)
            # a literal or a constant of the same file, nothing computed
            assert quoted or argument in named, (path, argument)
            name = quoted.group(2) if quoted else named[argument]
            if not quoted:
                # a constant names one region's scope: defined once a file,
                # and the same name wherever another file has it too
                assert [n for n, _ in defined].count(argument) == 1, path
                assert by_constant.setdefault(argument, name) == name, path
            used.add(name)
    return used


def test_every_scope_of_the_package_is_an_identifier_named_in_place():
    used = _source_scopes()
    assert used and all(op_scopes._IDENTIFIER.match(name) for name in used), used
    # the map's own name for the root is nobody's scope, and no scope takes
    # a kernel's name (a kernel keeps the name the op line shows)
    assert op_scopes.ROOT == "model" and op_scopes.ROOT not in used
    assert not used & KERNELS
    # what a scope is for: ``canonical`` keeps it as the part's element
    for name in used:
        part, _ = op_scopes.canonical(f"jit(step)/jvp(M)/block_3/{name}/add")
        assert part == f"block/{name}"


# ---- the real compiled step of a tiny model of each family ----------------------


def _tiny(name):
    with open(os.path.join(ROOT, "tests", "perf", "configs", name + ".json")) as f:
        return json.load(f)["run"]["model_params"]


class FirstStage(nn.Module):
    """ResNet-50's stem and first stage at a tiny width."""

    @nn.compact
    def __call__(self, features, training: bool = False):
        x = features["image"]
        x = nn.Conv(8, (7, 7), strides=(2, 2), use_bias=False, name="conv1")(x)
        x = resnet50_model._bn(training, "bn_conv1")(x)
        x = nn.max_pool(nn.relu(x), (3, 3), strides=(2, 2), padding="SAME")
        x = resnet50_model.ConvBlock(
            3, (8, 8, 32), strides=(1, 1), name="conv_block_2"
        )(x, training)
        x = resnet50_model.IdentityBlock(
            3, (8, 8, 32), name="identity_block_2_1"
        )(x, training)
        return nn.Dense(10, name="fc")(jnp.mean(x, axis=(1, 2)))


def _class_loss(labels, outputs):
    return optax.softmax_cross_entropy_with_integer_labels(
        outputs, labels
    ).mean()


def _lm_family(config, **more):
    params = dict(_tiny(config), **more)
    features = {"tokens": np.zeros((2, 64), np.int32)}
    return (
        lm.custom_model(**params), lm.loss, lm.optimizer(), features,
        np.zeros((2, 64), np.int32), bool(params.get("remat_layers")),
    )


FAMILIES = {
    "gpt2_block": lambda: _lm_family("tiny_lm"),
    "gpt2_block_remat": lambda: _lm_family("tiny_lm", remat_layers=True),
    "olmoe": lambda: _lm_family("tiny_olmoe"),
    "mamba_experts_attention": lambda: _lm_family("tiny_nemotron"),
    "latent_attention_mtp": lambda: _lm_family("tiny_joyai"),
    "sparse_attention": lambda: _lm_family("tiny_keye"),
    "window_and_full_attention": lambda: _lm_family("tiny_trinity"),
    "window_and_yarn_attention": lambda: _lm_family("tiny_mellum"),
    "short_conv_attention_experts_tied": lambda: _lm_family("tiny_lfm2"),
    "looped_stack": lambda: _lm_family("tiny_ouro"),
    "delta_rule_and_gated_attention": lambda: _lm_family("tiny_qwen3_next"),
    "resnet_first_stage": lambda: (
        FirstStage(), _class_loss, optax.sgd(0.1),
        {"image": np.zeros((2, 32, 32, 3), np.float32)},
        np.zeros((2,), np.int32), False,
    ),
}


def _lowered(family, donate=False):
    """The family's train step lowered from the shapes of its state (the
    text depends on no value), ``state`` being the tree of those shapes."""
    model, loss, tx, features, labels, _ = FAMILIES[family]()

    def create():
        variables = model.init(jax.random.PRNGKey(0), features, training=False)
        return TrainState.create(
            model.apply, variables["params"], tx,
            {k: v for k, v in variables.items() if k != "params"},
        )

    state = jax.eval_shape(create)
    step = build_train_step(loss, donate=donate)
    weights = np.ones((labels.shape[0],), np.float32)
    return state, step.lower(state, features, labels, weights)


# sha256 of each family's lowered step, kept as ``built`` lowers it
LOWERED_SHA256 = {}


@pytest.fixture(scope="module", params=sorted(FAMILIES))
def built(request):
    state, lowered = _lowered(request.param)
    LOWERED_SHA256[request.param] = hashlib.sha256(
        lowered.as_text().encode()
    ).hexdigest()
    compiled = lowered.compile()
    return request.param, state, compiled


def _modules(tree, found=None):
    """Every flax module of a parameter tree, by its canonical name."""
    found = set() if found is None else found
    for name, value in tree.items():
        if isinstance(value, dict):
            part, _ = op_scopes.canonical(f"{name}/op")
            found.update(part.split("/"))
            _modules(value, found)
    return found


def _owners(tree, found=None):
    """The modules that own parameters themselves."""
    found = set() if found is None else found
    for name, value in tree.items():
        if isinstance(value, dict):
            if any(not isinstance(v, dict) for v in value.values()):
                found.add(op_scopes.canonical(f"{name}/op")[0].split("/")[-1])
            _owners(value, found)
    return found


def test_every_region_of_the_step_has_a_name(built):
    family, state, compiled = built
    scopes = op_scopes.scope_map(compiled)
    assert op_scopes.scope_map(compiled) is scopes  # built once a program
    parts = {part for part, _, _, _ in scopes.values() if part is not None}
    modules = _modules(state.params)
    scopes_of_the_sources = _source_scopes() | {op_scopes.ROOT}
    known = modules | scopes_of_the_sources | KERNELS
    for part in parts:
        elements = part.split("/")
        assert set(elements) <= known, part
        # directly under a mixer nothing is bare: a module with parameters
        # of its own, a kernel, or a scope of the sources
        assert elements[-1] not in MIXERS, part
        if set(elements) & MIXERS:
            assert elements[-1] in (
                _owners(state.params) | scopes_of_the_sources | KERNELS
            ), part
    # the coverage of the compiled step
    held = [part for part, _, _, _ in scopes.values()]
    assert sum(p is not None for p in held) >= 0.98 * len(held)
    phases = {phase for _, phase, _, _ in scopes.values()}
    remat = FAMILIES[family]()[-1]
    assert ("recompute" in phases) is remat
    assert {"forward", "backward", "optimizer"} <= phases
    assert "optimizer" in parts and "loss" in parts
    assert {
        phase for part, phase, _, _ in scopes.values() if part == "optimizer"
    } == {"optimizer"}
    if family == "latent_attention_mtp":
        assert {"mtp/block/attn/join", "mtp/block/attn/rope"} <= parts
        assert {"block/moe/route/router", "block/moe/shared/shared_up"} <= parts
    if family == "mamba_experts_attention":
        # (the convolution, 128 channels wide here, is ops/mamba_passes.py's
        # kernel, a part of its own under the scope; the scan at 16-wide
        # heads is ops/ssd.py's plain form, whose ops are the scope's own:
        # nothing is transposed to a kernel's layout any more)
        assert {
            "block/mamba/gate_norm", "block/mamba/ssd_scan",
            "block/mamba/mamba_conv", "block/moe/dispatch", "block/moe/combine",
        } <= parts | {op_scopes.at_depth(part, 3) for part in parts}
    if family == "delta_rule_and_gated_attention":
        # the delta-rule part's projections, its three convolutions, the scan
        # (16-wide heads: ops/gated_delta.py's plain form, the scope's own
        # ops), the norm before the gate; the softmax part's gate and its
        # rotary positions; the shared expert's gate
        assert {
            "block/gdn/in_proj_qkvz", "block/gdn/in_proj_ba",
            "block/gdn/delta_conv", "block/gdn/delta_rule",
            "block/gdn/norm_gate", "block/gdn/out_proj", "block/attn/gate",
            "block/attn/rope", "block/moe/shared/shared_expert_gate",
        } <= parts | {op_scopes.at_depth(part, 3) for part in parts}
    if family == "olmoe":
        assert {"block/attn/qk_norm/q_norm", "block/attn/rope"} <= parts
    if family == "window_and_full_attention":
        # the gate's projection and its product, the norm on a part's output
        assert {"block/attn/gate", "block/norm_out/RMSNorm"} <= parts
    if family == "short_conv_attention_experts_tied":
        # the operator's two projections and its pass (128 channels here:
        # ops/short_conv.py's kernels, parts of their own under the scope);
        # the tied head's product keeps the head's name, and the tree has
        # no ``lm_head`` module to give it
        assert {
            "block/conv/in_proj", "block/conv/out_proj", "block/conv/pass",
            "lm_head",
        } <= parts | {op_scopes.at_depth(part, 3) for part in parts}
        assert "lm_head" not in state.params
        assert {
            phase for part, phase, _, _ in scopes.values() if part == "lm_head"
        } >= {"forward", "backward"}
    if family == "looped_stack":
        # a block's ops keep the block's names inside the loop; the loop's
        # own (the carry, the stacked exits) are the loop's; a pass's exit
        # and the head, which the loss applies, have theirs
        assert op_scopes.LOOP in _source_scopes()
        assert {
            op_scopes.LOOP, "block/attn/query", "block/norm_out/RMSNorm",
            "exit/norm/RMSNorm", "exit/exit_gate", "loss/lm_head",
        } <= parts
        assert not [p for p in parts if p.startswith(op_scopes.LOOP + "/")]
        for name in ("exit/exit_gate", "block/mlp/mlp_up"):
            assert {
                phase for part, phase, _, _ in scopes.values() if part == name
            } >= {"forward", "backward"}, name
        # the head's product and its two gradients are made in one loop body
        # beside the logits (``exits_cross_entropy``): the backward pass has
        # nothing of the head left to make, or to make again
        assert {
            phase for part, phase, _, _ in scopes.values()
            if part == "loss/lm_head"
        } == {"forward"}
        # (the head is a module of the tree; a training step's product is
        # the loss's, which applies it a pass at a time)
        assert "lm_head" in state.params
    if family == "resnet_first_stage":
        assert {"conv_block/conv_a", "identity_block/bn_c", "fc"} <= parts


_METADATA = re.compile(r",? ?metadata=\{[^{}]*\}")
_TABLES = re.compile(
    r"\n(FileNames|FunctionNames|FileLocations|StackFrames)\n(?:[^\n]+\n)*"
)


# sha256 of ``lowered.as_text()`` of each family's step at the parent of
# PR 63 (f1ce426), from a ``git archive`` of it through ``_lowered``: PR 63
# reaches none of these steps at 64 tokens but latent attention's, whose
# plain rotation joins q back before the rotary key is turned where the
# parent joined it after (the same operations in another order).  A PR that
# changes one of these steps on purpose pins its own text here.
PARENTS_STEP = {
    "gpt2_block":
        "fd3469bc5e7d713b7ddec50b02b938e1e1581cade354174852e0c46df4218cda",
    "gpt2_block_remat":
        "32bfd8ac88d4d58c679bd93abaee70424b93823c6142adde5aa1bc2c4ff74953",
    "looped_stack":
        "5a804e4f060c1a14a92e3ee7a9e75784e0324b9314fb91b3bcbcfa5288acca7f",
    "mamba_experts_attention":
        "a53a4b5b762fa2bea956543bd2f35b2bf1dc0549cd0c1dd21d90118ee3de1be3",
    "olmoe":
        "b91b006f777744c4ed3b4967cb069ff119056d6828cfd5a2711be81d588ad089",
    "resnet_first_stage":
        "a63d2ff069fdc8dc9da8d4bbd04ac24f6da1c1828e558f26f3ebc81aa5bf4e92",
    "short_conv_attention_experts_tied":
        "8edc0fbf70e5a44c602f2faea3e075bef189d4c575c09acc3ed962d5dce0ada4",
    "sparse_attention":
        "783fb0b0eb6c205c23c91838ccc1fd28af2329e50ae1e0698b44e9c2a706d31b",
    "window_and_full_attention":
        "4cfefd218fec71d3cd520698c6141f01c19f04392c48ed4f1aec430af840e8a8",
    "window_and_yarn_attention":
        "b3efc15557f21253f9564f47c29d2e1b1751cb267d0be22a6947a6849d61835b",
}


def test_a_step_the_new_forms_do_not_reach_lowers_to_the_parents_text(built):
    family, _, _ = built
    if family in ("latent_attention_mtp", "delta_rule_and_gated_attention"):
        # (the second is PR 65's own family: the parent cannot build it)
        assert family not in PARENTS_STEP
    else:
        assert LOWERED_SHA256[family] == PARENTS_STEP[family]


def _stripped(text):
    return _METADATA.sub("", _TABLES.sub("\n", text))


def test_a_scope_changes_metadata_and_nothing_else(built, monkeypatch):
    family, _, compiled = built
    text = compiled.as_text()
    assert "op_name=" in text
    # every scope gone, flax's too: the program compiles to the same text
    monkeypatch.setattr(
        jax, "named_scope", lambda name: contextlib.nullcontext()
    )
    _, lowered = _lowered(family)
    bare = lowered.compile().as_text()
    assert "/optimizer/" not in bare and "/loss/" not in bare
    assert _stripped(bare) == _stripped(text)


def test_the_map_survives_the_program_stores_serialisation(built):
    from jax.experimental import serialize_executable

    _, _, compiled = built
    payload, in_tree, out_tree = serialize_executable.serialize(compiled)
    loaded = serialize_executable.deserialize_and_load(
        payload, in_tree, out_tree
    )
    assert loaded is not compiled
    assert op_scopes.scope_map(loaded) == op_scopes.scope_map(compiled)


# ---- the step of a mixer the kernels tile, compiled for the chip ---------------


@pytest.fixture(scope="module")
def one_chip_mesh():
    """A mesh over one described ``v5e`` chip: its compiler is installed
    here, so Mosaic's kernels compile to the custom-calls the chip runs."""
    from jax.experimental import topologies

    try:
        topology = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return MeshConfig.from_string("dp=1").create(devices=topology.devices[:1])


def _lowered_for_the_chip(mesh, model, loss, tx, tokens=1024):
    """The train step of ``model`` over one sequence of ``tokens``, lowered
    for ``mesh``'s described chip from shapes alone (nothing is placed)."""
    from jax.sharding import NamedSharding, PartitionSpec

    features = {"tokens": np.zeros((1, tokens), np.int32)}
    labels = np.zeros((1, tokens), np.int32)
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), features, training=False)
    )
    whole = NamedSharding(mesh, PartitionSpec())

    def described(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(np.shape(x), x.dtype, sharding=whole),
            tree,
        )

    def zeros(tree):
        return jax.tree_util.tree_map(
            lambda x: jnp.zeros(x.shape, x.dtype), tree
        )

    state = jax.eval_shape(
        lambda: TrainState.create(
            model.apply, zeros(shapes["params"]), tx,
            zeros({k: v for k, v in shapes.items() if k != "params"}),
        )
    )
    step = build_train_step(loss, donate=False)
    with mesh, attention_ops.attention_mesh_scope(mesh):
        return step.lower(
            described(state), described(features), described(labels),
            described(np.ones((1,), np.float32)),
        )


def _cell_lowered(mesh, config, tokens=1024):
    """The scope of every instruction of a cell's model (``perf/configs``)
    lowered for the described chip: its HLO text with the metadata, without
    the kernels' bodies, what ``scope_map`` reads of a program, before XLA."""
    from jax._src.lib import xla_client

    with open(os.path.join(ROOT, "perf", "configs", config + ".json")) as f:
        params = json.load(f)["run"]["model_params"]
    lowered = _lowered_for_the_chip(
        mesh, lm.custom_model(**params), lm.loss, lm.optimizer(), tokens
    )
    options = xla_client._xla.HloPrintOptions()
    options.print_metadata = True
    options.print_backend_config = False
    return op_scopes._scope_of_text(
        lowered.compiler_ir("hlo").as_hlo_module().to_string(options)
    )


def test_the_mixers_passes_are_kernels_under_their_own_parts(one_chip_mesh):
    """A Mamba-2 layer wide enough for ``ops/mamba_passes.py`` and for
    ``ops/ssd.py``'s kernels (8 heads of 64 in 2 groups of 256 lanes and 128
    states, a 1,024-wide convolution, 64 steps), each layer recomputed: the
    six custom-calls keep their names, sit under the parts ``gate_norm``,
    ``mamba_conv`` and ``ssd_scan`` as kind ``kernel`` in all three phases,
    the scan kernels read the layer's own layout (nothing under the mixer is
    a ``fold``), and none of the passes' four reads as a scan, flash or
    grouped-matmul kernel to ``perf/``'s readers, which match by name."""
    from jax.sharding import NamedSharding, PartitionSpec

    from perf import expert_rooflines, layer_readers, ssd_rooflines, trace_reduce

    model, loss, tx, features, labels, _ = _lm_family(
        "tiny_nemotron", num_layers=1, layer_pattern="M", mamba_heads=8,
        mamba_head_dim=64, ssm_state=128, ssd_chunk=16,
    )
    variables = model.init(jax.random.PRNGKey(0), features, training=False)
    state = TrainState.create(model.apply, variables["params"], tx, {})
    whole = NamedSharding(one_chip_mesh, PartitionSpec())
    described = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(np.shape(x), x.dtype, sharding=whole),
        (state, features, labels, np.ones((labels.shape[0],), np.float32)),
    )
    step = build_train_step(loss, donate=False)
    with one_chip_mesh, attention_ops.attention_mesh_scope(one_chip_mesh):
        compiled = step.lower(*described).compile()
    scopes = op_scopes.scope_map(compiled)
    kernels = {
        name: (part, phase) for name, (part, phase, kind, _) in scopes.items()
        if kind == "kernel"
    }
    found = {}
    for name, (part, phase) in kernels.items():
        found.setdefault(name.split(".")[0], set()).add((part, phase))
    assert found[mamba_passes.GATE_NORM_FWD] == {
        ("block/mamba/gate_norm/gate_norm_fwd", "forward"),
        ("block/mamba/gate_norm/gate_norm_fwd", "recompute"),
    }
    assert found[mamba_passes.GATE_NORM_BWD] == {
        ("block/mamba/gate_norm/gate_norm_bwd", "backward")
    }
    assert found[mamba_passes.MAMBA_CONV_FWD] == {
        ("block/mamba/mamba_conv/mamba_conv_fwd", "forward"),
        ("block/mamba/mamba_conv/mamba_conv_fwd", "recompute"),
    }
    assert found[mamba_passes.MAMBA_CONV_BWD] == {
        ("block/mamba/mamba_conv/mamba_conv_bwd", "backward")
    }
    assert found[ssd_ops.SSD_FWD] == {
        ("block/mamba/ssd_scan/ssd_fwd", "forward"),
        ("block/mamba/ssd_scan/ssd_fwd", "recompute"),
    }
    assert found[ssd_ops.SSD_BWD] == {
        ("block/mamba/ssd_scan/ssd_bwd", "backward")
    }
    assert {
        op_scopes.at_depth(part, 3) for part, _ in kernels.values()
    } == {
        "block/mamba/gate_norm", "block/mamba/mamba_conv",
        "block/mamba/ssd_scan",
    }
    # the scan kernels read the layer's (batch, T, channels) layout: no
    # transposes to theirs and no split of xBC are left under the mixer
    under_the_mixer = {
        part for part, _, _, _ in scopes.values()
        if part is not None and part.startswith("block/mamba")
    }
    assert under_the_mixer
    assert not {p for p in under_the_mixer if "fold" in p.split("/")}
    # as perf/ reads a trace: an op's self time by its name
    ours = {
        name: 1.0 for name in kernels
        if name.split(".")[0] in (
            mamba_passes.GATE_NORM_FWD, mamba_passes.GATE_NORM_BWD,
            mamba_passes.MAMBA_CONV_FWD, mamba_passes.MAMBA_CONV_BWD,
        )
    }
    assert len(ours) == 6
    reduced = {"op_self_s": ours, "details": {}}
    patterns = [layer_readers.FLASH_KERNELS] + [
        rf"^{kernel}\b" for kernel in (
            expert_rooflines.EXPERT_KERNELS, *ssd_rooflines.KERNEL_SHARE_OF_SSD,
            "ssd_", "flash_", "expert_gmm_",
        )
    ]
    for pattern in patterns:
        assert trace_reduce.matching_seconds(reduced, pattern) == 0, pattern


def test_the_short_convolutions_pass_is_a_kernel_each_way(one_chip_mesh):
    """A gated short convolution layer at a width the kernels tile (256
    channels, 1,024 tokens), recomputed, compiled for the described chip:
    Mosaic takes both kernels, each custom-call keeps its name and sits
    under ``block/conv/pass`` in the phases the step runs it in, and neither
    reads as another family's kernel to ``perf/``'s readers, which match by
    name."""
    from perf import conv_rooflines, expert_rooflines, layer_readers, trace_reduce

    model, loss, tx, _, _, _ = _lm_family(
        "tiny_lfm2", num_layers=2, layer_pattern="c-", embed_dim=256,
    )
    compiled = _lowered_for_the_chip(one_chip_mesh, model, loss, tx).compile()
    scopes = op_scopes.scope_map(compiled)
    found = {}
    for name, (part, phase, kind, _) in scopes.items():
        if kind == "kernel":
            found.setdefault(name.split(".")[0], set()).add((part, phase))
    assert found == {
        short_conv_ops.SHORT_CONV_FWD: {
            ("block/conv/pass/short_conv_fwd", "forward"),
            ("block/conv/pass/short_conv_fwd", "recompute"),
        },
        short_conv_ops.SHORT_CONV_BWD: {
            ("block/conv/pass/short_conv_bwd", "backward")
        },
    }
    ours = {
        name: 1.0 for name, (_, _, kind, _) in scopes.items() if kind == "kernel"
    }
    assert len(ours) == 3
    reduced = {"op_self_s": ours, "details": {}}
    assert trace_reduce.matching_seconds(reduced, conv_rooflines.CONV_KERNELS) == 3
    for pattern in [layer_readers.FLASH_KERNELS] + [
        rf"^{kernel}\b" for kernel in (
            expert_rooflines.EXPERT_KERNELS, "ssd_", "flash_", "expert_gmm_",
            "mamba_conv", "gate_norm", "swa_", "dsa_", "rope_",
        )
    ]:
        assert trace_reduce.matching_seconds(reduced, pattern) == 0, pattern


def test_a_low_rungs_rows_are_summed_by_a_kernel_of_its_own_name(one_chip_mesh):
    """An expert layer that holds 8 of 64 experts (so its buffer is on the
    ladder: a low rung and the full one) compiled for the described chip:
    Mosaic takes ``expert_rows_sum`` with its row fetches and scalar tables,
    the custom-calls keep the name and sit under ``moe/rung`` as kind
    ``kernel`` (outside ``experts_other_share``, which counts kind
    ``other``): the combine in the forward, the recomputed and the
    backward's own forward, the dispatch's transpose in the backward.  To
    ``perf/``'s readers it is no grouped matmul, and the step holds no
    scatter of activations but the embedding's gradient."""
    from perf import expert_rooflines, layer_readers, trace_reduce

    model, loss, tx, _, _, _ = _lm_family(
        "tiny_lfm2", num_layers=2, layer_pattern="-E", num_experts=64,
    )
    assert len(gmm_ops.ladder(1024 * 2, 8, 64, gmm_ops.TILE_ROWS)) == 2
    compiled = _lowered_for_the_chip(one_chip_mesh, model, loss, tx).compile()
    scopes = op_scopes.scope_map(compiled)
    found = {}
    for name, (part, phase, kind, _) in scopes.items():
        if name.split(".")[0] == gmm_ops.ROWS_SUM:
            assert kind == "kernel", (name, kind)
            found.setdefault(part, set()).add(phase)
    assert set(found) == {
        f"block/moe/rung/{region}/{gmm_ops.ROWS_SUM}"
        for region in ("combine", "dispatch")
    }, found
    assert found[f"block/moe/rung/combine/{gmm_ops.ROWS_SUM}"] >= {"forward"}
    assert found[f"block/moe/rung/dispatch/{gmm_ops.ROWS_SUM}"] == {"backward"}
    ours = {
        name: 1.0 for name in scopes if name.split(".")[0] == gmm_ops.ROWS_SUM
    }
    reduced = {"op_self_s": ours, "details": {}}
    for pattern in [layer_readers.FLASH_KERNELS] + [
        rf"^{kernel}\b" for kernel in (
            expert_rooflines.EXPERT_KERNELS, "expert_gmm_", "flash_", "ssd_",
        )
    ]:
        assert trace_reduce.matching_seconds(reduced, pattern) == 0, pattern
    wide = [
        line for line in compiled.as_text().split("\n")
        if re.search(r"= \(?[a-z0-9]+\[\d+,\d+[^ ]* scatter\(", line)
    ]
    assert len(wide) == 1 and "[256,128]" in wide[0], wide


def test_sparse_attentions_kernels_are_under_their_own_parts(one_chip_mesh):
    """A sparse-attention expert layer at the published head widths (heads
    of 128 grouped 4 : 1, an indexer of 4 x 64, 1,024 tokens, top-256),
    recomputed, compiled for the described chip: Mosaic takes the five
    kernels, each custom-call keeps its name, the indexer's scores and
    selection sit under ``index_select``, its loss under ``indexer_kl``, the
    three selected-set kernels directly under ``attn``, in the phases the
    step runs them in, and none reads as a dense flash kernel to ``perf/``'s
    readers, which match by name; ``perf/dsa_rooflines.py``'s shares add up
    over that map."""
    from perf import dsa_rooflines, layer_readers, scope_shares, trace_reduce

    model, loss, tx, _, _, _ = _lm_family(
        "tiny_keye", num_layers=1, embed_dim=256, num_heads=4, num_kv_heads=1,
        head_dim=128, mrope_section=(16, 24, 24), index_topk=256,
        index_heads=4, index_head_dim=64,
    )
    compiled = _lowered_for_the_chip(one_chip_mesh, model, loss, tx).compile()
    scopes = op_scopes.scope_map(compiled)
    found = {}
    for name, (part, phase, kind, _) in scopes.items():
        if kind == "kernel":
            found.setdefault(name.split(".")[0], set()).add((part, phase))
    twice = {"forward", "recompute"}
    # the first pass searches; the recomputed one checks what it found
    assert found[sparse_ops.INDEX_SELECT] == {
        ("block/attn/index_select/dsa_index", "forward")
    }
    assert found[sparse_ops.INDEX_SELECT_HINTED] == {
        ("block/attn/index_select/dsa_index_hinted", "recompute")
    }
    # once a layer and step: the first pass knows it is being differentiated
    # (``recompute.offers_kept``) and runs the gradient variant, which writes
    # the value too; the recomputed pass is handed what it found and the
    # backward pass scales it, neither with a kernel
    assert found[sparse_ops.INDEXER_KL] == {
        ("block/attn/indexer_kl/dsa_kl", "forward")
    }
    assert found[attention_ops.SELECTED_FWD] == {
        ("block/attn/dsa_fwd", phase) for phase in twice
    }
    assert found[attention_ops.SELECTED_DQ] == {("block/attn/dsa_dq", "backward")}
    assert found[attention_ops.SELECTED_DKV] == {("block/attn/dsa_dkv", "backward")}
    assert not {"flash_fwd", "flash_dq", "flash_dkv"} & set(found)
    # as perf/ reads a trace: an op's self time by its name and by its scope
    ours = {name: 1.0 for name, (_, _, kind, _) in scopes.items() if kind == "kernel"
            and name.startswith("dsa_")}
    # seven calls a layer where PR 40 had eight (the cell's four layers: 4
    # ``dsa_index``, 4 ``dsa_index_hinted``, 4 ``dsa_kl``, 172 kernel calls
    # with the experts', PERF.md): one search, one check, one pass of the
    # indexer's loss, and the first pass of the backward rule's own
    # ``jax.checkpoint`` left no kernel behind
    assert len(ours) == 7
    assert sorted(name.split(".")[0] for name in ours) == [
        "dsa_dkv", "dsa_dq", "dsa_fwd", "dsa_fwd", "dsa_index",
        "dsa_index_hinted", "dsa_kl",
    ]
    # ``perf/kernel_rooflines.py::kernel_seconds`` reads the searching calls alone
    assert trace_reduce.matching_seconds(
        {"op_self_s": ours, "details": {}}, r"^dsa_index\b"
    ) == 1.0
    reduced = {"op_self_s": ours, "details": {}}
    assert trace_reduce.matching_seconds(reduced, layer_readers.FLASH_KERNELS) == 0
    run = {
        "trace": {"busy_s": 16.0, "op_self_s": ours},
        "_scope_shares": op_scopes.attribute(ours, [scopes]),
    }
    assert scope_shares.attributed(run) is run["_scope_shares"]
    assert dsa_rooflines.selection_time_share(run) == pytest.approx(100 * 2 / 16)
    assert dsa_rooflines.indexer_time_share(run) == pytest.approx(100 * 1 / 16)
    assert dsa_rooflines.sparse_attention_time_share(run) == pytest.approx(
        100 * 7 / 16
    )


@pytest.mark.parametrize(
    "config,calls,plain",
    [
        # four window layers rotate q and k (the full layer has no rope),
        # each layer recomputed: 4 x 2 calls a pass
        ("trinity_mini_26b_a3b", {"block/attn/rope": 8}, set()),
        # four sparse layers' main heads, 32 : 4 of 128, and since PR 63
        # the indexer's one key of 64, in its own scope: 12 calls more.  Its
        # 16 queries of 64 keep the plain form
        (
            "keye_vl2_30b_a3b",
            {"block/attn/rope": 8, "block/attn/indexer": 4},
            {(1, 1024, 16, 64)},
        ),
        # adjacent pairs on the last 64 of q's 192 lanes, the whole head in
        # place, and on the one shared rotary key (PR 63): five latent
        # layers, dense and expert, and the multi-token-prediction
        # module's, 2 calls each, 36 a step where the parent had none, and
        # nothing left to the plain form
        (
            "joyai_llm_flash_48b_a3b",
            {"block/attn/rope": 10, "mtp/block/attn/rope": 2},
            set(),
        ),
        # 32 : 8 heads of 64: several heads narrower than a lane tile keep
        # the plain form (folded they lost 1.4% end to end: PERF.md section
        # 6, PR 62), so no call, as at the parent
        ("lfm2_24b_a2b", {}, {(1, 1024, 32, 64), (1, 1024, 8, 64)}),
    ],
)
def test_the_rotary_kernels_calls_in_the_cells_models(
    one_chip_mesh, config, calls, plain, plain_rope_shapes
):
    """The benchmark's models at their published widths and 1,024 tokens,
    lowered for the described chip: ``ops/rotary.py``'s kernel is called
    under ``attn/rope`` (the indexer's under ``attn/indexer``) once an
    array, layer and pass wherever a head's rotating lanes are whole
    128-lane tiles or the 64 lanes of an array's one head, by halves or by
    adjacent pairs, and nowhere else; ``rope_plain`` is traced for the
    arrays of ``plain`` alone."""
    from elasticdl_tpu.ops import rotary

    scopes = _cell_lowered(one_chip_mesh, config)
    assert set(plain_rope_shapes) == plain
    found = {}
    for part, phase, kind, _ in scopes.values():
        if kind == "kernel" and "/rope" in part:
            found[part, phase] = found.get((part, phase), 0) + 1
    want = {}
    for owner, count in calls.items():
        fwd, bwd = (
            f"{owner}/{name}"
            for name in (rotary.ROPE_FWD, rotary.ROPE_BWD)
        )
        if owner.endswith("/indexer"):
            # the indexer is differentiated inside the first pass, under
            # the scope of the loss that asks for its gradient
            bwd = f"block/attn/indexer_kl/indexer/TransformerLM/{bwd}"
        want.update({
            (fwd, "forward"): count, (fwd, "recompute"): count,
            (bwd, "backward"): count,
        })
    assert found == want


@pytest.mark.parametrize(
    "shape,interleave,skip,components",
    [
        ((1, 32, 8192, 192), True, 128, False),  # latent attention's q whole
        ((1, 1, 8192, 64), True, 0, False),  # its one shared rotary key
        ((1, 1, 16384, 64), False, 0, True),  # the indexer's one key
    ],
    ids=["tail_pairs", "pairs_one_head", "half64_one_head_sections"],
)
def test_mosaic_takes_the_rotary_kernels_narrow_and_paired_forms(
    one_chip_mesh, shape, interleave, skip, components
):
    """``rope_fwd`` / ``rope_bwd`` at the cells' shapes PR 63 brought,
    compiled for the described chip: a roll over 64 lanes of a tile, a
    slice of a block from lane 128 on, tables 64 wide (interpret mode
    takes anything)."""
    from jax.sharding import NamedSharding, PartitionSpec

    from elasticdl_tpu.ops import rotary

    whole = NamedSharding(one_chip_mesh, PartitionSpec())
    turning = shape[3] - skip
    sections = (
        tuple(n * turning // 128 for n in (16, 24, 24)) if components else ()
    )
    positions = jax.ShapeDtypeStruct(
        (shape[0], 3, shape[2]) if components else (shape[2],), jnp.int32,
        sharding=whole,
    )
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=whole)

    def both_ways(x, g, positions):
        out, pull = jax.vjp(
            lambda x: rotary.rotate(
                x, positions, 1e4, sections, interleave, skip, False
            ),
            x,
        )
        return out, pull(g)[0]

    text = jax.jit(both_ways).lower(x, x, positions).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2


def test_a_window_layer_and_a_yarn_layer_at_published_widths(one_chip_mesh):
    """Mellum2's layer compiled for the described chip: a window layer of
    1,024 keys under the base and a full layer under YaRN at 2,304, 32 : 4
    heads of 128, 16 of 64 experts of 896 held, top-8, recomputed, 2,048
    tokens (two windows).  Mosaic takes every kernel at widths that are no
    multiple of 256; every region has a name; the YaRN tables and the scaled
    rotation sit under ``attn/rope`` (what ``attention_other_share.scope_lm``
    reads) around the one kernel; the window layer runs ``swa_*`` and the
    full layer ``flash_*`` directly under ``attn``."""
    with open(os.path.join(ROOT, "perf", "configs", "mellum2_12b_a2p5b.json")) as f:
        params = json.load(f)["run"]["model_params"]
    model = lm.custom_model(**{
        **params, "num_layers": 4, "layer_pattern": "wE*E", "vocab_size": 1024,
    })
    compiled = _lowered_for_the_chip(
        one_chip_mesh, model, lm.loss, lm.optimizer(), tokens=2048
    ).compile()
    scopes = op_scopes.scope_map(compiled)
    held = [part for part, _, _, _ in scopes.values()]
    assert sum(p is not None for p in held) >= 0.98 * len(held)
    kernels = {}
    for name, (part, phase, kind, _) in scopes.items():
        if kind == "kernel":
            kernels.setdefault(part, set()).add(phase)
    twice = {"forward", "recompute"}
    for name in ("swa_fwd", "flash_fwd", "rope/rope_fwd"):
        assert kernels[f"block/attn/{name}"] == twice, name
    for name in ("swa_dq", "swa_dkv", "flash_dq", "flash_dkv", "rope/rope_bwd"):
        assert kernels[f"block/attn/{name}"] == {"backward"}, name
    assert {
        op_scopes.at_depth(part, 3) for part in kernels if "/moe/" in part
    } >= {"block/moe/rung"}
    assert any(part.endswith(gmm_ops.ROWS_SUM) for part in kernels)
    assert any("expert_gmm_fwd" in part for part in kernels)
    # the tables' ops (the ramp's blend, cos and sin times the factor) are
    # the rope scope's own, of kind ``other``
    others = {
        part for part, _, kind, _ in scopes.values()
        if kind != "kernel" and part is not None
    }
    assert "block/attn/rope" in others


def test_mellum2s_cut_names_every_kernel_at_the_cells_shape(one_chip_mesh):
    """The whole cut ``mellum2_seq16384`` times (four layers, three window
    to one full, 24,576 rows of the vocabulary), lowered for the described
    chip at the cell's 16,384 tokens: every kernel call sits in a named
    region, once a layer and pass."""
    from elasticdl_tpu.ops import rotary

    scopes = _cell_lowered(one_chip_mesh, "mellum2_12b_a2p5b", tokens=16384)
    found = {}
    for part, phase, kind, _ in scopes.values():
        if kind == "kernel":
            assert part is not None
            found[part, phase] = found.get((part, phase), 0) + 1
    attention = {
        key: n for key, n in found.items() if key[0].startswith("block/attn/")
    }
    # q and k a layer; three window layers, one full layer
    per_pass = {"rope/" + rotary.ROPE_FWD: 8, "swa_fwd": 3, "flash_fwd": 1}
    back = {
        "rope/" + rotary.ROPE_BWD: 8, "swa_dq": 3, "swa_dkv": 3,
        "flash_dq": 1, "flash_dkv": 1,
    }
    assert attention == {
        **{
            (f"block/attn/{name}", phase): n
            for name, n in per_pass.items() for phase in ("forward", "recompute")
        },
        **{(f"block/attn/{name}", "backward"): n for name, n in back.items()},
    }
    experts = {key for key in found if key not in attention}
    assert experts and all(part.startswith("block/moe/") for part, _ in experts)


@pytest.mark.parametrize(
    "family", ["mamba_experts_attention", "latent_attention_mtp"],
    ids=["tiny_nemotron", "tiny_joyai"],
)
def test_a_recomputed_layer_without_a_selection_is_nn_remats(family, monkeypatch):
    """``layers/recompute.py`` is a sparse layer's alone: a recomputed model
    that sets no ``index_topk`` never reaches it and lowers to the text it
    lowers to with ``nn.remat`` in its place."""
    from elasticdl_tpu.layers import recompute

    assert FAMILIES[family]()[-1]  # its layers are recomputed

    def never(*args, **kwargs):
        raise AssertionError("a layer without a selection took the hinted remat")

    ours = _lowered(family)[1].as_text()
    monkeypatch.setattr(lm, "remat_with_findings", never)
    monkeypatch.setattr(recompute, "_lifted", never)
    assert _lowered(family)[1].as_text() == ours
    assert "checkpoint" in ours or "remat" in ours or "optimization_barrier" in ours


# ---- the trainer hands out the programs it dispatched ----------------------------


class Tiny(nn.Module):
    @nn.compact
    def __call__(self, features, training=False):
        return nn.Dense(4, name="head")(features["x"])


def _squares(labels, outputs):
    return jnp.mean((outputs - labels) ** 2)


def _trainer(**kwargs):
    features = {"x": np.ones((8, 3), np.float32)}
    trainer = SPMDTrainer(
        MeshConfig.from_string("dp=2").create(), Tiny(), _squares,
        optax.sgd(0.1), features, **kwargs,
    )
    batch = (
        trainer.place_batch(features),
        trainer.place_batch(np.ones((8, 4), np.float32)),
        trainer.place_mask(8, 8),
    )
    return trainer, batch


def test_nothing_is_read_before_a_step_and_nothing_compiles_for_a_read():
    compile_tracker.install()
    trainer, batch = _trainer()
    assert trainer.train_programs() == [] and op_scopes.read() is None
    trainer.train_step(*batch)
    trainer.train_step(*batch)
    before = compile_tracker.compile_count()
    programs = trainer.train_programs()
    maps = op_scopes.read()
    assert compile_tracker.compile_count() == before  # jit's own executable
    assert len(programs) == len(maps) == 1
    parts = {part for part, _, _, _ in maps[0].values()}
    assert {"head", "loss", "optimizer"} <= parts
    assert op_scopes.read()[0] is maps[0]
    # the stacked step is a program of its own, and a train program too
    stacked = jax.tree_util.tree_map(
        lambda x: np.stack([np.asarray(x)] * 2), batch
    )
    trainer.train_steps_stacked(*map(trainer.place_stacked, stacked))
    assert len(op_scopes.read()) == 2
    # the newest trainer is the one watched
    other, _ = _trainer()
    assert op_scopes.read() is None and other.train_programs() == []


def test_a_trainer_on_the_program_store_hands_out_the_stores_programs(
    tmp_path, monkeypatch
):
    import sys

    from elasticdl_tpu.parallel import program_store
    from elasticdl_tpu.utils.args import parse_master_args

    compile_tracker.install()
    store = program_store.ProgramStore(str(tmp_path / "program_store"))
    monkeypatch.setattr(program_store, "_active", store)
    args = parse_master_args(
        ["--model_def", "tests.tiny", "--training_data", "/nowhere"]
    )
    trainer, batch = _trainer(
        job_identity=program_store.job_identity(args, sys.modules[__name__])
    )
    trainer.train_step(*batch)
    (program,) = trainer.train_programs()
    assert program is trainer._stored("train_step", None, batch)
    (scopes,) = op_scopes.read()
    assert "optimizer" in {part for part, _, _, _ in scopes.values()}


# ---- a profile window, and the command that reads it --------------------------------


def test_a_window_leaves_the_op_scopes_beside_its_trace(tmp_path, monkeypatch):
    from elasticdl_tpu.utils.profiling import StepProfiler

    trainer, batch = _trainer()
    monkeypatch.setattr(jax.profiler, "start_trace", lambda d, **_options: None)
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    profiler = StepProfiler(str(tmp_path), start_step=1, num_steps=2)
    for _ in range(5):
        trainer.train_step(*batch)
        profiler.on_step()
    profiler.stop()
    written = op_scopes.load(str(tmp_path / op_scopes.OP_SCOPES_FILE))
    assert written == op_scopes.read()
    assert (tmp_path / "host_spans.json").exists()


class _Event:
    def __init__(self, name, start_ns, duration_ns):
        self.name, self.start_ns, self.duration_ns = name, start_ns, duration_ns


class _Line:
    def __init__(self, name, events):
        self.name, self.events = name, events


class _Plane:
    def __init__(self, name, lines):
        self.name, self.lines = name, lines


def test_the_command_prints_a_windows_busy_time_by_scope(
    tmp_path, monkeypatch, capsys
):
    import jax.profiler

    ops = _Line("XLA Ops", [
        _Event("%while.1 = (s32[]) while(...)", 0, 1000),
        _Event("%neg.1 = f32[8,64] negate(...)", 100, 300),
        _Event("%fusion.1 = f32[64,512] fusion(...)", 2000, 500),
        _Event("%fusion.99 = f32[] fusion(...)", 3000, 200),
    ])
    planes = [
        _Plane("/device:TPU:0", [ops, _Line("Steps", [_Event("0", 0, 5000)])]),
        _Plane("/host:CPU", [_Line("XLA Ops", [_Event("%x = y", 0, 9)])]),
    ]

    class _Data:
        @staticmethod
        def from_file(path):
            return type("D", (), {"planes": planes})

    monkeypatch.setattr(jax.profiler, "ProfileData", _Data)
    window = tmp_path / "plugins" / "profile" / "2026_01_01"
    window.mkdir(parents=True)
    (window / "host.xplane.pb").write_bytes(b"")
    assert op_scopes.main([str(tmp_path)]) == 1  # no op_scopes.json yet
    with open(window / op_scopes.OP_SCOPES_FILE, "w") as f:
        json.dump({"programs": [op_scopes._scope_of_text(MODULE)]}, f)
    op_self, busy_s = op_scopes.window_self_times(str(window / "host.xplane.pb"))
    # the loop keeps what its body's ops do not take
    assert op_self == {
        "while.1": 700e-9, "neg.1": 300e-9, "fusion.1": 500e-9,
        "fusion.99": 200e-9,
    }
    assert busy_s == 1700e-9
    assert op_scopes.main([str(tmp_path), "--depth", "2"]) == 0
    out = capsys.readouterr().out
    rows = [line.split() for line in out.splitlines()]
    assert ["block/moe", "forward", "other"] == rows[1][:3]
    assert rows[1][3] == "0.001" and rows[1][4] == "58.82"
    assert ["lm_head", "backward", "matmul"] == rows[2][:3]
    assert rows[3][0] == "unattributed"
    # the table's total is the window's busy time
    assert rows[4][0] == "total" and rows[4][-1] == "100.00"
    assert op_scopes.main([str(tmp_path / "nowhere")]) == 1


# ---- the live bytes of a scheduled program -------------------------------------------

FWD = 'metadata={op_name="jit(f)/jvp(M)/enc/op"}'
BWD = 'metadata={op_name="jit(f)/transpose(jvp(M))/enc/op"}'
HEAD = "HloModule m, is_scheduled=true"

# name -> (text, bytes at the peak, the instruction there, the loops it is
# within, some of the rows [owner, phase, role, bytes])
LIVE_CASES = {
    # a (1 KiB) feeds b (2 KiB) feeds c: a is dead when c is made
    "chain": (f"""{HEAD}

ENTRY %main (x: f32[256]) -> f32[256] {{
  %x = f32[256]{{0}} parameter(0), metadata={{op_name="tokens"}}
  %a = f32[256]{{0}} exponential(%x), {FWD}
  %b = f32[512]{{0}} concatenate(%a, %a), dimensions={{0}}, {FWD}
  ROOT %c = f32[256]{{0}} slice(%b), slice={{[0:256]}}, {FWD}
}}
""", 4096, "b", [], [["batch", "", "argument", 1024], ["enc", "forward", "temporary", 3072]]),
    # both branches of a are alive where the second is made, a with them
    "diamond": (f"""{HEAD}

ENTRY %main (x: f32[256]) -> f32[256] {{
  %x = f32[256]{{0}} parameter(0)
  %a = f32[256]{{0}} exponential(%x), {FWD}
  %b = f32[512]{{0}} concatenate(%a, %a), dimensions={{0}}, {FWD}
  %c = f32[768]{{0}} concatenate(%a, %a, %a), dimensions={{0}}, {FWD}
  ROOT %d = f32[256]{{0}} custom-call(%b, %c), custom_call_target="join", {FWD}
}}
""", 7168, "c", [], [["argument", "", "argument", 1024], ["enc", "forward", "temporary", 6144]]),
    # r is made early and read last by the backward pass: alive at the peak
    # (u), and a residual there, as u is; t dies into u's slice, a temporary
    "outlives_the_peak": (f"""{HEAD}

ENTRY %main (x: f32[256]) -> f32[256] {{
  %x = f32[256]{{0}} parameter(0)
  %r = f32[256]{{0}} exponential(%x), {FWD}
  %t = f32[2048]{{0}} broadcast(%x), dimensions={{}}, {FWD}
  %u = f32[256]{{0}} slice(%t), slice={{[0:256]}}, {FWD}
  %v = f32[256]{{0}} custom-call(%u), custom_call_target="k", {BWD}
  ROOT %g = f32[256]{{0}} custom-call(%v, %r), custom_call_target="k", {BWD}
}}
""", 11264, "u", [], [
        ["enc", "forward", "residual", 2048], ["enc", "forward", "temporary", 8192],
    ]),
    # the update is written into buf: no second kilobyte-array for its result
    "dynamic_update_slice_in_place": (f"""{HEAD}

ENTRY %main (x: f32[1024], i: s32[]) -> f32[1024] {{
  %x = f32[1024]{{0}} parameter(0)
  %i = s32[] parameter(1)
  %buf = f32[1024]{{0}} broadcast(%i), dimensions={{}}, {FWD}
  %upd = f32[256]{{0}} slice(%x), slice={{[0:256]}}, {FWD}
  %dus = f32[1024]{{0}} dynamic-update-slice(%buf, %upd, %i), {FWD}
  ROOT %out = f32[1024]{{0}} custom-call(%dus), custom_call_target="k", {FWD}
}}
""", 12292, "out", [], [["enc", "forward", "temporary", 4096], ["enc", "forward", "output", 4096]]),
    # the new parameter is written in place of the donated one and counts
    # once; the moment is not donated and is named
    "donated_argument": ("""HloModule m, is_scheduled=true, input_output_alias={ {0}: (0, {}, may-alias) }, entry_computation_layout={(f32[1024]{0}, f32[1024]{0})->(f32[1024]{0}, f32[])}

ENTRY %main (p: f32[1024], g: f32[1024]) -> (f32[1024], f32[]) {
  %p = f32[1024]{0} parameter(0), metadata={op_name="state.params[\\'w\\']"}
  %g = f32[1024]{0} parameter(1), metadata={op_name="state.opt_state[0].mu[\\'w\\']"}
  %new = f32[1024]{0} custom-call(%p, %g), custom_call_target="adam", metadata={op_name="jit(f)/optimizer/adam"}
  %loss = f32[] custom-call(%g), custom_call_target="l", metadata={op_name="jit(f)/jvp(M)/loss/l"}
  ROOT %t = (f32[1024]{0}, f32[]) tuple(%new, %loss)
}
""", 8196, "loss", [], [
        ["params", "", "argument", 4096], ["opt_state", "", "argument", 4096],
        ["loss", "forward", "output", 4],
    ]),
    # the loop's body holds 16 KiB over the 2 KiB that live across the loop;
    # its new carry is written in place of the old
    "while_body_peak": (f"""{HEAD}

%body (c: (s32[], f32[256])) -> (s32[], f32[256]) {{
  %c = (s32[], f32[256]{{0}}) parameter(0)
  %i = s32[] get-tuple-element(%c), index=0
  %v = f32[256]{{0}} get-tuple-element(%c), index=1
  %one = s32[] constant(1)
  %big = f32[4096]{{0}} broadcast(%v), dimensions={{}}, metadata={{op_name="jit(f)/jvp(M)/while/body/block_3/mlp/mul"}}
  %nv = f32[256]{{0}} slice(%big), slice={{[0:256]}}, metadata={{op_name="jit(f)/jvp(M)/while/body/block_3/mlp/slice"}}
  %ni = s32[] add(%i, %one)
  ROOT %r = (s32[], f32[256]{{0}}) tuple(%ni, %nv)
}}

%cond (c.1: (s32[], f32[256])) -> pred[] {{
  %c.1 = (s32[], f32[256]{{0}}) parameter(0)
  %i.1 = s32[] get-tuple-element(%c.1), index=0
  %n = s32[] constant(4)
  ROOT %lt = pred[] compare(%i.1, %n), direction=LT
}}

ENTRY %main (x: f32[256]) -> f32[256] {{
  %x = f32[256]{{0}} parameter(0)
  %zero = s32[] constant(0)
  %init = f32[256]{{0}} copy(%x), {FWD}
  %t = (s32[], f32[256]{{0}}) tuple(%zero, %init)
  %w = (s32[], f32[256]{{0}}) while(%t), condition=%cond, body=%body, metadata={{op_name="jit(f)/jvp(M)/while"}}
  ROOT %out = f32[256]{{0}} get-tuple-element(%w), index=1
}}
""", 18432, "big", ["w"], [["block/mlp", "forward", "temporary", 16384], ["enc", "forward", "output", 1024]]),
    # one element of a tuple-shaped result dies before the other
    "tuple_shaped_result": (f"""{HEAD}

ENTRY %main (x: f32[256]) -> f32[256] {{
  %x = f32[256]{{0}} parameter(0)
  %f = (f32[256]{{0}}, f32[512]{{0}}) custom-call(%x), custom_call_target="two", {FWD}
  %f0 = f32[256]{{0}} get-tuple-element(%f), index=0
  %f1 = f32[512]{{0}} get-tuple-element(%f), index=1
  %h = f32[512]{{0}} custom-call(%f0), custom_call_target="k", {FWD}
  %k = f32[2048]{{0}} custom-call(%f1, %h), custom_call_target="k", {FWD}
  ROOT %m = f32[256]{{0}} custom-call(%k), custom_call_target="k", {FWD}
}}
""", 13312, "k", [], [["enc", "forward", "temporary", 12288]]),
    # an element-wise result takes the place of an operand nothing reads
    # afterwards (n over a, q over n), as XLA assigns them: four arrays are
    # alive at n without that; s is read again and stays
    "elementwise_result_over_its_operand": (f"""{HEAD}

ENTRY %main (x: f32[256]) -> f32[256] {{
  %x = f32[256]{{0}} parameter(0)
  %a = f32[256]{{0}} custom-call(%x), custom_call_target="k", {FWD}
  %s = f32[256]{{0}} custom-call(%x), custom_call_target="k", {FWD}
  %n = f32[256]{{0}} negate(%a), {FWD}
  %q = f32[256]{{0}} multiply(%s, %n), {FWD}
  ROOT %z = f32[256]{{0}} custom-call(%q, %s), custom_call_target="k", {FWD}
}}
""", 4096, "z", [], []),
    # the branch that holds most decides; its result is made inside it and
    # is the caller's once the branch has ended
    "conditional_branches": (f"""{HEAD}

%small (a.0: f32[256]) -> f32[256] {{
  %a.0 = f32[256]{{0}} parameter(0)
  ROOT %s.0 = f32[256]{{0}} custom-call(%a.0), custom_call_target="k", {FWD}
}}

%large (a.1: f32[256]) -> f32[256] {{
  %a.1 = f32[256]{{0}} parameter(0)
  %wide = f32[1024]{{0}} broadcast(%a.1), dimensions={{}}, metadata={{op_name="jit(f)/jvp(M)/block_0/moe/rung/cond/branch_1_fun/mul"}}
  ROOT %s.1 = f32[256]{{0}} slice(%wide), slice={{[0:256]}}, {FWD}
}}

ENTRY %main (x: f32[256], which: s32[]) -> f32[256] {{
  %x = f32[256]{{0}} parameter(0)
  %which = s32[] parameter(1)
  ROOT %picked = f32[256]{{0}} conditional(%which, %x, %x), branch_computations={{%small, %large}}, {FWD}
}}
""", 6148, "s.1", ["picked"], [["block/moe/rung", "forward", "temporary", 4096]]),
    # what the compiler prefetches into on-chip memory (S(1)) is no HBM, a
    # copy's source is no new buffer, and an array is padded to its tile
    "memory_spaces_and_tiles": (f"""{HEAD}

ENTRY %main (x: f32[12,64]) -> f32[12,64] {{
  %x = f32[12,64]{{1,0:T(8,128)}} parameter(0)
  %cs = (f32[12,64]{{1,0:T(8,128)S(1)}}, f32[12,64]{{1,0:T(8,128)}}, u32[]{{:S(2)}}) copy-start(%x)
  %cd = f32[12,64]{{1,0:T(8,128)S(1)}} copy-done(%cs)
  ROOT %y = f32[12,64]{{1,0:T(8,128)}} custom-call(%cd), custom_call_target="k", {FWD}
}}
""", 16384, "y", [], [["argument", "", "argument", 8192], ["enc", "forward", "output", 8192]]),
}


@pytest.mark.parametrize("case", sorted(LIVE_CASES))
def test_live_bytes_of_a_hand_written_schedule(case):
    text, peak, instruction, within, rows = LIVE_CASES[case]
    read = op_scopes._live_of_text(text)
    assert read["peak_bytes"] == peak
    assert (read["instruction"], read["within"]) == (instruction, within)
    # the owners' bytes add up to the total
    assert sum(size for *_, size in read["live"]) == peak
    for row in rows:
        assert row in read["live"], read["live"]
    assert read["largest"][0][-1] == max(size for *_, size in read["largest"])


def test_a_donated_argument_counts_once_and_an_undonated_leaf_is_named():
    read = op_scopes._live_of_text(LIVE_CASES["donated_argument"][0])
    assert read["undonated"] == [["state.opt_state[0].mu['w']", 4096]]
    assert (read["part"], read["phase"]) == ("loss", "forward")
    # without the alias the new parameter is a second array
    undonated = LIVE_CASES["donated_argument"][0].replace(
        "input_output_alias={ {0}: (0, {}, may-alias) }, ", ""
    )
    assert op_scopes._live_of_text(undonated)["peak_bytes"] == 8196 + 4096


def test_a_text_that_is_not_scheduled_is_not_read():
    text = LIVE_CASES["chain"][0].replace(", is_scheduled=true", "")
    assert op_scopes._live_of_text(text) is None


@pytest.mark.parametrize("shape,size", [
    ("f32[768]{0:T(1024)}", 4096),
    ("f32[12,64]{1,0:T(8,128)}", 16 * 128 * 4),
    ("f32[768,12,64]{0,2,1:T(8,128)}", 768 * 12 * 64 * 4),
    ("bf16[2,4096]{1,0:T(2,128)(2,1)}", 2 * 4096 * 2),
    ("bf16[8,1024,768]{2,1,0:T(8,128)(2,1)S(1)}", 0),
    ("s32[]{:T(128)}", 512),
    ("f32[8,64]{1,0}", 2048),
    ("pred[]", 1),
])
def test_an_arrays_bytes_are_its_tiles_in_main_memory(shape, size):
    assert op_scopes._shape_tree(shape) == (size, shape)


def test_a_window_leaves_the_steps_bytes_beside_its_trace_and_the_command_prints_them(
    tmp_path, monkeypatch, capsys
):
    from elasticdl_tpu.telemetry import memory
    from elasticdl_tpu.utils.profiling import StepProfiler

    trainer, batch = _trainer()
    monkeypatch.setattr(jax.profiler, "start_trace", lambda d, **_options: None)
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    assert op_scopes.main([str(tmp_path), "--memory"]) == 1  # nothing yet
    profiler = StepProfiler(str(tmp_path), start_step=1, num_steps=2)
    for _ in range(5):
        trainer.train_step(*batch)
        profiler.on_step()
    profiler.stop()
    with open(tmp_path / op_scopes.STEP_MEMORY_FILE) as f:
        written = json.load(f)
    read = memory.read_step_memory()
    assert written["state"] == read["state"]
    assert [p["peak_bytes"] for p in written["programs"]] == [
        p["peak_bytes"] for p in read["programs"]
    ]
    assert (tmp_path / op_scopes.OP_SCOPES_FILE).exists()  # beside the map
    assert op_scopes.main([str(tmp_path), "--memory", "--depth", "1"]) == 0
    out = capsys.readouterr().out
    assert "peak at " in out and "XLA: argument " in out
    assert "state on device " in out and "params " in out


def test_the_memory_table_adds_up_and_names_the_peak():
    read = op_scopes._live_of_text(LIVE_CASES["while_body_peak"][0])
    read.update(
        xla={"argument": 1024, "output": 1024, "alias": 0, "temp": 17408,
             "generated_code": 0, "peak": 18432},
        ratio=1.0, held_to="peak",
    )
    rows = [line.split() for line in op_scopes.memory_table(read, depth=1).splitlines()]
    assert rows[0] == ["owner", "phase", "role", "MB", "%"]
    assert rows[1][:3] == ["block", "forward", "temporary"]
    total = next(row for row in rows if row[0] == "total")
    assert total[-1] == "100.00"
    peak = " ".join(next(row for row in rows if row[0] == "peak"))
    assert "peak at big in w: block/mlp, forward" in peak
    assert "1.0000 of XLA's peak" in peak
