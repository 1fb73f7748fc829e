"""The rotary kernel in whole train steps (PR 63): the three families whose
rotation is narrower than a lane tile or by adjacent pairs call the kernel,
and the plain form not once, at a shape that tiles.  (That the other
families' steps lower to the parent's text, and the cells' own models at
their published widths lowered for the described chip, are in
``tests/test_op_scopes.py``, which lowers every family once and is the one
process that may load the TPU's compiler.)"""

import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elasticdl_tpu.layers import attention as attention_layers
from elasticdl_tpu.models import long_seq_transformer as lm
from elasticdl_tpu.ops import rotary
from elasticdl_tpu.trainer.state import TrainState
from elasticdl_tpu.trainer.step import build_train_step

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _traced(config, tokens, **more):
    """The train step of a tiny configuration of ``tests/perf/configs``
    over one sequence of ``tokens``, traced."""
    with open(os.path.join(ROOT, "tests", "perf", "configs", config + ".json")) as f:
        params = dict(json.load(f)["run"]["model_params"], **more)
    model = lm.custom_model(**params)
    features = {"tokens": np.zeros((1, tokens), np.int32)}

    def state():
        variables = model.init(jax.random.PRNGKey(0), features, training=False)
        return TrainState.create(
            model.apply, variables["params"], lm.optimizer(),
            {k: v for k, v in variables.items() if k != "params"},
        )

    step = build_train_step(lm.loss, donate=False)
    # from shapes alone: nothing runs, the interpreted kernels neither
    return step.trace(
        jax.eval_shape(state), features, np.zeros((1, tokens), np.int32),
        np.ones((1,), np.float32),
    )


@pytest.mark.parametrize(
    "config,fields,arrays,plain",
    [
        # a latent layer and the MTP module's: q whole (128 + 64, its tail
        # by adjacent pairs) and the one shared rotary key
        (
            "tiny_joyai",
            {
                "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
                "num_layers": 2, "layer_pattern": "*E",
            },
            2 * 2, set(),
        ),
        # a sparse layer: q and k at 128 and the indexer's one key at 64,
        # by halves under three components' sections; its four queries of
        # 64 keep the plain form
        (
            "tiny_keye",
            {
                "head_dim": 128, "mrope_section": (16, 24, 24),
                "index_head_dim": 64, "num_layers": 1,
            },
            1 * 3, {(1, 512, 4, 64)},
        ),
        # one attention layer of 4 : 2 heads of 64: several heads narrower
        # than a lane tile, the plain form
        (
            "tiny_lfm2",
            {"head_dim": 64, "num_layers": 2, "layer_pattern": "*E"},
            0, {(1, 512, 4, 64), (1, 512, 2, 64)},
        ),
    ],
)
def test_a_narrow_or_paired_rotation_is_the_kernels(
    config, fields, arrays, plain, plain_rope_shapes
):
    """At one tile of rows and the cells' head widths the step's rotations
    are ``rope_fwd`` / ``rope_bwd`` (once an array and layer backward;
    forward at least once more than that, the recomputed pass) wherever
    whole lane tiles or an array's one narrow head rotate, and
    ``rope_plain`` is traced for several narrow heads alone."""
    text = str(_traced(config, 512, **fields).jaxpr)
    assert set(plain_rope_shapes) == plain
    forward = len(re.findall(f"name={rotary.ROPE_FWD}", text))
    backward = len(re.findall(f"name={rotary.ROPE_BWD}", text))
    assert backward == arrays
    assert forward >= 2 * arrays


def test_below_a_tile_of_rows_latent_attention_turns_the_slice_alone(
    plain_rope_shapes,
):
    mixer = attention_layers.LatentSelfAttention(
        num_heads=2, q_lora_rank=16, kv_lora_rank=16, qk_nope_head_dim=128,
        qk_rope_head_dim=64, v_head_dim=128, causal=True,
    )
    x = jnp.zeros((1, 256, 32))
    text = str(
        jax.make_jaxpr(lambda x: mixer.init(jax.random.PRNGKey(0), x))(x)
    )
    assert rotary.ROPE_FWD not in text
    # the rotating slice of q and the one key, never the whole head
    assert plain_rope_shapes == [(1, 256, 2, 64), (1, 256, 1, 64)]
