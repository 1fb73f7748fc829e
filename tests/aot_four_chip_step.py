"""AOT-compile the transformer's dp=4 train step for a v5e 2x2 host from
libtpu's topology description (no chip needed) and print what the
compiled program hands the attention kernel.  Driven by
tests/test_chip_bringup.py; exits 77 where no TPU topology description
is available."""

import json
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import topologies

import elasticdl_tpu.parallel.distributed  # noqa: F401 — layout-invariant RNG
from elasticdl_tpu.models import long_seq_transformer as lm
from elasticdl_tpu.ops.attention import attention_mesh_scope
from elasticdl_tpu.parallel import sharding as sharding_lib
from elasticdl_tpu.parallel.mesh import MeshConfig
from elasticdl_tpu.trainer.state import TrainState
from elasticdl_tpu.trainer.step import build_train_step

BATCH, SEQ, HEADS = 8, 256, 2

try:
    topology = topologies.get_topology_desc(
        topology_name="v5e:2x2", platform="tpu"
    )
except Exception as ex:  # noqa: BLE001 — any failure here means "not available"
    print(f"{type(ex).__name__}: {ex}", file=sys.stderr)
    sys.exit(77)

mesh = MeshConfig.from_string("dp=4").create(devices=topology.devices)
model = lm.custom_model(
    vocab_size=512,
    embed_dim=64,
    num_heads=HEADS,
    num_layers=1,
    dtype="bfloat16",
)
tx = lm.optimizer()


def create_state():
    variables = model.init(
        jax.random.PRNGKey(0),
        {"tokens": np.zeros((1, SEQ), np.int32)},
        training=False,
    )
    return TrainState.create(model.apply, variables["params"], tx, {})


with mesh, attention_mesh_scope(mesh):
    shapes = jax.eval_shape(create_state)
shardings = sharding_lib.specs_to_shardings(
    sharding_lib.infer_param_specs(shapes, mesh, lm.sharding_rules(mesh)), mesh
)
state = jax.tree_util.tree_map(
    lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
    shapes,
    shardings,
)
rows = sharding_lib.batch_sharding(mesh, 2, sp_dim=1)
tokens = jax.ShapeDtypeStruct((BATCH, SEQ), jnp.int32, sharding=rows)
weights = jax.ShapeDtypeStruct(
    (BATCH,), jnp.float32, sharding=sharding_lib.batch_sharding(mesh, 1)
)
step = build_train_step(lm.loss, state_shardings=shardings)
with mesh, attention_mesh_scope(mesh):
    hlo = step.lower(state, {"tokens": tokens}, tokens, weights).compile().as_text()

calls = re.findall(
    r'= \(?bf16\[(\d+),\d+,\d+\][^\n]*custom_call_target="tpu_custom_call"', hlo
)
print(
    json.dumps(
        {
            "device_kind": topology.devices[0].device_kind,
            "kernel_calls": len(calls),
            "kernel_batch_x_heads": sorted({int(c) for c in calls}),
            "tokens_param": next(
                (
                    s
                    for s in (f"s32[{BATCH // 4},{SEQ}]", f"s32[{BATCH},{SEQ}]")
                    if s in hlo
                ),
                None,
            ),
        }
    )
)
