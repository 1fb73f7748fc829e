"""AOT-compile the GPT-2-small train step (the benchmark's ``gpt2_small``
shape: 12 x 768, 12 heads, 50,257-row head, bf16, 8,192 tokens per chip)
for a v5e 2x2 host from libtpu's topology description (no chip needed) and
print what the compiled program holds of the gathering loss's footprint:
``while`` loops, ``dynamic-update-slice`` and ``scatter`` ops, temporaries;
and the copies and transposes of an array as large as a flash kernel's
operand (``activation_copies``: 96 when the kernels took folded heads).
Driven by tests/test_chip_bringup.py; exits 77 where no TPU topology
description is available.

    JAX_PLATFORMS=cpu python3 tests/aot_gpt2_small_step.py [--seq 8192 --rows 1] [--chips 4]
"""

import argparse
import json
import math
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

parser = argparse.ArgumentParser()
parser.add_argument("--seq", type=int, default=1024)
parser.add_argument("--rows", type=int, default=8, help="sequences per chip")
parser.add_argument("--chips", type=int, default=1)
args = parser.parse_args()

try:
    topology = topologies.get_topology_desc(
        topology_name="v5e:2x2", platform="tpu"
    )
except Exception as ex:  # noqa: BLE001 — any failure here means "not available"
    print(f"{type(ex).__name__}: {ex}", file=sys.stderr)
    sys.exit(77)

mesh = MeshConfig.from_string(f"dp={args.chips}").create(
    devices=topology.devices[: args.chips]
)
model = lm.custom_model(
    vocab_size=50257,
    embed_dim=768,
    num_heads=12,
    num_layers=12,
    dtype="bfloat16",
)
tx = lm.optimizer()


def create_state():
    variables = model.init(
        jax.random.PRNGKey(0),
        {"tokens": np.zeros((1, args.seq), np.int32)},
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
rows = args.rows * args.chips
tokens = jax.ShapeDtypeStruct(
    (rows, args.seq),
    jnp.int32,
    sharding=sharding_lib.batch_sharding(mesh, 2, sp_dim=1),
)
weights = jax.ShapeDtypeStruct(
    (rows,), jnp.float32, sharding=sharding_lib.batch_sharding(mesh, 1)
)
step = build_train_step(lm.loss, state_shardings=shardings)
with mesh, attention_mesh_scope(mesh):
    compiled = step.lower(state, {"tokens": tokens}, tokens, weights).compile()
hlo = compiled.as_text()
memory = compiled.memory_analysis()
# q, k, v, out, dO, dq, dk or dv of one layer, in whatever shape and layout,
# copied or turned inside the attention module's scope
operand = args.rows * args.seq * 768
activation_copies = [
    match.group(1)
    for match in re.finditer(
        r"= bf16\[([\d,]+)\]\S* (?:copy|transpose)\([^\n]*/attn/", hlo
    )
    if math.prod(int(n) for n in match.group(1).split(",")) == operand
]
print(
    json.dumps(
        {
            "device_kind": topology.devices[0].device_kind,
            "while_loops": hlo.count(" while("),
            "dynamic_update_slices": hlo.count(" dynamic-update-slice("),
            "scatters": hlo.count(" scatter("),
            "kernel_calls": hlo.count('custom_call_target="tpu_custom_call"'),
            "activation_copies": len(activation_copies),
            "temp_bytes": memory.temp_size_in_bytes,
            "live_bytes_per_device": memory.argument_size_in_bytes
            + memory.output_size_in_bytes
            - memory.alias_size_in_bytes
            + memory.temp_size_in_bytes,
        }
    )
)
