"""AOT-compile a cell's train step for a described ``v5e:2x2`` (no chip
needed; libtpu's compiler is installed) and print XLA's memory analysis of
the compiled program: arguments, outputs, temporaries, per device.

    JAX_PLATFORMS=cpu python3 perf/aot_memory.py --workload gpt2s_seq1024 [--batch-per-chip N]

A script run by hand before chip time is spent, never imported by a test
(it describes the topology at its top level).  A compile that passes is not
a chip run: it says what fits, never how fast."""

from __future__ import annotations

import argparse
import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.experimental import topologies  # noqa: E402

import elasticdl_tpu.parallel.distributed  # noqa: E402,F401 — layout-invariant RNG
from elasticdl_tpu.ops.attention import attention_mesh_scope  # noqa: E402
from elasticdl_tpu.parallel import sharding as sharding_lib  # noqa: E402
from elasticdl_tpu.parallel.mesh import MeshConfig  # noqa: E402
from elasticdl_tpu.trainer.local_executor import build_optimizer  # noqa: E402
from elasticdl_tpu.trainer.state import TrainState  # noqa: E402
from elasticdl_tpu.trainer.step import build_train_step  # noqa: E402
from elasticdl_tpu.utils.args import parse_master_args  # noqa: E402
from elasticdl_tpu.utils.model_utils import get_model_spec  # noqa: E402

from perf import manifest  # noqa: E402


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--batch-per-chip", type=int, default=None)
    parser.add_argument("--manifest", default=None)
    args = parser.parse_args()
    cell = manifest.Cell(manifest.load_manifest(args.manifest), args.workload)
    per_chip = args.batch_per_chip or int(cell.traffic["batch_per_chip"])
    rows = per_chip * cell.chips
    run = cell.config["run"]
    params = ";".join(f"{k}={v}" for k, v in run["model_params"].items())
    parsed = parse_master_args(
        ["--model_def", run["model_def"], "--model_params", params,
         "--minibatch_size", str(rows)] + list(run["train_args"])
    )
    spec = get_model_spec(
        parsed.model_zoo, parsed.model_def,
        model_params=parsed.model_params_dict,
    )
    model = spec.build_model()
    tx = build_optimizer(spec, parsed.learning_rate)

    topology = topologies.get_topology_desc(
        topology_name="v5e:2x2", platform="tpu"
    )
    mesh = MeshConfig.from_string(
        f"dp={cell.chips}"
    ).create(devices=topology.devices[: cell.chips])
    feature_shapes, label_shape = cell.record_kind().batch_shapes(
        cell.traffic["records"], rows
    )
    init_features = {
        k: np.zeros((1, *shape[1:]), np.dtype(dtype))
        for k, (shape, dtype) in feature_shapes.items()
    }

    def create_state():
        features = (
            spec.device_parse(init_features)
            if spec.device_parse is not None
            else init_features
        )
        variables = model.init(jax.random.PRNGKey(0), features, training=False)
        model_state = {k: v for k, v in variables.items() if k != "params"}
        return TrainState.create(
            model.apply, variables.get("params", {}), tx, model_state
        )

    with mesh, attention_mesh_scope(mesh):
        shapes = jax.eval_shape(create_state)
    rules = tuple(spec.sharding_rules(mesh)) if spec.sharding_rules else ()
    shardings = sharding_lib.specs_to_shardings(
        sharding_lib.infer_param_specs(shapes, mesh, rules), mesh
    )
    state = jax.tree_util.tree_map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        shapes, shardings,
    )

    def placed(shape, dtype):
        return jax.ShapeDtypeStruct(
            shape, dtype,
            sharding=sharding_lib.batch_sharding(
                mesh, len(shape), sp_dim=1 if len(shape) >= 2 else None
            ),
        )

    features = {k: placed(*sd) for k, sd in feature_shapes.items()}
    labels = placed(*label_shape)
    weights = placed((rows,), jnp.float32)
    compute_dtype = getattr(parsed, "compute_dtype", "float32")
    step = build_train_step(
        spec.loss,
        compute_dtype=None if compute_dtype == "float32" else compute_dtype,
        state_shardings=shardings,
        device_parse=spec.device_parse,
    )
    with mesh, attention_mesh_scope(mesh):
        compiled = step.lower(state, features, labels, weights).compile()
    analysis = compiled.memory_analysis()
    hlo = compiled.as_text()
    out = {
        "workload": cell.name,
        "device_kind": topology.devices[0].device_kind,
        "chips": cell.chips,
        "batch_per_chip": per_chip,
        "argument_bytes": analysis.argument_size_in_bytes,
        "output_bytes": analysis.output_size_in_bytes,
        "alias_bytes": analysis.alias_size_in_bytes,
        "temp_bytes": analysis.temp_size_in_bytes,
        "generated_code_bytes": analysis.generated_code_size_in_bytes,
        "kernel_calls": hlo.count('custom_call_target="tpu_custom_call"'),
        "all_reduces": hlo.count(" all-reduce("),
    }
    out["live_bytes_per_device"] = (
        out["argument_bytes"] + out["output_bytes"] - out["alias_bytes"]
        + out["temp_bytes"]
    )
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
