"""Model-zoo parity: every reference model module exists, resolves through
the spec contract, and trains (one-plus jitted steps, finite loss) on its
synthetic dataset.

This is the analogue of the reference's ``example_test.py:15-60`` which
runs every model-zoo model through the distributed harness; here the tier-1
check is per-model spec + train-step soundness (the distributed run is
covered by the worker/master tests).
"""

import jax
import numpy as np
import pytest

from elasticdl_tpu.data.dataset import Dataset
from elasticdl_tpu.data.recordio_gen import synthetic
from elasticdl_tpu.data.recordio_reader import RecordIODataReader
from elasticdl_tpu.trainer.metrics import (
    metric_tree_results,
    update_metric_tree,
)
from elasticdl_tpu.trainer.state import Modes, TrainState, init_model
from elasticdl_tpu.trainer.step import (
    build_eval_step,
    build_train_step,
    resolve_optimizer,
)
from elasticdl_tpu.utils.model_utils import get_model_spec

# (model_def, synthetic generator, records, batch)
ZOO = [
    ("mnist_functional_api.mnist_functional_api.custom_model", "mnist", 64, 16),
    ("mnist_subclass.mnist_subclass.custom_model", "mnist", 64, 16),
    (
        "cifar10_functional_api.cifar10_functional_api.custom_model",
        "cifar10",
        32,
        8,
    ),
    ("cifar10_subclass.cifar10_subclass.custom_model", "cifar10", 32, 8),
    ("deepfm_functional_api.deepfm_functional_api.custom_model", "frappe", 64, 16),
    ("deepfm_edl_embedding.deepfm_edl_embedding.custom_model", "frappe", 64, 16),
    (
        "census_dnn_model.census_functional_api.custom_model",
        "census",
        64,
        16,
    ),
    ("census_dnn_model.census_sequential.custom_model", "census", 64, 16),
    ("census_dnn_model.census_subclass.custom_model", "census", 64, 16),
    ("heart_functional_api.heart_functional_api.custom_model", "heart", 64, 16),
    ("odps_iris_dnn_model.odps_iris_dnn_model.custom_model", "iris", 64, 16),
    # TPU-build additions (no reference counterpart): long-context
    # transformer (flash attention on the single-device path) and the
    # pipeline-parallel transformer (sequential-scan path here)
    (
        "long_seq_transformer.long_seq_transformer.custom_model",
        "sequence",
        32,
        8,
    ),
    (
        "pipelined_transformer.pipelined_transformer.custom_model",
        "sequence",
        32,
        8,
    ),
]


def _first_batches(spec, data_dir, batch_size, n=2, mode=Modes.TRAINING):
    reader = RecordIODataReader(data_dir=data_dir)
    shards = reader.create_shards()
    name, (start, count) = next(iter(shards.items()))

    class _Task:
        shard_name = name

    _Task.start, _Task.end = start, start + count
    ds = Dataset.from_generator(lambda: reader.read_records(_Task))
    ds = spec.dataset_fn(ds, mode, reader.metadata)
    out = []
    for el in ds.batch(batch_size):
        out.append(el)
        if len(out) >= n:
            break
    return out


@pytest.mark.parametrize("model_def,gen,records,batch", ZOO)
def test_zoo_model_trains(model_def, gen, records, batch, tmp_path):
    data_dir = synthetic.GENERATORS[gen](
        str(tmp_path / gen), num_records=records, num_shards=1, seed=0
    )
    spec = get_model_spec("", model_def)
    model = spec.build_model()
    batches = _first_batches(spec, data_dir, batch)
    features, labels = batches[0]

    params, model_state = jax.jit(lambda: init_model(model, features))()
    tx = resolve_optimizer(spec.optimizer)
    state = TrainState.create(model.apply, params, tx, model_state)
    train_step = build_train_step(spec.loss, compute_dtype=None)

    losses = []
    for feats, labs in batches * 3:
        state, metrics = train_step(state, feats, labs)
        losses.append(float(metrics["loss"]))
    assert all(np.isfinite(l) for l in losses), losses
    assert int(state.step) == len(losses)

    # eval path + metrics contract
    eval_step = build_eval_step(spec.loss)
    outputs, eval_loss = eval_step(state, features, labels)
    assert np.isfinite(float(eval_loss))
    if spec.eval_metrics_fn is not None:
        tree = spec.eval_metrics_fn()
        update_metric_tree(tree, np.asarray(labels), jax.device_get(outputs))
        results = metric_tree_results(tree)
        assert results and all(np.isfinite(v) for v in results.values())


def test_resnet50_builds_and_steps(tmp_path):
    """ResNet-50 is too heavy for the per-model sweep on CPU; one tiny
    train step proves the full block stack + decayed-weights optimizer."""
    data_dir = synthetic.gen_cifar10(
        str(tmp_path / "c10"), num_records=4, num_shards=1, seed=0
    )
    spec = get_model_spec(
        "", "resnet50_subclass.resnet50_subclass.custom_model"
    )
    model = spec.build_model()
    (features, labels), = _first_batches(spec, data_dir, 2, n=1)
    params, model_state = jax.jit(lambda: init_model(model, features))()
    n_kernels = len(
        [1 for k in jax.tree_util.tree_leaves(params) if k.ndim == 4]
    )
    assert n_kernels == 1 + 16 * 3 + 4  # stem + 16 blocks x3 + 4 shortcuts
    # softmax-probability output contract (the loss consumes probabilities)
    probs = jax.jit(model.apply)({"params": params, **model_state}, features)
    np.testing.assert_allclose(
        np.asarray(probs).sum(-1), np.ones(2), rtol=1e-5
    )
    tx = resolve_optimizer(spec.optimizer)
    state = TrainState.create(model.apply, params, tx, model_state)
    train_step = build_train_step(spec.loss, compute_dtype=None)
    state, metrics = train_step(state, features, labels)
    assert np.isfinite(float(metrics["loss"]))


def test_imagenet_prep_and_model():
    import io

    import pytest as _pytest

    from elasticdl_tpu.data.reader import decode_example
    from elasticdl_tpu.models import imagenet_resnet50

    m = imagenet_resnet50.custom_model(num_classes=12)
    assert m.num_classes == 12

    # real image bytes -> (224, 224, 3) record
    from PIL import Image

    buf = io.BytesIO()
    Image.fromarray(
        np.zeros((8, 8, 3), np.uint8)
    ).save(buf, format="PNG")
    rec = imagenet_resnet50.prepare_data_for_a_single_file(
        io.BytesIO(buf.getvalue()), "n02/7_sample.JPEG"
    )
    ex = decode_example(rec)
    assert int(ex["label"]) == 7
    assert ex["image"].shape == (224, 224, 3)

    # garbage bytes must fail loudly at prep time, not corrupt the dataset
    with _pytest.raises(ValueError, match="not a decodable image"):
        imagenet_resnet50.prepare_data_for_a_single_file(
            io.BytesIO(b"\x01\x02\x03"), "n02/7_sample.JPEG"
        )


def test_deepfm_edl_sharding_rules():
    """The rules must actually APPLY on a mesh (odd 5383 vocab is padded to
    /128 so ep=4 divides), not just regex-match — and the spec loader must
    surface the hook."""
    from jax.sharding import PartitionSpec as P

    from elasticdl_tpu.models import deepfm_edl_embedding
    from elasticdl_tpu.parallel.mesh import MeshConfig
    from elasticdl_tpu.parallel.sharding import infer_param_specs

    mesh = MeshConfig.from_string("dp=2,ep=4").create(jax.devices("cpu")[:8])
    rules = deepfm_edl_embedding.sharding_rules(mesh)
    assert len(rules) == 2
    assert rules[0].matches("embedding/embedding")
    assert not rules[0].matches("my_embedding/embedding")

    spec = get_model_spec(
        "", "deepfm_edl_embedding.deepfm_edl_embedding.custom_model"
    )
    assert spec.sharding_rules is deepfm_edl_embedding.sharding_rules
    model = spec.build_model()
    ids = np.zeros((2, 10), np.int32)
    params = model.init(jax.random.PRNGKey(0), ids)["params"]
    assert params["embedding"]["embedding"].shape[0] % 128 == 0  # padded
    specs = infer_param_specs(params, mesh, rules)
    assert specs["embedding"]["embedding"] == P("ep", None)
    assert specs["id_bias"]["embedding"] == P("ep", None)
