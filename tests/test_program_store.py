"""The program store (parallel/program_store.py): a second process loads
its init program and train step instead of tracing them, the identity
moves with everything a program is made from, and a bad entry falls back
to the build and leaves a good one behind."""

import json
import os
import re
import subprocess
import sys
import threading

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from elasticdl_tpu.parallel import program_store
from elasticdl_tpu.parallel.distributed import SPMDTrainer
from elasticdl_tpu.parallel.mesh import MeshConfig
from elasticdl_tpu.telemetry import compile_tracker
from elasticdl_tpu.utils.args import parse_master_args, parse_worker_args

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---- a fresh process hits ---------------------------------------------------

# One process: the store switched on where the compile cache is, a trainer
# built the way the runtimes build it, three steps on one seeded batch;
# then every program that went through the store is traced AGAIN and its
# module compared with the digest its entry recorded.
_CHILD = r"""
import json, sys
import jax, numpy as np
from elasticdl_tpu.ops.attention import attention_mesh_scope
from elasticdl_tpu.parallel import elastic, program_store
from elasticdl_tpu.parallel.distributed import SPMDTrainer
from elasticdl_tpu.parallel.mesh import MeshConfig
from elasticdl_tpu.telemetry import compile_tracker
from elasticdl_tpu.trainer.local_executor import build_optimizer
from elasticdl_tpu.utils.args import parse_master_args
from elasticdl_tpu.utils.model_utils import get_model_spec

cache, model_def, model_params, kind = sys.argv[1:5]
compile_tracker.install()
elastic.configure_compilation_cache(cache)
store = program_store.active()
through_the_store = []
inner = store.get_or_build
def recording(identity, lower, *rest):
    through_the_store.append((identity, lower))
    return inner(identity, lower, *rest)
store.get_or_build = recording

args = parse_master_args([
    "--model_def", model_def, "--model_params", model_params,
    "--minibatch_size", "8", "--training_data", "/nowhere",
    "--compute_dtype", "float32",
])
spec = get_model_spec(args.model_zoo, args.model_def, args.model_params_dict)
rng = np.random.RandomState(0)
if kind == "lm":
    features = {"tokens": rng.randint(0, 64, size=(8, 32)).astype(np.int32)}
    labels = rng.randint(0, 64, size=(8, 32)).astype(np.int32)
else:
    features = {"image": rng.rand(8, 28, 28).astype(np.float32)}
    labels = rng.randint(0, 10, size=(8,)).astype(np.int32)
mesh = MeshConfig.from_string("dp=2").create()
trainer = SPMDTrainer(
    mesh, spec.build_model(), spec.loss,
    build_optimizer(spec, args.learning_rate), features,
    device_parse=spec.device_parse,
    job_identity=program_store.job_identity(args, spec.module),
)
losses = []
for _ in range(3):
    metrics = trainer.train_step(
        trainer.place_batch(features), trainer.place_batch(labels),
        trainer.place_mask(8, 8),
    )
    losses.append(float(jax.device_get(metrics["loss"])))
counted = {
    "losses": losses,
    "programs": len(through_the_store),
    "hits": compile_tracker.program_store_hits(),
    "misses": compile_tracker.program_store_misses(),
    "rejects": compile_tracker.program_store_rejects(),
    "compiles": compile_tracker.compile_count(),
    "trace_s": compile_tracker.trace_secs_total(),
    "lower_s": compile_tracker.lower_secs_total(),
}
# traced the way the trainer traces: under the mesh and the attention scope
with mesh, attention_mesh_scope(mesh):
    counted["digests_agree"] = [
        store.read_header(store.path(identity))["module_sha256"]
        == program_store.module_digest(lower())
        for identity, lower in through_the_store
    ]
print("COUNTED " + json.dumps(counted))
"""


def _child(cache, model_def, model_params, kind):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    env["PYTHONPATH"] = ROOT
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    done = subprocess.run(
        [sys.executable, "-c", _CHILD, str(cache), model_def, model_params, kind],
        env=env, capture_output=True, text=True, timeout=300,
    )
    assert done.returncode == 0, done.stderr[-3000:]
    line = [l for l in done.stdout.splitlines() if l.startswith("COUNTED ")]
    return json.loads(line[-1][len("COUNTED "):])


@pytest.mark.parametrize(
    "model_def,model_params,kind",
    [
        (
            "long_seq_transformer.custom_model",
            "vocab_size=64;embed_dim=32;num_heads=2;num_layers=2",
            "lm",
        ),
        ("mnist_functional_api.mnist_functional_api.custom_model", "", "cnn"),
    ],
    ids=["tiny_lm", "mnist_cnn"],
)
def test_a_fresh_process_takes_its_programs_from_the_store(
    tmp_path, model_def, model_params, kind
):
    first = _child(tmp_path / "cache", model_def, model_params, kind)
    second = _child(tmp_path / "cache", model_def, model_params, kind)
    # the trainer built an init program and a train step
    assert first["programs"] == second["programs"] == 2
    assert (first["hits"], first["misses"], first["rejects"]) == (0, 2, 0)
    assert (second["hits"], second["misses"], second["rejects"]) == (2, 0, 0)
    # loading is not deriving: nothing lowered, next to nothing traced
    assert second["lower_s"] == 0.0
    assert second["trace_s"] < 0.1 * first["trace_s"], (first, second)
    # a hit is one program handed to the backend, as a miss is
    assert second["compiles"] == first["compiles"]
    # the loaded executable IS the built one
    assert second["losses"] == first["losses"]
    # and a fresh trace gives the module each entry recorded
    assert first["digests_agree"] == second["digests_agree"] == [True, True]


# ---- the identity moves with everything a program is made from -------------


class _Tiny(nn.Module):
    width: int = 4

    @nn.compact
    def __call__(self, features, training=False):
        return nn.Dense(self.width)(features["x"])


def _tiny_loss(labels, outputs):
    return jnp.mean((outputs - labels) ** 2)


class _Recording(program_store.ProgramStore):
    """Writes nothing and loads nothing: records each identity and builds."""

    def __init__(self):
        super().__init__("/nowhere")
        self.identities = {}

    def get_or_build(self, identity, lower, in_tree, out_tree, devices):
        self.identities[identity["program"]] = identity
        return lower().compile()


def _identities(
    monkeypatch,
    rows=8,
    dtype=np.float32,
    mesh_shape="dp=2",
    model_params="",
    learning_rate="0.1",
    rng_seed=0,
    donate_batch=False,
    stacked=2,
    shuffle_seed="1",
    worker=None,
):
    """The identities of the three programs a trainer builds."""
    argv = [
        "--model_def", "tests.tiny", "--model_params", model_params,
        "--minibatch_size", "8", "--training_data", "/nowhere",
        "--learning_rate", learning_rate, "--shuffle_seed", shuffle_seed,
    ]
    if worker is None:
        args = parse_master_args(argv)
    else:
        args = parse_worker_args(argv + list(worker))
    store = _Recording()
    monkeypatch.setattr(program_store, "_active", store)
    mesh = MeshConfig.from_string(mesh_shape).create()
    features = {"x": np.ones((rows, 3), dtype)}
    labels = np.zeros((rows, 4), np.float32)
    trainer = SPMDTrainer(
        mesh, _Tiny(**args.model_params_dict), _tiny_loss,
        optax.sgd(args.learning_rate), features,
        rng_seed=rng_seed, donate_batch=donate_batch,
        job_identity=program_store.job_identity(args, sys.modules[__name__]),
    )
    trainer.train_step(
        trainer.place_batch(features), trainer.place_batch(labels),
        trainer.place_mask(rows, rows),
    )
    trainer.train_steps_stacked(
        trainer.place_stacked({"x": np.ones((stacked, rows, 3), dtype)}),
        trainer.place_stacked(np.zeros((stacked, rows, 4), np.float32)),
        trainer.place_stacked(np.ones((stacked, rows), np.float32)),
    )
    assert sorted(store.identities) == [
        "init", "train_step", "train_steps_stacked",
    ]
    return {
        name: program_store.canonical(identity)
        for name, identity in store.identities.items()
    }


@pytest.mark.parametrize(
    "change,programs_that_move",
    [
        ({"rows": 16}, {"train_step", "train_steps_stacked"}),
        ({"dtype": np.float16}, {"init", "train_step", "train_steps_stacked"}),
        ({"mesh_shape": "dp=4"}, {"init", "train_step", "train_steps_stacked"}),
        ({"model_params": "width=4"}, {"init", "train_step", "train_steps_stacked"}),
        ({"learning_rate": "0.2"}, {"init", "train_step", "train_steps_stacked"}),
        ({"rng_seed": 1}, {"init", "train_step", "train_steps_stacked"}),
        ({"donate_batch": True}, {"init", "train_step", "train_steps_stacked"}),
        ({"stacked": 3}, {"train_steps_stacked"}),
        # proved out of every program: a new seed for the shuffle, and a
        # relaunched worker's new coordinates, name the same programs
        ({"shuffle_seed": "2"}, set()),
    ],
    ids=[
        "batch_shape", "dtype", "mesh_shape", "model_params", "learning_rate",
        "rng_seed", "donate_batch", "stacked_length", "shuffle_seed",
    ],
)
def test_the_identity_moves_with_what_a_program_is_made_from(
    monkeypatch, change, programs_that_move
):
    base = _identities(monkeypatch)
    changed = _identities(monkeypatch, **change)
    moved = {name for name in base if base[name] != changed[name]}
    assert moved == programs_that_move


def test_a_relaunched_workers_coordinates_name_the_same_programs(monkeypatch):
    one = _identities(
        monkeypatch,
        worker=["--worker_id", "0", "--master_addr", "a:1",
                "--coordinator_addr", "a:2", "--cluster_version", "0"],
    )
    relaunched = _identities(
        monkeypatch,
        worker=["--worker_id", "7", "--master_addr", "a:1",
                "--coordinator_addr", "a:9", "--cluster_version", "3"],
    )
    assert one == relaunched


@pytest.mark.parametrize("what", ["source_byte", "version_string"])
def test_the_identity_moves_with_the_code_and_the_installation(
    tmp_path, monkeypatch, what
):
    zoo = tmp_path / "zoo"
    (zoo / "sub").mkdir(parents=True)
    (zoo / "model.py").write_text("WIDTH = 4\n")
    (zoo / "sub" / "layer.py").write_text("DEPTH = 2\n")
    (zoo / "notes.txt").write_text("not code\n")
    job = {"arguments": {}, "model_zoo_directory": str(zoo)}
    mesh = MeshConfig.from_string("dp=2").create()
    before = program_store.canonical(program_store.process_identity(job, mesh))
    assert before == program_store.canonical(
        program_store.process_identity(job, mesh)
    )
    (zoo / "notes.txt").write_text("still not code\n")
    assert before == program_store.canonical(
        program_store.process_identity(job, mesh)
    )
    if what == "source_byte":
        (zoo / "sub" / "layer.py").write_text("DEPTH = 3\n")
    else:
        installed = program_store.versions()
        assert installed["jax"] == jax.__version__
        assert set(installed) == {
            "jax", "jaxlib", "libtpu", "flax", "optax", "numpy"
        }
        monkeypatch.setattr(
            program_store, "versions", lambda: {**installed, "optax": "0.0"}
        )
    assert before != program_store.canonical(
        program_store.process_identity(job, mesh)
    )


# ---- the one list of arguments that are outside every program --------------

# where a traced function could read an argument: the model zoo, the
# layers and kernels, the step builders and the state
_TRACED_SIDE = [
    "elasticdl_tpu/models", "elasticdl_tpu/layers", "elasticdl_tpu/ops",
    "elasticdl_tpu/embeddings", "elasticdl_tpu/feature_column",
    "elasticdl_tpu/trainer/step.py", "elasticdl_tpu/trainer/state.py",
    "elasticdl_tpu/trainer/losses.py",
]


def _traced_side_sources():
    for entry in _TRACED_SIDE:
        path = os.path.join(ROOT, entry)
        if os.path.isfile(path):
            yield path
        for root, _subdirs, files in os.walk(path):
            for name in files:
                if name.endswith(".py"):
                    yield os.path.join(root, name)


def test_arguments_outside_every_program_are_arguments_and_reach_no_trace():
    excluded = program_store.ARGUMENTS_OUTSIDE_EVERY_PROGRAM
    argv = ["--model_def", "m.f", "--training_data", "/x"]
    parsed = set(vars(parse_master_args(argv))) | set(
        vars(parse_worker_args(argv + ["--worker_id", "0", "--master_addr", "a:1"]))
    )
    assert excluded <= parsed, sorted(excluded - parsed)
    # what makes a program is never among them
    assert not excluded & {
        "model_def", "model_zoo", "model_params", "model_params_dict", "loss",
        "optimizer", "learning_rate", "compute_dtype", "remat", "donate_state",
        "device_prefetch", "mesh_shape", "dcn_mesh_shape", "minibatch_size",
        "steps_per_dispatch", "dataset_fn", "envs", "jax_platform",
    }
    # no file of the traced side reads one by name (as an attribute of the
    # parsed arguments or as a keyword)
    names = re.compile(
        r"\b(?:args|self\._args)\.(%s)\b" % "|".join(sorted(excluded))
    )
    for path in _traced_side_sources():
        with open(path) as f:
            found = names.findall(f.read())
        assert not found, (path, found)


def test_job_identity_keeps_every_other_argument():
    args = parse_master_args(
        ["--model_def", "m.f", "--training_data", "/x", "--learning_rate", "0.5",
         "--model_params", "a=1"]
    )
    job = program_store.job_identity(args, sys.modules[__name__])
    assert job["model_zoo_directory"] == os.path.dirname(os.path.abspath(__file__))
    assert job["arguments"]["learning_rate"] == 0.5
    assert job["arguments"]["model_params_dict"] == {"a": 1}
    assert set(job["arguments"]) == set(vars(args)) - set(
        program_store.ARGUMENTS_OUTSIDE_EVERY_PROGRAM
    )


# ---- a bad entry falls back and is replaced --------------------------------


@pytest.fixture
def store(tmp_path, monkeypatch):
    compile_tracker.install()
    store = program_store.ProgramStore(str(tmp_path / "program_store"))
    monkeypatch.setattr(program_store, "_active", store)
    return store


def _trained(rows=8):
    """A trainer built through the active store, after one step; the
    step's loss."""
    args = parse_master_args(
        ["--model_def", "tests.tiny", "--training_data", "/nowhere"]
    )
    features = {"x": np.arange(rows * 3, dtype=np.float32).reshape(rows, 3)}
    labels = np.ones((rows, 4), np.float32)
    trainer = SPMDTrainer(
        MeshConfig.from_string("dp=2").create(), _Tiny(), _tiny_loss,
        optax.sgd(0.1), features,
        job_identity=program_store.job_identity(args, sys.modules[__name__]),
    )
    metrics = trainer.train_step(
        trainer.place_batch(features), trainer.place_batch(labels),
        trainer.place_mask(rows, rows),
    )
    return float(jax.device_get(metrics["loss"]))


def _counted():
    return (
        compile_tracker.program_store_hits(),
        compile_tracker.program_store_misses(),
        compile_tracker.program_store_rejects(),
    )


def _entries(store):
    return sorted(
        os.path.join(store.directory, name)
        for name in os.listdir(store.directory)
        if name.endswith(".program")
    )


def _is_whole(store, path):
    with open(path, "rb") as f:
        header = store._read_header(f)
        return len(f.read()) == header["payload_bytes"]


def _truncate(store, path):
    size = os.path.getsize(path)
    with open(path, "r+b") as f:
        f.truncate(size // 2)


def _cut_into_the_header(store, path):
    with open(path, "r+b") as f:
        f.truncate(20)


def _foreign_header(store, path):
    # a whole, well-formed entry of ANOTHER program under this one's name
    other = next(p for p in _entries(store) if p != path)
    with open(other, "rb") as src, open(path, "wb") as dst:
        dst.write(src.read())


def _not_an_entry(store, path):
    with open(path, "wb") as f:
        f.write(b"something else entirely")


@pytest.mark.parametrize(
    "damage",
    [_truncate, _cut_into_the_header, _foreign_header, _not_an_entry],
    ids=["truncated", "short_header", "foreign_header", "no_magic"],
)
def test_a_bad_entry_falls_back_and_leaves_a_good_one_behind(store, damage):
    start = _counted()
    loss = _trained()
    assert _counted() == (start[0], start[1] + 2, start[2])
    entries = _entries(store)
    assert len(entries) == 2 and all(_is_whole(store, p) for p in entries)
    for path in entries:
        compiles = compile_tracker.compile_count()
        before = _counted()
        damage(store, path)
        assert _trained() == loss
        # the bad one was refused and built again; the other one hit
        assert _counted() == (before[0] + 1, before[1], before[2] + 1)
        # both ways hand one program each to the backend
        assert compile_tracker.compile_count() == compiles + 2
        assert all(_is_whole(store, p) for p in _entries(store))
    before = _counted()
    assert _trained() == loss
    assert _counted() == (before[0] + 2, before[1], before[2])


def test_a_concurrent_double_write_leaves_one_whole_entry(store):
    mesh = MeshConfig.from_string("dp=2").create()
    devices = list(mesh.devices.flat)

    def double(x):
        return x * 2

    x = jax.device_put(
        np.arange(8, dtype=np.float32),
        jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec("dp")),
    )
    identity = {"program": "double", "n": 8}
    trees = (
        jax.tree_util.tree_structure(((x,), {})),
        jax.tree_util.tree_structure(0),
    )
    barrier = threading.Barrier(6)
    results, failures = [], []

    def writer():
        try:
            barrier.wait(timeout=30)
            program = store.get_or_build(
                identity, lambda: jax.jit(double).lower(x), *trees, devices
            )
            results.append(np.asarray(program(x)))
        except Exception as ex:  # noqa: BLE001 — reported below
            failures.append(ex)

    threads = [threading.Thread(target=writer) for _ in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not any(t.is_alive() for t in threads)
    assert not failures, failures
    assert len(results) == 6
    for result in results:
        np.testing.assert_array_equal(result, np.arange(8) * 2.0)
    # one whole entry, no temporary file left, and the next caller hits
    assert [os.path.basename(p) for p in _entries(store)] == [
        os.path.basename(store.path(identity))
    ]
    assert sorted(os.listdir(store.directory)) == [
        os.path.basename(store.path(identity))
    ]
    assert _is_whole(store, store.path(identity))
    hits = compile_tracker.program_store_hits()
    store.get_or_build(identity, None, *trees, devices)
    assert compile_tracker.program_store_hits() == hits + 1


def test_a_program_whose_structure_was_not_foreseen_is_built_and_not_stored(store):
    mesh = MeshConfig.from_string("dp=2").create()
    x = jax.device_put(np.ones(4, np.float32), list(mesh.devices.flat)[0])
    program = store.get_or_build(
        {"program": "pair"},
        lambda: jax.jit(lambda v: (v, v)).lower(x),
        jax.tree_util.tree_structure(((x,), {})),
        jax.tree_util.tree_structure(0),  # the caller expected one leaf
        [list(mesh.devices.flat)[0]],
    )
    assert len(program(x)) == 2
    assert not os.path.exists(store.path({"program": "pair"}))


def test_on_the_cpu_a_program_the_compile_cache_served_is_not_written(
    store, monkeypatch
):
    """XLA:CPU writes a loaded executable without its kernels (the next
    process would load it and fail at its first dispatch), so a build the
    persistent compile cache served leaves no entry there."""
    served = iter(range(100))
    monkeypatch.setattr(
        compile_tracker, "compile_cache_hits", lambda: next(served)
    )
    before = _counted()
    _trained()
    assert _counted() == (before[0], before[1] + 2, before[2])
    assert not os.path.exists(store.directory) or not _entries(store)


# ---- where it is switched on ------------------------------------------------


def test_the_store_is_switched_on_where_the_compile_cache_is(tmp_path, monkeypatch):
    from elasticdl_tpu.parallel import elastic

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(program_store, "_active", None)
    kept = {
        name: getattr(jax.config, name)
        for name in (
            "jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes",
            "jax_enable_compilation_cache",
        )
    }
    try:
        assert program_store.active() is None
        elastic.configure_compilation_cache(str(tmp_path / "cache"))
        assert program_store.active().directory == str(
            tmp_path / "cache" / "program_store"
        )
        # no compile cache, no store
        jax.config.update("jax_enable_compilation_cache", False)
        elastic.configure_compilation_cache(str(tmp_path / "cache"))
        assert program_store.active() is None
    finally:
        for name, value in kept.items():
            jax.config.update(name, value)


def test_a_trainer_without_a_job_identity_stays_on_jit(store):
    features = {"x": np.ones((8, 3), np.float32)}
    before = _counted()
    trainer = SPMDTrainer(
        MeshConfig.from_string("dp=2").create(), _Tiny(), _tiny_loss,
        optax.sgd(0.1), features,
    )
    trainer.train_step(
        trainer.place_batch(features),
        trainer.place_batch(np.ones((8, 4), np.float32)),
    )
    assert _counted() == before
    assert not os.path.exists(store.directory)


def test_host_arrays_handed_to_a_step_stay_on_jit(store):
    """A leaf that is no device array has no sharding to name: such a call
    is jit's, as before."""
    args = parse_master_args(
        ["--model_def", "tests.tiny", "--training_data", "/nowhere"]
    )
    features = {"x": np.ones((8, 3), np.float32)}
    trainer = SPMDTrainer(
        MeshConfig.from_string("dp=1").create(), _Tiny(), _tiny_loss,
        optax.sgd(0.1), features,
        job_identity=program_store.job_identity(args, sys.modules[__name__]),
    )
    before = _counted()
    metrics = trainer.train_step(features, np.ones((8, 4), np.float32))
    assert np.isfinite(float(metrics["loss"]))
    assert _counted() == before


def test_describe_shapes_round_trips_nested_dicts_only():
    shapes = {
        "params": {"a": {"kernel": jax.ShapeDtypeStruct((3, 4), jnp.float32)}},
        "batch_stats": {"mean": jax.ShapeDtypeStruct((4,), jnp.bfloat16)},
    }
    described = program_store.describe_shapes(shapes)
    assert described is not None
    rebuilt = program_store.shapes_from(json.loads(json.dumps(described)))
    assert jax.tree_util.tree_structure(rebuilt) == jax.tree_util.tree_structure(
        shapes
    )
    assert rebuilt["batch_stats"]["mean"].dtype == jnp.bfloat16
    assert program_store.describe_shapes({"params": [shapes]}) is None
