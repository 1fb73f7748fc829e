"""The vectorized data plane: chunk scanner -> fused decode -> windowed
shuffle -> minibatches (data/fast_pipeline.py), and the cross-task
prefetcher (trainer/host_pipeline.py)."""

import threading

import numpy as np
import pytest

from elasticdl_tpu.data import recordio
from elasticdl_tpu.data.dataset import Dataset, batched_model_pipeline
from elasticdl_tpu.data.factory import create_data_reader
from elasticdl_tpu.data.fast_pipeline import (
    FallbackNeeded,
    _vectorized_task_batches,
    build_task_batches,
)
from elasticdl_tpu.data.reader import (
    decode_concat_batch,
    decode_example,
    encode_example,
)
from elasticdl_tpu.data.recordio_gen import synthetic
from elasticdl_tpu.master.task_dispatcher import TaskDispatcher
from elasticdl_tpu.trainer.host_pipeline import TaskPrefetcher
from elasticdl_tpu.trainer.state import Modes
from elasticdl_tpu.utils.model_utils import get_model_spec


def _frappe_setup(tmp_path, num_records=12000, records_per_task=6000):
    data_dir = synthetic.gen_frappe(
        str(tmp_path / "data"), num_records=num_records, num_shards=2, seed=0
    )
    reader = create_data_reader(data_dir, records_per_task=records_per_task)
    spec = get_model_spec(
        "", "deepfm_edl_embedding.deepfm_edl_embedding.custom_model"
    )
    disp = TaskDispatcher(
        reader.create_shards(),
        records_per_task=records_per_task,
        num_epochs=1,
    )
    return reader, spec, disp


# ---- chunk API ------------------------------------------------------------


def test_scanner_next_chunk_roundtrip(tmp_path):
    path = str(tmp_path / "c.edlio")
    recs = [b"a" * 10, b"bb" * 20, b"xyz"]
    with recordio.Writer(path) as w:
        for r in recs:
            w.write(r)
    with recordio.Scanner(path) as sc:
        buf, lengths = sc.next_chunk()
        assert [int(x) for x in lengths] == [len(r) for r in recs]
        joined = bytes(memoryview(buf))
        assert joined == b"".join(recs)
        assert sc.next_chunk() is None


def test_pyimpl_scanner_next_chunk_matches(tmp_path):
    path = str(tmp_path / "p.edlio")
    recs = [b"one", b"two2", b"three33"]
    with recordio._pyimpl.Writer(path) as w:
        for r in recs:
            w.write(r)
    with recordio._pyimpl.Scanner(path) as sc:
        buf, lengths = sc.next_chunk(max_records=2)
        assert bytes(memoryview(buf)) == b"onetwo2"
        assert [int(x) for x in lengths] == [3, 4]
        buf2, lengths2 = sc.next_chunk(max_records=2)
        assert bytes(memoryview(buf2)) == b"three33"
        assert sc.next_chunk() is None


@pytest.mark.skipif(
    not recordio.native_available(), reason="native codec not built"
)
def test_decode_concat_batch_matches_per_record():
    rng = np.random.RandomState(0)
    examples = [
        {
            "feature": rng.randint(0, 100, 10).astype(np.int64),
            "label": np.int64(i % 2),
        }
        for i in range(17)
    ]
    payloads = [encode_example(e) for e in examples]
    buf = np.frombuffer(b"".join(payloads), dtype=np.uint8)
    lengths = np.array([len(p) for p in payloads], dtype=np.uint64)
    template = decode_example(payloads[0])
    out = decode_concat_batch(buf, lengths, template)
    assert out is not None
    for i, e in enumerate(examples):
        np.testing.assert_array_equal(out["feature"][i], e["feature"])
        assert out["label"][i] == e["label"]


# ---- vectorized task pipeline --------------------------------------------


def test_fast_path_covers_all_records_with_classic_batch_count(tmp_path):
    reader, spec, disp = _frappe_setup(tmp_path)
    _tid, task = disp.get(0)

    fast = list(
        build_task_batches(
            reader,
            task,
            spec,
            Modes.TRAINING,
            reader.metadata,
            512,
            shuffle_records=True,
        )
    )
    classic = list(
        batched_model_pipeline(
            Dataset.from_generator(lambda: reader.read_records(task)),
            spec,
            Modes.TRAINING,
            reader.metadata,
            512,
            shuffle_records=True,
        )
    )
    # lockstep invariant: identical batch count and total records
    assert len(fast) == len(classic)
    assert sum(b[1].shape[0] for b in fast) == sum(
        b[1].shape[0] for b in classic
    )
    # same multiset of labels: every record exactly once
    fast_labels = np.sort(np.concatenate([b[1] for b in fast]))
    classic_labels = np.sort(np.concatenate([b[1] for b in classic]))
    np.testing.assert_array_equal(fast_labels, classic_labels)


def test_fast_path_deterministic_reiteration(tmp_path):
    reader, spec, disp = _frappe_setup(tmp_path)
    _tid, task = disp.get(0)
    ds = build_task_batches(
        reader,
        task,
        spec,
        Modes.TRAINING,
        reader.metadata,
        512,
        shuffle_records=True,
    )
    a = list(ds)
    b = list(ds)
    assert len(a) == len(b)
    for (fa, la), (fb, lb) in zip(a, b):
        np.testing.assert_array_equal(fa["feature"], fb["feature"])
        np.testing.assert_array_equal(la, lb)


def test_fast_path_eval_preserves_record_order(tmp_path):
    reader, spec, disp = _frappe_setup(tmp_path)
    _tid, task = disp.get(0)
    fast = list(
        build_task_batches(
            reader,
            task,
            spec,
            Modes.EVALUATION,
            reader.metadata,
            512,
            shuffle_records=False,
        )
    )
    classic = list(
        batched_model_pipeline(
            Dataset.from_generator(lambda: reader.read_records(task)),
            spec,
            Modes.EVALUATION,
            reader.metadata,
            512,
        )
    )
    for (fa, la), (fb, lb) in zip(fast, classic):
        np.testing.assert_array_equal(fa["feature"], fb["feature"])
        np.testing.assert_array_equal(la, lb)


def test_fast_path_windowed_flush_emits_exact_batches(tmp_path):
    """A window smaller than the task still yields ceil(n/batch) batches
    with every record exactly once (full batches from every flush, one
    final partial)."""
    reader, spec, disp = _frappe_setup(
        tmp_path, num_records=5000, records_per_task=2500
    )
    _tid, task = disp.get(0)
    batches = list(
        _vectorized_task_batches(
            reader,
            task,
            spec.batch_parse,
            Modes.TRAINING,
            batch_size=400,
            shuffle_seed=0,
            window_bytes=900 * 100,  # ~ a few batches per window
        )
    )
    sizes = [b[1].shape[0] for b in batches]
    assert sum(sizes) == 2500
    assert len(batches) == -(-2500 // 400)
    assert all(s == 400 for s in sizes[:-1])
    assert sizes[-1] == 2500 % 400


def test_fallback_on_schema_the_native_decoder_rejects(tmp_path):
    """Records the fused decoder cannot batch (a string-keyed object
    column is fine — but sparse/mixed schemas are not) fall back to the
    classic path before the first yield."""
    path = str(tmp_path / "mixed")
    import os

    os.makedirs(path)
    with recordio.Writer(os.path.join(path, "s-000.edlio")) as w:
        # schema varies per record: vectorized decode must refuse
        for i in range(100):
            shape = (10,) if i % 2 == 0 else (11,)
            w.write(
                encode_example(
                    {
                        "feature": np.zeros(shape, dtype=np.int64),
                        "label": np.int64(0),
                    }
                )
            )
    reader = create_data_reader(path, records_per_task=100)
    disp = TaskDispatcher(
        reader.create_shards(), records_per_task=100, num_epochs=1
    )
    _tid, task = disp.get(0)

    calls = []

    def batch_parse(example_batch, mode):
        calls.append(len(example_batch))
        return example_batch, np.zeros(1)

    with pytest.raises(FallbackNeeded):
        list(
            _vectorized_task_batches(
                reader, task, batch_parse, Modes.TRAINING, 32, None
            )
        )


# ---- cross-task prefetcher ------------------------------------------------


def _fake_task_stream(n_tasks, batches_per_task):
    tasks = [(i, f"task{i}") for i in range(n_tasks)] + [(None, None)]
    it = iter(tasks)

    def next_task():
        return next(it)

    def make_batches(task):
        return [f"{task}-b{j}" for j in range(batches_per_task)]

    return next_task, make_batches


def test_prefetcher_preserves_task_and_batch_order():
    next_task, make_batches = _fake_task_stream(5, 3)
    out = []
    pf = TaskPrefetcher(next_task, make_batches, max_buffered_batches=4)
    for tid, task, batches in pf:
        out.append((tid, task, list(batches)))
    pf.close()
    assert [t[0] for t in out] == [0, 1, 2, 3, 4]
    assert out[2] == (2, "task2", ["task2-b0", "task2-b1", "task2-b2"])


def test_prefetcher_decodes_ahead_while_consumer_holds_a_task():
    """While the consumer sits inside task 0, the producer fills the
    buffer with upcoming batches (the whole point: decode overlaps the
    device dispatch)."""
    produced = []
    gate = threading.Event()

    def next_task():
        if len(produced) >= 3:
            return None, None
        tid = len(produced)
        produced.append(tid)
        return tid, f"t{tid}"

    def make_batches(task):
        for j in range(2):
            yield f"{task}-b{j}"

    pf = TaskPrefetcher(next_task, make_batches, max_buffered_batches=16)
    it = iter(pf)
    _tid, _task, batches = next(it)
    first = next(iter(batches))
    assert first == "t0-b0"
    # give the producer a moment: it should have pulled MORE tasks than
    # the one the consumer is holding
    for _ in range(100):
        if len(produced) >= 3:
            break
        gate.wait(0.05)
    assert len(produced) >= 2
    # drain cleanly
    list(batches)
    for _tid, _task, bs in it:
        list(bs)
    pf.close()


def test_prefetcher_propagates_producer_error():
    def next_task():
        return 0, "t0"

    def make_batches(task):
        yield "b0"
        raise RuntimeError("decode exploded")

    pf = TaskPrefetcher(next_task, make_batches)
    with pytest.raises(RuntimeError, match="decode exploded"):
        for _tid, _task, batches in pf:
            list(batches)
    pf.close()


def test_prefetcher_close_releases_blocked_producer():
    def next_task():
        return 0, "t0"

    def make_batches(task):
        for j in range(1000):
            yield j

    pf = TaskPrefetcher(next_task, make_batches, max_buffered_batches=2)
    it = iter(pf)
    next(it)  # start the producer; it will fill the queue and block
    pf.close()
    assert not pf._thread.is_alive()


def test_prefetcher_byte_budget_blocks_producer():
    """Decode-ahead is bounded by bytes, not just batch count: two 1MB
    batches exhaust a 2MB budget even with a generous count bound."""
    import time as _time

    produced = []

    def next_task():
        if produced:
            return None, None
        produced.append(0)
        return 0, "t0"

    def make_batches(task):
        for j in range(50):
            yield np.zeros((256, 1024), np.float32)  # ~1MB each

    pf = TaskPrefetcher(
        next_task,
        make_batches,
        max_buffered_batches=1000,
        max_buffered_bytes=2 << 20,
    )
    it = iter(pf)
    _tid, _task, batches = next(it)
    _time.sleep(0.5)
    # ~2 batches fit the byte budget (+1 may be mid-put)
    assert pf._buffered_batches <= 3
    n = sum(1 for _ in batches)
    assert n == 50  # consuming releases credit; all batches arrive
    pf.close()


def test_deepfm_wire_dtype_narrows_and_widens():
    """deepfm ids ship int16 while the model's vocab fits, int32 when a
    user overrides input_dim past int16 range; the model output is
    identical either way (ids are cast to int32 on device)."""
    from elasticdl_tpu.models import deepfm_functional_api as dfm

    rng = np.random.RandomState(0)
    batch = {
        "feature": rng.randint(0, 5383, (8, 10)).astype(np.int64),
        "label": rng.randint(0, 2, 8).astype(np.int64),
    }
    dfm.custom_model()
    feats, labels = dfm.batch_parse(batch, Modes.TRAINING)
    assert feats["feature"].dtype == np.int16
    assert labels.dtype == np.int32

    dfm.custom_model(input_dim=40000)
    feats, _ = dfm.batch_parse(batch, Modes.TRAINING)
    assert feats["feature"].dtype == np.int32

    # the wire dtype is a pure function of the BUILT model, never of
    # batch history (a history-dependent dtype would flip int16<->int32
    # — one step recompile per flip — and diverge between lockstep
    # processes with different histories).  An id past int16 range
    # under an int16-resolved wire is >= 2^15 > input_dim, outside the
    # embedding vocab: corrupt data, raise rather than widen
    dfm.custom_model()  # resolves int16
    with pytest.raises(ValueError, match="exceeds int16 range"):
        dfm.batch_parse(
            dict(batch, feature=np.full((8, 10), 40000, np.int64)),
            Modes.TRAINING,
        )
    feats, _ = dfm.batch_parse(batch, Modes.TRAINING)
    assert feats["feature"].dtype == np.int16  # unchanged by the reject

    # negative ids are corrupt data (astype would wrap silently): raise
    with pytest.raises(ValueError, match="negative feature id"):
        dfm.batch_parse(
            dict(batch, feature=np.full((2, 10), -1, np.int64)),
            Modes.TRAINING,
        )

    # restore the default for other tests (module-level state)
    dfm.custom_model()
    # int16 ids drive the model fine (device-side widening)
    import jax

    model = dfm.custom_model()
    feats16, _ = dfm.batch_parse(batch, Modes.TRAINING)
    params = model.init(jax.random.PRNGKey(0), feats16, training=False)
    out = model.apply(params, feats16, training=False)
    assert np.asarray(out["logits"]).shape == (8,)


def test_device_parse_step_equivalence():
    """A train step fed uint8 wire batches through device_parse computes
    the same update as one fed host-normalized f32 batches (the classic
    path) — the wire format changes transfer bytes, not math."""
    import jax
    import optax

    from elasticdl_tpu.models import mnist_functional_api as mnist
    from elasticdl_tpu.trainer.state import TrainState
    from elasticdl_tpu.trainer.step import build_train_step

    rng = np.random.RandomState(0)
    raw = {"image": rng.randint(0, 255, (8, 28, 28)).astype(np.uint8)}
    labels = rng.randint(0, 10, 8).astype(np.int32)
    f32 = {"image": raw["image"].astype(np.float32) / 255.0}

    model = mnist.custom_model()

    def make_state():
        variables = model.init(
            jax.random.PRNGKey(0), f32, training=False
        )
        return TrainState.create(
            model.apply,
            variables.get("params", {}),
            optax.sgd(0.1),
            {k: v for k, v in variables.items() if k != "params"},
        )

    step_wire = build_train_step(
        mnist.loss, device_parse=mnist.device_parse
    )
    step_classic = build_train_step(mnist.loss)
    s1, m1 = step_wire(make_state(), raw, labels)
    s2, m2 = step_classic(make_state(), f32, labels)
    # same math, different programs: XLA fuses the in-step /255 with the
    # first conv, so values round differently in the last ulps — tight
    # tolerance, not bitwise (applies to the loss too)
    np.testing.assert_allclose(
        float(m1["loss"]), float(m2["loss"]), rtol=1e-5
    )
    for a, b in zip(
        jax.tree_util.tree_leaves(s1.params),
        jax.tree_util.tree_leaves(s2.params),
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6
        )


def test_prestacked_groups_match_plain_batches(tmp_path):
    """stack_k emits PreStacked groups whose (k, B, ...) contents equal
    the plain path's batches exactly (same permutation, same rows), with
    leftover batches plain."""
    from elasticdl_tpu.trainer.stacking import PreStacked

    reader, spec, disp = _frappe_setup(
        tmp_path, num_records=12000, records_per_task=6000
    )
    _tid, task = disp.get(0)
    plain = list(
        build_task_batches(
            reader,
            task,
            spec,
            Modes.TRAINING,
            reader.metadata,
            512,
            shuffle_records=True,
        )
    )
    stacked = list(
        build_task_batches(
            reader,
            task,
            spec,
            Modes.TRAINING,
            reader.metadata,
            512,
            shuffle_records=True,
            stack_k=4,
        )
    )
    # 6000 records / 512 = 11 full batches + tail -> 2 groups of 4,
    # then 3 plain full batches, then the partial tail
    assert isinstance(stacked[0], PreStacked)
    assert isinstance(stacked[1], PreStacked)
    assert all(not isinstance(x, PreStacked) for x in stacked[2:])
    assert len(stacked) == 2 + 3 + 1

    rebuilt = []
    for item in stacked:
        if isinstance(item, PreStacked):
            for i in range(item.num_steps):
                rebuilt.append(
                    (
                        {
                            k: v[i]
                            for k, v in item.features.items()
                        },
                        item.labels[i],
                    )
                )
        else:
            rebuilt.append(item)
    assert len(rebuilt) == len(plain)
    for (fa, la), (fb, lb) in zip(rebuilt, plain):
        np.testing.assert_array_equal(fa["feature"], fb["feature"])
        np.testing.assert_array_equal(la, lb)


def test_run_stacked_steps_dispatches_prestacked():
    """PreStacked items dispatch directly (one stacked call, no
    grouping), counting records and firing hooks per group."""
    from elasticdl_tpu.trainer import stacking

    class FakeTrainer:
        def __init__(self):
            self.stacked = []
            self.single = 0

        def place_stacked(self, tree):
            return tree

        def place_batch(self, tree):
            return tree

        def pad_to(self, tree, rows):
            return tree

        def row_mask(self, n_real, rows):
            return np.ones(rows, np.float32)

        def train_step(self, f, l, mask):
            self.single += 1

        def train_steps_stacked(self, f, l, weights):
            self.stacked.append(weights.shape)

    feats = {"x": np.zeros((4, 8, 3), np.float32)}
    labels = np.zeros((4, 8), np.int32)
    group = stacking.PreStacked(
        feats, labels, 32, {"x": feats["x"][0]}
    )
    tail = ({"x": np.zeros((5, 3), np.float32)}, np.zeros(5, np.int32))
    pre, post = [], []
    trainer = FakeTrainer()
    n = stacking.run_stacked_steps(
        lambda: trainer,
        iter([group, tail]),
        4,
        pre_batch=lambda f: pre.append(1),
        post_group=lambda: post.append(1),
        canonical_rows=8,
    )
    assert n == 32 + 5
    assert trainer.stacked == [(4, 8)]
    assert trainer.single == 1  # the tail dispatches as a single step
    assert len(pre) == 4 + 1  # one hook call per step
    assert len(post) == 2  # one per dispatch group


def test_prestacked_caps_group_to_window(tmp_path):
    """stack_k larger than the task's full-batch count still groups:
    one PreStacked of however many full batches exist (auto k=36 over a
    32-batch task must not silently fall back to per-batch grouping)."""
    from elasticdl_tpu.trainer.stacking import PreStacked

    reader, spec, disp = _frappe_setup(
        tmp_path, num_records=4096, records_per_task=2048
    )
    _tid, task = disp.get(0)
    items = list(
        build_task_batches(
            reader,
            task,
            spec,
            Modes.TRAINING,
            reader.metadata,
            512,
            shuffle_records=True,
            stack_k=36,
        )
    )
    # 2048/512 = 4 full batches -> one PreStacked(4), no tail
    assert len(items) == 1
    assert isinstance(items[0], PreStacked)
    assert items[0].num_steps == 4
    assert items[0].num_records == 2048


def test_prestacked_disabled_for_prediction_parse(tmp_path):
    """An explicit int stack_k with a prediction-shaped batch_parse
    (no labels) downgrades to plain batches instead of crashing."""
    from elasticdl_tpu.trainer.stacking import PreStacked

    reader, spec, disp = _frappe_setup(
        tmp_path, num_records=4096, records_per_task=2048
    )
    _tid, task = disp.get(0)
    items = list(
        build_task_batches(
            reader,
            task,
            spec,
            Modes.PREDICTION,
            reader.metadata,
            512,
            shuffle_records=False,
            stack_k=4,
        )
    )
    assert all(not isinstance(x, PreStacked) for x in items)
    assert sum(x["feature"].shape[0] for x in items) == 2048


def test_prefetcher_charges_prestacked_groups_their_step_count():
    """A PreStacked group counts its k steps against the decode-ahead
    batch budget, so 'two dispatch groups ahead' means two GROUPS, not
    2*k of them."""
    import time as _time

    from elasticdl_tpu.trainer.stacking import PreStacked

    def next_task():
        return 0, "t0"

    def make_batches(task):
        while True:
            feats = {"x": np.zeros((8, 4, 2), np.float32)}
            yield PreStacked(
                feats, np.zeros((8, 4), np.int32), 32, feats["x"][0]
            )

    pf = TaskPrefetcher(
        next_task,
        make_batches,
        max_buffered_batches=16,  # two 8-step groups
        max_buffered_bytes=1 << 30,
    )
    it = iter(pf)
    next(it)
    _time.sleep(0.5)
    # the QUEUE must hold only ~2 groups (a regression charging groups
    # 1 instead of num_steps would admit ~16 of them before blocking;
    # the budget counter itself can never exceed the cap by much, so
    # asserting on it alone would be vacuous)
    assert pf._q.qsize() <= 4, pf._q.qsize()
    assert pf._buffered_batches >= 16  # the admitted groups charged 8 each
    pf.close()


def test_census_batch_parse_matches_dataset_fn(tmp_path):
    """The feature-column model's vectorized parse equals the per-record
    dataset_fn path batch for batch (same shuffle stream policy)."""
    data_dir = synthetic.gen_census(
        str(tmp_path / "c"), num_records=1200, num_shards=1, seed=0
    )
    reader = create_data_reader(data_dir, records_per_task=1200)
    spec = get_model_spec(
        "", "census_dnn_model.census_functional_api.custom_model"
    )
    assert spec.batch_parse is not None
    disp = TaskDispatcher(
        reader.create_shards(), records_per_task=1200, num_epochs=1
    )
    _tid, task = disp.get(0)
    fast = list(
        build_task_batches(
            reader,
            task,
            spec,
            Modes.EVALUATION,  # no shuffle: order-comparable
            reader.metadata,
            256,
        )
    )
    # force the TRUE per-record dataset_fn path for the comparison side
    # (otherwise batched_model_pipeline would prefer batch_parse and the
    # test would compare batch_parse with itself)
    spec.batch_parse = None
    classic = list(
        batched_model_pipeline(
            Dataset.from_generator(lambda: reader.read_records(task)),
            spec,
            Modes.EVALUATION,
            reader.metadata,
            256,
        )
    )
    assert len(fast) == len(classic) == 5
    for (fa, la), (fb, lb) in zip(fast, classic):
        assert set(fa) == set(fb)
        for k in fa:
            np.testing.assert_array_equal(fa[k], fb[k])
        np.testing.assert_array_equal(la, lb)
