"""The always-on host timeline (ISSUE 24): the ring, that it changes
nothing it records, that every runtime callee writes its span, and the
profile window that shares a clock with the device trace."""

from __future__ import annotations

import json
import os
import sys
import threading
import time

import jax
import numpy as np
import pytest

from elasticdl_tpu.telemetry import anatomy
from elasticdl_tpu.telemetry.anatomy import (
    PHASE_ASSEMBLE,
    PHASE_ENQUEUE,
    PHASE_H2D_TRANSFER,
    PHASE_HOST_FETCH,
    PHASE_PRODUCE_BATCH,
    PHASE_PRODUCE_BLOCKED,
    PHASE_PRODUCE_NEXT_TASK,
    PHASE_STEP_BOOKKEEPING,
    PHASE_SYNC,
    TIMELINE,
    Span,
    Timeline,
)

# ---- the ring ----------------------------------------------------------------


def test_wrap_around_keeps_the_newest_spans_in_order():
    ring = Timeline(capacity=8)
    for i in range(21):
        ring.record(f"s{i}", time.perf_counter_ns())
    names = [s.name for s in ring.snapshot()]
    assert names == [f"s{i}" for i in range(13, 21)]
    starts = [s.start_ns for s in ring.snapshot()]
    assert starts == sorted(starts)
    assert ring.head() == 21


def test_capacity_is_a_power_of_two():
    with pytest.raises(ValueError):
        Timeline(capacity=12)


def test_since_returns_new_spans_and_survives_a_lapped_mark():
    ring = Timeline(capacity=8)
    ring.record("a", 1)
    mark = ring.head()
    ring.record("b", 2)
    ring.record("c", 3)
    spans, mark = ring.since(mark)
    assert [s.name for s in spans] == ["b", "c"] and mark == 3
    assert ring.since(mark) == ([], 3)
    for i in range(20):  # laps the mark: what is left comes back, in order
        ring.record(f"n{i}", 10 + i)
    spans, mark = ring.since(mark)
    assert [s.name for s in spans] == [f"n{i}" for i in range(12, 20)]
    assert mark == 23


def test_appends_from_two_threads_lose_nothing_and_tear_nothing():
    ring = Timeline(capacity=1 << 15)
    per_thread, workers = 5000, 4
    start = threading.Barrier(workers)

    def write(tag):
        start.wait(timeout=30)
        for i in range(per_thread):
            # name, start and count say the same thing: a torn span differs
            ring.record(f"{tag}:{i}", i, cpu_ns=i, count=i)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [
            threading.Thread(target=write, args=(f"w{t}",), name=f"w{t}")
            for t in range(workers)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
            assert not t.is_alive()
    finally:
        sys.setswitchinterval(old)
    spans = ring.snapshot()
    assert len(spans) == per_thread * workers
    seen = {t: [] for t in range(workers)}
    for s in spans:
        tag, i = s.name.split(":")
        assert s.thread == tag  # the thread's own name, never another's
        assert s.start_ns == s.cpu_ns == s.count == int(i)
        seen[int(tag[1:])].append(int(i))
    # nothing lost, and each thread's spans in the order it wrote them
    assert all(v == list(range(per_thread)) for v in seen.values())


def test_one_span_costs_microseconds():
    """The stated cost: a span under 5 us (median of 20,000 here; the
    issue's "a few us per dispatch" at 8 spans a dispatch).  Host time on a
    shared CI core, so the bound is loose by design: it catches a lock, an
    allocation storm or an I/O call on the append path, not a 20% drift."""
    ring = Timeline()
    costs = []
    for _ in range(20000):
        t0 = time.perf_counter_ns()
        ring.record(PHASE_ASSEMBLE, t0)
        costs.append(time.perf_counter_ns() - t0)
    costs.sort()
    assert costs[len(costs) // 2] < 5_000, costs[len(costs) // 2]


def test_enqueue_moves_the_threads_dispatch_ordinal_on():
    ring = Timeline()
    ring.record(PHASE_ASSEMBLE, 1)
    ring.record_enqueue(2, "out0")
    ring.record(PHASE_ASSEMBLE, 3)
    ring.record_enqueue(4, "out1")
    other = threading.Thread(
        target=lambda: ring.record_enqueue(5, "x"), name="other"
    )
    other.start()
    other.join(timeout=10)
    by = [(s.name, s.thread, s.ordinal) for s in ring.snapshot()]
    me = threading.current_thread().name
    assert by == [
        (PHASE_ASSEMBLE, me, 0),
        (PHASE_ENQUEUE, me, 0),
        (PHASE_ASSEMBLE, me, 1),
        (PHASE_ENQUEUE, me, 1),
        (PHASE_ENQUEUE, "other", 0),  # ordinals are per thread
    ]


def test_timed_fetches_number_batches_and_record_the_end_of_stream():
    ring = Timeline()
    assert list(ring.timed_fetches(iter("abc"))) == ["a", "b", "c"]
    fetched = [(s.name, s.ordinal, s.count) for s in ring.snapshot()]
    assert fetched == [
        (PHASE_HOST_FETCH, 0, 1),
        (PHASE_HOST_FETCH, 1, 1),
        (PHASE_HOST_FETCH, 2, 1),
        (PHASE_HOST_FETCH, 3, 0),  # the wait that ended the stream
    ]


def test_dump_writes_the_window_with_named_columns(tmp_path):
    ring = Timeline()
    ring.record("early", 10)
    t0 = time.perf_counter_ns()
    ring.record("inside", t0, count=7)
    path = tmp_path / "spans.json"
    assert ring.dump(str(path), start_ns=t0) == 1
    dumped = json.loads(path.read_text())
    assert dumped["fields"] == list(Span._fields)
    assert dumped["clock"] == "time.perf_counter_ns"
    (span,) = dumped["spans"]
    assert span[0] == "inside" and span[-1] == 7


# ---- it changes nothing it records -------------------------------------------


class _RecordingTrainer:
    """What a dispatch loop asks of a trainer, with every dispatch's
    shapes written down."""

    step = 0

    def __init__(self):
        self.dispatches = []

    def pad_to(self, tree, rows):
        def _pad(x):
            x = np.asarray(x)
            if x.shape[0] == rows:
                return x
            return np.concatenate(
                [x, np.repeat(x[-1:], rows - x.shape[0], axis=0)]
            )

        return jax.tree_util.tree_map(_pad, tree)

    def row_mask(self, n, rows):
        mask = np.zeros(rows, np.float32)
        mask[:n] = 1.0
        return mask

    def place_batch(self, tree):
        return tree

    place_stacked = place_batch

    def train_step(self, features, labels, weights=None):
        self.dispatches.append(
            ("step", features.shape, int(np.sum(weights)))
        )
        return np.float32(0.0)

    def train_steps_stacked(self, features, labels, weights=None):
        self.dispatches.append(
            ("scan", features.shape, int(np.sum(weights)))
        )
        return np.float32(0.0)


def _stream(sizes):
    return [
        (np.full((n, 2), i, np.float32), np.arange(n, dtype=np.int32))
        for i, n in enumerate(sizes)
    ]


@pytest.fixture
def no_block(monkeypatch):
    """The default path never blocks on a dispatch: poison the call."""

    def boom(*_a, **_k):
        raise AssertionError("block_until_ready on the default train path")

    monkeypatch.setattr(jax, "block_until_ready", boom)


@pytest.mark.parametrize(
    "k,sizes,expected",
    [
        (1, [4, 4, 3], [("step", (4, 2), 4), ("step", (4, 2), 4), ("step", (4, 2), 3)]),
        (2, [4, 4, 3], [("scan", (2, 4, 2), 8), ("step", (4, 2), 3)]),
        (3, [4, 4, 3], [("scan", (3, 4, 2), 11)]),
    ],
)
def test_dispatch_order_and_shapes_are_the_uninstrumented_loops(
    no_block, k, sizes, expected
):
    """The grouping policy's dispatches — order, kind, shapes, real rows —
    are what they were before the spans, with nothing blocking."""
    from elasticdl_tpu.trainer.stacking import run_stacked_steps

    trainer = _RecordingTrainer()
    hooks = []
    processed = run_stacked_steps(
        lambda: trainer,
        iter(_stream(sizes)),
        k,
        pre_batch=lambda f: hooks.append("pre"),
        post_group=lambda: hooks.append("post"),
        canonical_rows=4,
    )
    assert processed == sum(sizes)
    assert trainer.dispatches == expected
    assert hooks.count("pre") == len(sizes)
    assert hooks.count("post") == (len(sizes) + k - 1) // k


def _tiny_trainer():
    import flax.linen as nn
    import optax

    from elasticdl_tpu.parallel.distributed import SPMDTrainer
    from elasticdl_tpu.parallel.mesh import MeshConfig

    class Tiny(nn.Module):
        @nn.compact
        def __call__(self, x, training=False):
            return nn.Dense(3)(x)

    def loss(labels, logits):
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, labels
        )

    mesh = MeshConfig.from_string("dp=1").create()
    return SPMDTrainer(
        mesh, Tiny(), loss, optax.sgd(0.1), np.zeros((4, 2), np.float32)
    )


def test_final_state_is_bit_for_bit_a_plain_loops(no_block):
    """The same seeded stream through ``run_stacked_steps`` (spans on, as
    always) and through a hand-written pad/mask/place/step loop ends in the
    same parameters, bit for bit."""
    from elasticdl_tpu.trainer.stacking import run_stacked_steps

    rng = np.random.default_rng(24)
    stream = [
        (
            rng.normal(size=(n, 2)).astype(np.float32),
            rng.integers(0, 3, size=n).astype(np.int32),
        )
        for n in (4, 4, 3, 4, 2)
    ]
    through = _tiny_trainer()
    run_stacked_steps(lambda: through, iter(stream), 1, canonical_rows=4)
    plain = _tiny_trainer()
    for features, labels in stream:
        n = labels.shape[0]
        plain._state, _ = plain._train_step(
            plain._state,
            jax.device_put(plain.pad_to(features, 4)),
            jax.device_put(plain.pad_to(labels, 4)),
            jax.device_put(plain.row_mask(n, 4)),
        )
    a = jax.tree_util.tree_leaves(jax.device_get(through.state.params))
    b = jax.tree_util.tree_leaves(jax.device_get(plain.state.params))
    assert len(a) == len(b) and all(np.array_equal(x, y) for x, y in zip(a, b))
    assert int(jax.device_get(through.state.step)) == len(stream)


def test_recorder_off_means_no_recorder_work_no_block_no_event(
    no_block, tmp_path
):
    """Restated from "no clock read when ``anatomy is None``": with no
    recorder installed the loop does no recorder work (none exists), blocks
    on nothing and emits no ``step_anatomy`` event; only the timeline's
    spans are written."""
    from elasticdl_tpu.telemetry import worker_hooks
    from elasticdl_tpu.telemetry.events import read_events
    from elasticdl_tpu.trainer.stacking import run_stacked_steps

    anatomy.uninstall()
    assert anatomy.get_recorder() is None
    assert anatomy.heartbeat_snapshot() == {}
    worker_hooks.install(str(tmp_path), worker_id=0)
    try:
        mark = TIMELINE.head()
        run_stacked_steps(
            lambda: _RecordingTrainer(), iter(_stream([4, 3])), 1,
            canonical_rows=4, anatomy=None,
        )
        names = {s.name for s in TIMELINE.since(mark)[0]}
    finally:
        worker_hooks.uninstall()
    assert {PHASE_HOST_FETCH, PHASE_ASSEMBLE} <= names
    events_file = tmp_path / "events.jsonl"
    events = read_events(str(events_file)) if events_file.exists() else []
    assert not [e for e in events if e["event"] == "step_anatomy"]


# ---- every callee writes its span --------------------------------------------


@pytest.fixture(scope="module")
def local_run(tmp_path_factory):
    """One LocalExecutor job (mnist, 2 tasks of 3 batches) and the spans it
    left on the process's timeline."""
    from elasticdl_tpu.data.recordio_gen import synthetic
    from elasticdl_tpu.trainer.local_executor import LocalExecutor
    from elasticdl_tpu.utils.args import parse_master_args

    tmp = tmp_path_factory.mktemp("timeline")
    train = synthetic.gen_mnist(
        str(tmp / "t"), num_records=192, num_shards=1, seed=0
    )
    args = parse_master_args(
        [
            "--model_def",
            "mnist_functional_api.mnist_functional_api.custom_model",
            "--training_data", train,
            "--minibatch_size", "32",
            "--records_per_task", "96",
        ]
    )
    mark = TIMELINE.head()
    LocalExecutor(args).run()
    spans, _ = TIMELINE.since(mark)
    return spans


DISPATCH_THREAD_SPANS = (
    PHASE_HOST_FETCH,
    PHASE_STEP_BOOKKEEPING,
    PHASE_ASSEMBLE,
    PHASE_H2D_TRANSFER,
    PHASE_ENQUEUE,
)
PRODUCER_THREAD_SPANS = (PHASE_PRODUCE_NEXT_TASK, PHASE_PRODUCE_BATCH)


@pytest.mark.parametrize("name", DISPATCH_THREAD_SPANS)
def test_local_executor_writes_dispatch_span(local_run, name):
    mine = [s for s in local_run if s.name == name]
    assert mine, f"no {name} span"
    assert {s.thread for s in mine} == {threading.current_thread().name}
    assert all(s.duration_ns >= 0 for s in mine)


@pytest.mark.parametrize("name", PRODUCER_THREAD_SPANS)
def test_task_prefetcher_writes_producer_span(local_run, name):
    mine = [s for s in local_run if s.name == name]
    assert mine, f"no {name} span"
    assert {s.thread for s in mine} == {"task-prefetch"}
    if name == PHASE_PRODUCE_BATCH:
        # wall and thread CPU time side by side, and the batch's bytes
        assert all(s.cpu_ns is not None and s.cpu_ns >= 0 for s in mine)
        assert all(s.count and s.count > 0 for s in mine)


def test_dispatch_ordinal_rises_by_one_per_enqueue(local_run):
    enqueues = [s for s in local_run if s.name == PHASE_ENQUEUE]
    assert len(enqueues) == 6  # 192 records / 32, one dispatch a batch
    ordinals = [s.ordinal for s in enqueues]
    assert ordinals == list(range(ordinals[0], ordinals[0] + 6))
    # "the span that caused it": what the thread recorded since its
    # previous enqueue carries this enqueue's ordinal
    for enqueue in enqueues:
        led_up = [
            s for s in local_run
            if s.ordinal == enqueue.ordinal and s.thread == enqueue.thread
            and s.name in (PHASE_ASSEMBLE, PHASE_H2D_TRANSFER)
        ]
        assert len(led_up) == 4  # one assemble; features, labels, mask placed
        assert all(
            s.start_ns + s.duration_ns <= enqueue.start_ns + enqueue.duration_ns
            for s in led_up
        )


def test_place_spans_count_the_bytes_placed(local_run):
    placed = [s.count for s in local_run if s.name == PHASE_H2D_TRANSFER]
    assert placed and all(c and c > 0 for c in placed)
    # the 32-row float mask is one of the three placements of a dispatch
    assert 32 * 4 in placed


def test_kth_produce_batch_is_the_kth_host_fetch(local_run):
    made = {s.ordinal: s for s in local_run if s.name == PHASE_PRODUCE_BATCH}
    fetched = {
        s.ordinal: s
        for s in local_run
        if s.name == PHASE_HOST_FETCH and s.count
    }
    assert len(made) == len(fetched) == 6
    assert sorted(made) == sorted(fetched)
    for k, fetch in fetched.items():
        # a batch is fetched after it was made
        assert made[k].start_ns + made[k].duration_ns <= (
            fetch.start_ns + fetch.duration_ns
        )


def test_outermost_place_call_records_one_span():
    """``place_canonical`` goes through the same placement as
    ``place_batch`` and must not count twice."""
    trainer = _tiny_trainer()
    batch = np.ones((3, 2), np.float32)
    for place in (
        trainer.place_batch,
        lambda t: trainer.place_canonical(t, 4),
        lambda t: trainer.place_stacked(np.stack([t, t])),
    ):
        mark = TIMELINE.head()
        place(batch)
        spans, _ = TIMELINE.since(mark)
        assert [s.name for s in spans] == [PHASE_H2D_TRANSFER]
        assert spans[0].count >= batch.nbytes


def test_a_blocked_put_is_on_the_timeline():
    from elasticdl_tpu.trainer.host_pipeline import TaskPrefetcher

    tasks = iter([(0, "t"), (1, None)])
    batch = (np.zeros((2, 2), np.float32), np.zeros(2, np.int32))
    prefetcher = TaskPrefetcher(
        lambda: next(tasks),
        lambda task: [batch] * 4,
        max_buffered_batches=1,
    )
    mark = TIMELINE.head()
    try:
        for _tid, _task, batches in prefetcher:
            for _ in batches:
                time.sleep(0.02)  # the producer waits for its budget
    finally:
        prefetcher.close()
    blocked = [
        s for s in TIMELINE.since(mark)[0] if s.name == PHASE_PRODUCE_BLOCKED
    ]
    assert blocked and all(s.thread == "task-prefetch" for s in blocked)
    assert max(s.duration_ns for s in blocked) >= 5_000_000


# ---- the profile window ------------------------------------------------------


class _FakeProfiler:
    def __init__(self, monkeypatch):
        self.calls = []
        monkeypatch.setattr(
            jax.profiler,
            "start_trace",
            lambda d, **kw: self.calls.append(("start", d, kw)),
        )
        monkeypatch.setattr(
            jax.profiler, "stop_trace", lambda: self.calls.append(("stop",))
        )


def test_window_opens_with_both_tracer_levels_at_zero(monkeypatch, tmp_path):
    from elasticdl_tpu.utils.profiling import StepProfiler

    fake = _FakeProfiler(monkeypatch)
    profiler = StepProfiler(str(tmp_path / "p"), start_step=1, num_steps=1)
    for _ in range(4):
        profiler.on_step()
    (start,) = [c for c in fake.calls if c[0] == "start"]
    options = start[2]["profiler_options"]
    assert options.python_tracer_level == 0
    assert options.host_tracer_level == 0


@pytest.mark.parametrize("how", ["flag", "armed"])
def test_no_way_to_open_a_window_with_default_options(
    monkeypatch, tmp_path, how
):
    from elasticdl_tpu.utils.profiling import StepProfiler

    fake = _FakeProfiler(monkeypatch)
    if how == "flag":
        profiler = StepProfiler(str(tmp_path / "p"), start_step=0, num_steps=1)
    else:
        profiler = StepProfiler("")
        assert profiler.arm(str(tmp_path / "p"), num_steps=1, window_id=1)
    profiler.on_step()
    profiler.on_step()
    starts = [c for c in fake.calls if c[0] == "start"]
    assert starts and all("profiler_options" in c[2] for c in starts)


def test_window_in_seconds_closes_by_the_clock(monkeypatch, tmp_path):
    from elasticdl_tpu.utils.profiling import StepProfiler

    fake = _FakeProfiler(monkeypatch)
    profiler = StepProfiler("")
    assert profiler.arm(str(tmp_path / "w"), seconds=0.05, window_id=1)
    profiler.on_step()  # opens
    for _ in range(50):  # many more steps than any default step count
        profiler.on_step()
    assert ("stop",) not in fake.calls
    time.sleep(0.06)
    profiler.on_step()
    assert fake.calls[-1] == ("stop",)


def test_window_close_writes_host_spans_with_a_sync_anchor(
    monkeypatch, tmp_path
):
    from elasticdl_tpu.utils.profiling import HOST_SPANS_FILE, StepProfiler

    _FakeProfiler(monkeypatch)
    out = tmp_path / "p"
    profiler = StepProfiler(str(out), start_step=1, num_steps=2)
    TIMELINE.record(PHASE_ASSEMBLE, time.perf_counter_ns())  # before: left out
    for i in range(5):
        profiler.on_step()
        t0 = time.perf_counter_ns()
        TIMELINE.record_enqueue(t0, jax.numpy.float32(i))
    profiler.stop()
    dumped = json.loads((out / HOST_SPANS_FILE).read_text())
    at = {n: i for i, n in enumerate(dumped["fields"])}
    names = [s[at["name"]] for s in dumped["spans"]]
    assert names.count(PHASE_SYNC) == 1
    assert names.count(PHASE_ENQUEUE) == 2  # the window's two steps
    assert PHASE_ASSEMBLE not in names
    sync = next(s for s in dumped["spans"] if s[at["name"]] == PHASE_SYNC)
    ends = [s[at["start_ns"]] + s[at["duration_ns"]] for s in dumped["spans"]]
    # the anchor is the window's last instant
    assert sync[at["start_ns"]] + sync[at["duration_ns"]] == max(ends)


def test_host_spans_land_beside_the_xplane(tmp_path):
    """A real capture on this backend: ``host_spans.json`` sits in the
    directory the profiler wrote its ``.xplane.pb`` to."""
    import glob

    from elasticdl_tpu.utils.profiling import HOST_SPANS_FILE, StepProfiler

    out = tmp_path / "p"
    profiler = StepProfiler(str(out), start_step=0, num_steps=1)
    step = jax.jit(lambda x: x + 1)
    for i in range(3):
        profiler.on_step()
        TIMELINE.record_enqueue(time.perf_counter_ns(), step(np.float32(i)))
    profiler.stop()
    traces = glob.glob(str(out / "plugins" / "profile" / "*" / "*.xplane.pb"))
    assert traces
    assert os.path.exists(
        os.path.join(os.path.dirname(traces[0]), HOST_SPANS_FILE)
    )


# ---- satellites --------------------------------------------------------------


def test_compile_listener_sums_trace_and_lower_time():
    from elasticdl_tpu.telemetry import compile_tracker

    compile_tracker.install()
    before = (
        compile_tracker.trace_secs_total(),
        compile_tracker.lower_secs_total(),
        compile_tracker.compile_count(),
    )
    jax.jit(lambda x: jax.numpy.tanh(x) * 24.0)(np.ones(7, np.float32))
    assert compile_tracker.trace_secs_total() > before[0]
    assert compile_tracker.lower_secs_total() > before[1]
    assert compile_tracker.compile_count() > before[2]


def test_resnet50_flops_count_two_per_multiply_accumulate():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from perf.flop_functions import resnet50

    assert anatomy.MODEL_FLOPS_PER_RECORD["imagenet_resnet50"] == pytest.approx(
        6.0 * resnet50.forward_macs(), rel=1e-3
    )


@pytest.mark.parametrize("kernel", ["flash_fwd", "flash_dq", "flash_dkv"])
def test_flash_kernels_carry_stable_names(kernel):
    from elasticdl_tpu.ops import attention

    q = jax.ShapeDtypeStruct((1, 256, 2, 64), jax.numpy.bfloat16)

    def loss(q, k, v):
        out = attention.flash_attention(q, k, v, causal=True, interpret=False)
        return out.astype(jax.numpy.float32).sum()

    jaxpr = str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, q, q))
    assert f"name={kernel}" in jaxpr


def test_request_profile_carries_a_window_in_seconds(monkeypatch, tmp_path):
    """The operator's RPC sizes a window by the clock: the field rides the
    heartbeat command down and arms the worker's profiler in seconds."""
    from elasticdl_tpu.master.servicer import MasterServicer
    from elasticdl_tpu.master.task_dispatcher import TaskDispatcher
    from elasticdl_tpu.rpc import messages as msg
    from elasticdl_tpu.utils.profiling import StepProfiler, apply_profile_command

    request = msg.decode(msg.encode(msg.RequestProfileRequest(seconds=2.5)))
    assert request.seconds == 2.5
    servicer = MasterServicer(4, TaskDispatcher({"s": (0, 8)}, records_per_task=4))
    assert servicer.request_profile(request).accepted
    command = dict(servicer._profile_command)
    assert command["seconds"] == 2.5
    fake = _FakeProfiler(monkeypatch)
    profiler = StepProfiler("")
    assert apply_profile_command(profiler, command, telemetry_dir=str(tmp_path))
    profiler.on_step()  # opens
    for _ in range(20):
        profiler.on_step()
    assert [c[0] for c in fake.calls] == ["start"]  # 2.5 s have not passed
    profiler.stop()
