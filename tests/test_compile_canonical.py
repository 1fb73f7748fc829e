"""Shape-canonical batching + the compile-count regression gate.

Pins ISSUE 5's guarantees:

- masked padded steps are EXACT over the real rows (train, stacked
  train, eval) — and the old repeat-last-row padding demonstrably was
  not (the tail-gradient bias this replaces);
- the canonical grouping policy: ragged tails join the dispatch group
  as masked members (no flush on shape change), trailing partial groups
  reuse the single-step program, the program cache holds two entries;
- the process-wide compile counter: increments on the first dispatch,
  stays flat across subsequent tasks and tails, survives reform
  generations monotonically on the master mirror;
- ``trace analyze`` attributes measured ``compile`` spans to the
  ``warmup_compile`` reform phase.
"""

import json
import os

import flax.linen as nn
import jax
import numpy as np
import optax
import pytest

from elasticdl_tpu.parallel.distributed import SPMDTrainer, trim_pad
from elasticdl_tpu.parallel.mesh import MeshConfig
from elasticdl_tpu.telemetry import compile_tracker
from elasticdl_tpu.trainer import stacking
from elasticdl_tpu.trainer.stacking import (
    PreStacked,
    canonical_batch_rows,
    run_stacked_steps,
)


class _Dense(nn.Module):
    """Deterministic per-row model: no batch stats, no dropout — batch
    composition cannot leak between rows, so masked-pad parity is exact
    up to float reduction order."""

    @nn.compact
    def __call__(self, x, training=False):
        return nn.Dense(3)(x)


def _loss(labels, predictions):
    labels = labels.reshape(-1)
    return optax.softmax_cross_entropy_with_integer_labels(
        predictions, labels
    ).mean()


def _mesh():
    # ONE device: the parity reference runs genuinely unpadded batches,
    # which a multi-device data axis would reject as indivisible
    return MeshConfig.from_string("dp=1").create()


def _data(n=8, seed=0):
    rng = np.random.RandomState(seed)
    feats = rng.randn(n, 4).astype(np.float32)
    labels = rng.randint(0, 3, size=(n,)).astype(np.int32)
    return feats, labels


def _trainer(mesh, tx=None):
    feats, _ = _data()
    return SPMDTrainer(
        mesh,
        _Dense(),
        _loss,
        tx if tx is not None else optax.sgd(0.1, momentum=0.9),
        feats[:1],
        embedding_threshold=None,
    )


def _params(trainer):
    return jax.device_get(trainer.state.params)


def _assert_tree_allclose(a, b, atol=1e-6):
    for left, right in zip(
        jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    ):
        np.testing.assert_allclose(left, right, atol=atol)


def _tree_max_delta(a, b):
    return max(
        float(np.abs(np.asarray(x) - np.asarray(y)).max())
        for x, y in zip(
            jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
        )
    )


# ---- canonical shape policy -------------------------------------------------


def test_canonical_batch_rows_policy():
    assert canonical_batch_rows(64, 1) == 64
    assert canonical_batch_rows(64, 8) == 64
    assert canonical_batch_rows(65, 8) == 72  # round UP to the divisor
    assert canonical_batch_rows(3, 8) == 8  # never below one shard row
    assert canonical_batch_rows(1, 1) == 1


# ---- masked-step exactness (the tail-gradient bias, pinned) -----------------


class TestMaskedStepParity:
    def test_masked_train_step_matches_unpadded(self):
        mesh = _mesh()
        feats, labels = _data()
        n, rows = 5, 8
        ref = _trainer(mesh)
        masked = _trainer(mesh)

        ref_metrics = ref.train_step(
            ref.place_batch(feats[:n]), ref.place_batch(labels[:n])
        )
        padded_f = masked.pad_to(feats[:n], rows)
        padded_l = masked.pad_to(labels[:n], rows)
        masked_metrics = masked.train_step(
            masked.place_batch(padded_f),
            masked.place_batch(padded_l),
            masked.place_batch(masked.row_mask(n, rows)),
        )
        assert abs(
            float(ref_metrics["loss"]) - float(masked_metrics["loss"])
        ) < 1e-6
        _assert_tree_allclose(_params(ref), _params(masked))

    def test_repeat_row_padding_without_mask_is_biased(self):
        """The bug the mask fixes: an UNWEIGHTED step over the padded
        batch over-weights the repeated last row and diverges from the
        unpadded step — this must stay visibly broken so the mask's
        value is falsifiable."""
        mesh = _mesh()
        feats, labels = _data()
        n, rows = 5, 8
        ref = _trainer(mesh)
        biased = _trainer(mesh)

        ref.train_step(
            ref.place_batch(feats[:n]), ref.place_batch(labels[:n])
        )
        biased.train_step(
            biased.place_batch(biased.pad_to(feats[:n], rows)),
            biased.place_batch(biased.pad_to(labels[:n], rows)),
        )
        assert _tree_max_delta(_params(ref), _params(biased)) > 1e-5

    def test_masked_stacked_steps_match_sequential_unpadded(self):
        mesh = _mesh()
        feats, labels = _data()
        n_tail, rows = 5, 8
        ref = _trainer(mesh)
        masked = _trainer(mesh)

        # reference: a full batch then an unpadded ragged tail
        ref.train_step(ref.place_batch(feats), ref.place_batch(labels))
        ref.train_step(
            ref.place_batch(feats[:n_tail]),
            ref.place_batch(labels[:n_tail]),
        )

        # canonical: ONE stacked dispatch, tail as a masked member
        stacked_f = np.stack([feats, masked.pad_to(feats[:n_tail], rows)])
        stacked_l = np.stack([labels, masked.pad_to(labels[:n_tail], rows)])
        stacked_w = np.stack(
            [masked.row_mask(rows, rows), masked.row_mask(n_tail, rows)]
        )
        masked.train_steps_stacked(
            masked.place_stacked(stacked_f),
            masked.place_stacked(stacked_l),
            masked.place_stacked(stacked_w),
        )
        assert masked.step == ref.step == 2
        _assert_tree_allclose(_params(ref), _params(masked), atol=1e-5)

    def test_masked_eval_loss_matches_host_recompute(self):
        """Satellite: the masked in-step eval loss is exact over the
        real rows — the host-side recompute LocalExecutor used to do is
        redundant."""
        mesh = _mesh()
        feats, labels = _data()
        n, rows = 5, 8
        trainer = _trainer(mesh)
        outputs, in_step_loss = trainer.eval_step(
            trainer.place_batch(trainer.pad_to(feats[:n], rows)),
            trainer.place_batch(trainer.pad_to(labels[:n], rows)),
            trainer.place_batch(trainer.row_mask(n, rows)),
        )
        trimmed = trim_pad(jax.device_get(outputs), n)
        host_loss = float(np.asarray(_loss(labels[:n], trimmed)))
        assert abs(float(jax.device_get(in_step_loss)) - host_loss) < 1e-6


# ---- canonical grouping policy ----------------------------------------------


class _RecordingTrainer:
    """pad_to/row_mask/dispatch shim recording every dispatch's kind,
    label shape and weights."""

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

    def place_stacked(self, tree):
        return tree

    def train_step(self, features, labels, weights=None):
        self.dispatches.append(
            ("single", np.shape(labels), np.array(weights))
        )

    def train_steps_stacked(self, features, labels, weights=None):
        self.dispatches.append(
            ("stacked", np.shape(labels), np.array(weights))
        )


def _plain_batches(sizes):
    return [
        (np.ones((n, 2), np.float32) * i, np.arange(n, dtype=np.int32))
        for i, n in enumerate(sizes)
    ]


class TestCanonicalGrouping:
    def test_tail_joins_group_as_masked_member(self):
        """A ragged tail no longer flushes the group: (4,4,3) at k=3 is
        ONE stacked dispatch whose last member is masked."""
        trainer = _RecordingTrainer()
        processed = run_stacked_steps(
            lambda: trainer,
            iter(_plain_batches([4, 4, 3])),
            3,
            canonical_rows=4,
        )
        assert processed == 11
        assert [d[0] for d in trainer.dispatches] == ["stacked"]
        kind, shape, weights = trainer.dispatches[0]
        assert shape == (3, 4)
        np.testing.assert_array_equal(
            weights,
            [[1, 1, 1, 1], [1, 1, 1, 1], [1, 1, 1, 0]],
        )

    def test_trailing_partial_group_dispatches_singles(self):
        """Fewer than k leftovers run through the already-compiled
        single-step program — never a new scan length."""
        trainer = _RecordingTrainer()
        processed = run_stacked_steps(
            lambda: trainer,
            iter(_plain_batches([4, 4, 3])),
            2,
            canonical_rows=4,
        )
        assert processed == 11
        assert [d[0] for d in trainer.dispatches] == ["stacked", "single"]
        assert trainer.dispatches[0][1] == (2, 4)
        assert trainer.dispatches[1][1] == (4,)
        np.testing.assert_array_equal(
            trainer.dispatches[1][2], [1, 1, 1, 0]
        )

    def test_prestacked_group_gets_all_ones_mask(self):
        trainer = _RecordingTrainer()
        feats = np.ones((2, 4, 2), np.float32)
        labels = np.zeros((2, 4), np.int32)
        item = PreStacked(feats, labels, 8, feats[0])
        processed = run_stacked_steps(
            lambda: trainer, iter([item]), 2, canonical_rows=4
        )
        assert processed == 8
        kind, shape, weights = trainer.dispatches[0]
        assert kind == "stacked" and shape == (2, 4)
        np.testing.assert_array_equal(weights, np.ones((2, 4)))

    def test_k1_is_a_group_of_one_masked_single(self):
        trainer = _RecordingTrainer()
        processed = run_stacked_steps(
            lambda: trainer,
            iter(_plain_batches([4, 3])),
            1,
            canonical_rows=4,
        )
        assert processed == 7
        assert [d[0] for d in trainer.dispatches] == ["single", "single"]
        np.testing.assert_array_equal(
            trainer.dispatches[1][2], [1, 1, 1, 0]
        )


# ---- compile counting -------------------------------------------------------


def _unique_jit_compile():
    """Force exactly one fresh backend compile (a shape this process
    has never jitted)."""
    _unique_jit_compile.dim += 1
    dim = 7000 + _unique_jit_compile.dim
    jax.jit(lambda x: x * 2 + 1)(np.ones(dim, np.float32))


_unique_jit_compile.dim = 0


class TestCompileTracking:
    def test_install_and_count(self):
        compile_tracker.install()
        before = compile_tracker.compile_count()
        _unique_jit_compile()
        assert compile_tracker.compile_count() == before + 1
        assert compile_tracker.compile_secs_total() > 0.0

    def test_compile_span_recorded(self, tmp_path):
        from elasticdl_tpu.telemetry import tracing

        compile_tracker.install()
        tracing.install(str(tmp_path), role="worker", sample_rate=1.0)
        try:
            _unique_jit_compile()
            tracing.flush()
        finally:
            tracing.uninstall()
        spans = tracing.read_spans(str(tmp_path / "spans.jsonl"))
        compile_spans = [
            s for s in spans if s.get("span") == tracing.SPAN_COMPILE
        ]
        assert compile_spans
        span = compile_spans[-1]
        assert span["end"] >= span["start"]

    def test_master_mirror_is_monotone_across_generation_resets(self):
        """Reset semantics: a re-formed world's processes start their
        per-process counters at zero, but the master's
        ``elasticdl_compile_total`` (set_total = monotone max, plus
        worker-reported exec-counter sums) never walks backward."""
        from elasticdl_tpu.telemetry.compile_tracker import (
            COMPILE_COUNT_KEY,
        )
        from elasticdl_tpu.telemetry.master_hooks import MasterTelemetry

        class _Dispatcher:
            exec_compiles = 0

            def add_observer(self, obs):
                pass

            def snapshot(self):
                return {
                    "pending": 0,
                    "pending_eval": 0,
                    "active": [],
                    "epoch": 0,
                }

            def exec_metrics_snapshot(self, _task_type):
                return {COMPILE_COUNT_KEY: self.exec_compiles}

        class _Servicer:
            cluster_version = 0

            def add_version_observer(self, cb):
                pass

            def set_event_sink(self, cb):
                pass

            def set_trace_provider(self, cb):
                pass

            def live_workers(self):
                return []

        telemetry = MasterTelemetry()
        dispatcher = _Dispatcher()
        telemetry.attach(dispatcher, _Servicer())

        def scraped_total():
            for line in telemetry.registry.exposition().splitlines():
                if line.startswith("elasticdl_compile_total "):
                    return float(line.split()[-1])
            raise AssertionError("elasticdl_compile_total not exposed")

        compile_tracker.install()
        _unique_jit_compile()
        dispatcher.exec_compiles = 5  # generation-0 worker reports
        gen0_total = scraped_total()
        assert gen0_total >= compile_tracker.compile_count() + 5

        # generation 1: fresh worker processes -> per-process counters
        # restart at zero (simulated via the test reset)...
        compile_tracker._reset_for_tests()
        assert compile_tracker.compile_count() == 0
        dispatcher.exec_compiles = 5
        # ...yet the exposed total never decreases
        assert scraped_total() >= gen0_total
        # and new generation compiles keep accumulating on top
        _unique_jit_compile()
        dispatcher.exec_compiles = 7
        assert scraped_total() >= gen0_total

    def test_stale_report_still_accumulates_compile_delta(self):
        """A report landing on a reclaimed/unknown lease is dropped for
        task accounting — but its compile delta is PROCESS-level, and
        the worker's watermark advances on RPC success, so the
        dispatcher must bank it anyway or the recompile disappears from
        the /metrics mirror forever."""
        from elasticdl_tpu.master.task_dispatcher import TaskDispatcher
        from elasticdl_tpu.telemetry.compile_tracker import (
            COMPILE_COUNT_KEY,
        )
        from elasticdl_tpu.utils.constants import TaskType

        dispatcher = TaskDispatcher(None)
        dispatcher.report(999, True, exec_counters={COMPILE_COUNT_KEY: 3})
        snapshot = dispatcher.exec_metrics_snapshot(TaskType.TRAINING)
        assert snapshot.get(COMPILE_COUNT_KEY) == 3
        # non-compile counters of a stale report stay dropped
        dispatcher.report(998, True, exec_counters={"time_foo_ms": 7})
        snapshot = dispatcher.exec_metrics_snapshot(TaskType.TRAINING)
        assert "time_foo_ms" not in snapshot

    def test_exec_counter_reporter_reships_delta_after_failed_report(self):
        """ExecCounterReporter advances its watermark only on commit():
        an attach whose report RPC failed re-ships the same delta."""
        compile_tracker.install()
        reporter = compile_tracker.ExecCounterReporter()
        _unique_jit_compile()
        first: dict = {}
        mark = reporter.attach(first)
        assert first.get(compile_tracker.COMPILE_COUNT_KEY, 0) >= 1
        # RPC failed -> no commit -> the delta stays pending
        second: dict = {}
        reporter.attach(second)
        assert second == first
        reporter.commit(mark)
        third: dict = {}
        reporter.attach(third)
        assert compile_tracker.COMPILE_COUNT_KEY not in third

    def test_program_store_counters_and_the_program_load_span(self, tmp_path):
        """A hit is one program handed to the backend: it moves
        ``compile_count`` and the ``compile`` span exactly as a miss's
        compile does, with the load's seconds, and adds a hit and a
        ``program_load`` span; a miss and a reject move only their own
        counter."""
        from elasticdl_tpu.telemetry import tracing

        compile_tracker.install()
        hits = compile_tracker.program_store_hits()
        misses = compile_tracker.program_store_misses()
        rejects = compile_tracker.program_store_rejects()
        compiles = compile_tracker.compile_count()
        secs = compile_tracker.compile_secs_total()
        tracing.install(str(tmp_path), role="worker", sample_rate=1.0)
        try:
            compile_tracker.record_program_store_miss()
            _unique_jit_compile()  # the miss's build
            compile_tracker.record_program_store_reject()
            compile_tracker.record_program_load(0.25)
            tracing.flush()
        finally:
            tracing.uninstall()
        assert compile_tracker.program_store_hits() == hits + 1
        assert compile_tracker.program_store_misses() == misses + 1
        assert compile_tracker.program_store_rejects() == rejects + 1
        # the miss's compile and the hit's load: one each
        assert compile_tracker.compile_count() == compiles + 2
        assert compile_tracker.compile_secs_total() >= secs + 0.25
        spans = tracing.read_spans(str(tmp_path / "spans.jsonl"))
        by_name = {}
        for span in spans:
            by_name.setdefault(span.get("span"), []).append(span)
        assert len(by_name[tracing.SPAN_COMPILE]) == 2
        (load,) = by_name[tracing.SPAN_PROGRAM_LOAD]
        assert load["end"] - load["start"] == pytest.approx(0.25, abs=1e-6)

    def test_reporter_and_master_mirror_carry_the_program_store_counters(self):
        """What carries ``compile_count`` to ``elasticdl_compile_total``
        carries the store's three counters beside it: the reporter's
        deltas, the dispatcher's bank of a stale report, the mirror."""
        from elasticdl_tpu.master.task_dispatcher import TaskDispatcher
        from elasticdl_tpu.telemetry.master_hooks import MasterTelemetry
        from elasticdl_tpu.utils.constants import TaskType

        compile_tracker.install()
        reporter = compile_tracker.ExecCounterReporter()
        compile_tracker.record_program_store_miss()
        compile_tracker.record_program_store_reject()
        compile_tracker.record_program_load(0.0)
        compile_tracker.record_program_load(0.0)
        shipped: dict = {}
        mark = reporter.attach(shipped)
        assert shipped == {
            compile_tracker.COMPILE_COUNT_KEY: 2,
            compile_tracker.PROGRAM_STORE_HITS_KEY: 2,
            compile_tracker.PROGRAM_STORE_MISSES_KEY: 1,
            compile_tracker.PROGRAM_STORE_REJECTS_KEY: 1,
        }
        reporter.commit(mark)
        again: dict = {}
        reporter.attach(again)
        assert again == {}

        dispatcher = TaskDispatcher(None)
        dispatcher.report(999, True, exec_counters=dict(shipped))
        banked = dispatcher.exec_metrics_snapshot(TaskType.TRAINING)
        assert banked == shipped

        class _Servicer:
            cluster_version = 0

            def add_version_observer(self, cb):
                pass

            def set_event_sink(self, cb):
                pass

            def set_trace_provider(self, cb):
                pass

            def live_workers(self):
                return []

        telemetry = MasterTelemetry()
        telemetry.attach(dispatcher, _Servicer())
        exposed = {
            line.split()[0]: float(line.split()[-1])
            for line in telemetry.registry.exposition().splitlines()
            if line.startswith("elasticdl_program_store_")
        }
        # the workers' shipped deltas on top of this process's own totals
        assert exposed == {
            "elasticdl_program_store_hits_total": 2
            + compile_tracker.program_store_hits(),
            "elasticdl_program_store_misses_total": 1
            + compile_tracker.program_store_misses(),
            "elasticdl_program_store_rejects_total": 1
            + compile_tracker.program_store_rejects(),
        }

    def test_compile_metric_visible_without_dispatcher(self):
        from elasticdl_tpu.telemetry.master_hooks import MasterTelemetry

        telemetry = MasterTelemetry()
        text = telemetry.registry.exposition()
        assert "# TYPE elasticdl_compile_total counter" in text


# ---- the compile-once guarantee, end to end ---------------------------------


def _ragged_local_args(tmp_path, steps_per_dispatch="1"):
    """3 tasks (9, 9, 6 records at minibatch 4) -> batch streams
    (4,4,1), (4,4,1), (4,2): two distinct tail lengths."""
    from elasticdl_tpu.data.recordio_gen import synthetic
    from elasticdl_tpu.utils.args import parse_master_args

    train = synthetic.gen_mnist(
        str(tmp_path / "train"), num_records=24, num_shards=1, seed=3
    )
    return parse_master_args(
        [
            "--model_def",
            "mnist_functional_api.mnist_functional_api.custom_model",
            "--training_data",
            train,
            "--minibatch_size",
            "4",
            "--records_per_task",
            "9",
            "--num_epochs",
            "1",
            "--steps_per_dispatch",
            steps_per_dispatch,
            "--compute_dtype",
            "float32",
        ]
    )


def test_local_executor_ragged_tails_compile_once(tmp_path, monkeypatch):
    """Acceptance: >= 3 tasks with >= 2 distinct tail lengths execute
    with exactly ONE train-step compile — the counter increments on the
    first dispatch and stays flat across subsequent tasks and tails."""
    from elasticdl_tpu.trainer.local_executor import LocalExecutor

    compile_tracker.install()
    args = _ragged_local_args(tmp_path, steps_per_dispatch="1")
    executor = LocalExecutor(args)

    dispatch_compiles = []
    orig = SPMDTrainer.train_step

    def wrapped(self, *a, **kw):
        before = compile_tracker.compile_count()
        result = orig(self, *a, **kw)
        dispatch_compiles.append(compile_tracker.compile_count() - before)
        return result

    monkeypatch.setattr(SPMDTrainer, "train_step", wrapped)
    executor.run()
    assert int(executor.state.step) == 8  # ceil(9/4)*2 + ceil(6/4)
    assert len(dispatch_compiles) == 8
    assert dispatch_compiles[0] > 0  # first dispatch compiles the step
    # ...and every later dispatch (other tasks, BOTH tail lengths)
    # reuses it: zero mid-task recompiles
    assert dispatch_compiles[1:] == [0] * 7, dispatch_compiles


# ---- trace analyze: measured compile spans ----------------------------------


def test_analyze_attributes_measured_compile_span(tmp_path):
    from elasticdl_tpu.telemetry import trace as trace_cli
    from elasticdl_tpu.telemetry.tracing import SPAN_COMPILE, gen_span_id, gen_trace_id

    run = str(tmp_path / "run")
    os.makedirs(run)
    t0 = 1000.0
    events = []
    for generation, base in ((0, t0), (1, t0 + 14.0)):
        for i in range(2):
            events.append(
                {
                    "monotonic": base + i,
                    "time": 1.7e9 + base + i,
                    "event": "step",
                    "step": i,
                    "generation": generation,
                    "worker_id": 0,
                    "records": 8,
                    **({"duration_secs": 1.0} if i else {}),
                }
            )
    # gap: 10s (last gen-0 step at t0+1 -> first gen-1 step at t0+14);
    # a measured 4s compile sits inside it
    spans = [
        {
            "span": SPAN_COMPILE,
            "trace_id": gen_trace_id(),
            "span_id": gen_span_id(),
            "parent_span_id": "",
            "role": "worker",
            "worker_id": 0,
            "generation": 1,
            "start": t0 + 8.0,
            "end": t0 + 12.0,
        }
    ]
    for name, records in (("events.jsonl", events), ("spans.jsonl", spans)):
        with open(os.path.join(run, name), "w", encoding="utf-8") as f:
            for record in records:
                f.write(json.dumps(record) + "\n")

    report = trace_cli.analyze_run_dir(run)
    analysis = next(iter(report["runs"].values()))
    gap = analysis["reform_downtime"][0]
    phases = gap["phases_secs"]
    # the compile span (4s) plus the bridge to the first step (2s) are
    # measured warmup_compile; the 7s before the span are unattributed
    assert abs(phases["warmup_compile"] - 6.0) < 1e-6, phases
    assert abs(phases["unattributed"] - 7.0) < 1e-6, phases
    assert abs(sum(phases.values()) - gap["downtime_secs"]) < 1e-6


# ---- dispatch-probe warm ----------------------------------------------------


def test_warm_dispatch_overhead_async(monkeypatch):
    monkeypatch.setattr(stacking, "_DISPATCH_OVERHEAD", [None])
    calls = []

    def fake_probe(trials=3):
        calls.append(trials)
        return 0.001

    monkeypatch.setattr(stacking, "probe_dispatch_overhead", fake_probe)
    thread = stacking.warm_dispatch_overhead_async()
    assert thread is not None
    thread.join(timeout=5)
    assert stacking._DISPATCH_OVERHEAD[0] == 0.001
    # cache hot -> the real consumer pays nothing and no second probe
    assert stacking.measured_dispatch_overhead() == 0.001
    assert calls == [3]
    # warm again: no-op once measured
    assert stacking.warm_dispatch_overhead_async() is None


def test_eval_reported_loss_matches_host_recompute_end_to_end(tmp_path):
    """Satellite: LocalExecutor's reported eval loss (now the masked
    in-step loss) equals the deleted host-side recompute."""
    from elasticdl_tpu.data.recordio_gen import synthetic
    from elasticdl_tpu.trainer.local_executor import LocalExecutor
    from elasticdl_tpu.utils.args import parse_master_args

    train = synthetic.gen_mnist(
        str(tmp_path / "train"), num_records=16, num_shards=1, seed=5
    )
    eval_dir = synthetic.gen_mnist(
        str(tmp_path / "eval"), num_records=10, num_shards=1, seed=6
    )
    args = parse_master_args(
        [
            "--model_def",
            "mnist_functional_api.mnist_functional_api.custom_model",
            "--training_data",
            train,
            "--validation_data",
            eval_dir,
            "--minibatch_size",
            "4",
            "--records_per_task",
            "16",
            "--num_epochs",
            "1",
            "--compute_dtype",
            "float32",
        ]
    )
    executor = LocalExecutor(args)
    executor.run()
    # recompute the eval loss host-side over the REAL rows, the way the
    # deleted code did, and compare to the reported (in-step) loss
    from elasticdl_tpu.data.factory import create_data_reader
    from elasticdl_tpu.master.task_dispatcher import TaskDispatcher
    from elasticdl_tpu.trainer.state import Modes

    spec = executor._spec
    reader = create_data_reader(
        args.validation_data, records_per_task=args.records_per_task
    )
    dispatcher = TaskDispatcher(
        None,
        evaluation_shards=reader.create_shards(),
        records_per_task=args.records_per_task,
    )
    total, weight = 0.0, 0
    while True:
        tid, task = dispatcher.get_eval_task(0)
        if task is None:
            break
        for features, labels in executor._task_dataset(
            reader, task, Modes.EVALUATION
        ):
            n = int(np.shape(np.asarray(labels))[0])
            outputs = executor.trainer.predict_step(
                executor._place_canonical(features)
            )
            outputs = trim_pad(jax.device_get(outputs), n)
            total += float(np.asarray(spec.loss(labels, outputs))) * n
            weight += n
        dispatcher.report(tid, True)
    host_loss = total / weight
    reported = executor.evaluate()["loss"]
    assert reported == pytest.approx(host_loss, rel=1e-6)
