"""Shared ``--steps_per_dispatch`` grouping: k minibatches -> one scanned
dispatch.

THE one implementation of the grouping/ragged-tail policy, used by every
runtime (LocalExecutor, the lockstep worker and the task-stream worker)
so their step semantics cannot drift.  Every batch is padded to the
canonical row count (:func:`canonical_batch_rows`) and carries a
zero/one row mask, so a task's ragged tail batch is one more group
member, not a new input shape.  A full group of k >= 2 is stacked on a
leading axis and run through ``SPMDTrainer.train_steps_stacked``; a
trailing partial group (fewer than k leftovers) runs its members as
single steps through the one compiled single-step program, never a new
scan length.  In lockstep worlds every process sees the same
deterministic batch stream per task, so all processes compute the same
grouping without communication.
"""

from __future__ import annotations

import contextlib
import os
import threading
import time
from typing import Callable, Iterable

import jax
import numpy as np

from elasticdl_tpu.telemetry.anatomy import (
    PHASE_ASSEMBLE,
    PHASE_STEP_BOOKKEEPING,
    TIMELINE,
)


def _batch_size(tree) -> int:
    leaves = jax.tree_util.tree_leaves(tree)
    return int(np.shape(leaves[0])[0]) if leaves else 0


def canonical_batch_rows(minibatch_size: int, divisor: int) -> int:
    """THE canonical per-step batch shape (shape-canonical batching,
    docs/designs/shape_canonicalization.md): ``minibatch_size`` rounded
    up to the mesh's batch divisor, so one padded-and-masked shape
    serves full batches, ragged tails AND shard divisibility — the
    jitted step compiles once per step kind instead of once per tail
    length."""
    div = max(1, int(divisor))
    return max(div, -(-int(minibatch_size) // div) * div)


class PreStacked:
    """A ready-made dispatch group: ``(k, B, ...)`` feature/label trees
    (typically zero-copy reshapes of a decode window —
    ``data/fast_pipeline.py``), dispatched as ONE stacked scan without
    the per-batch grouping path's k queue hops, k pad calls, and the
    np.stack copy.  ``num_records`` counts the real rows;
    ``sample_features`` is a (B, ...) view for lazy trainer creation."""

    __slots__ = ("features", "labels", "num_records", "sample_features")

    def __init__(self, features, labels, num_records, sample_features):
        self.features = features
        self.labels = labels
        self.num_records = num_records
        self.sample_features = sample_features

    @property
    def num_steps(self) -> int:
        return int(
            jax.tree_util.tree_leaves(self.features)[0].shape[0]
        )


# ---- `--steps_per_dispatch auto` sizing ------------------------------------

# byte target per stacked host->device put: auto-k sizes a dispatch
# group to stay under it (env-overridable).  The value predates the
# current machine; re-deriving it on the chip is ROADMAP A1/C2.
TRANSFER_CLIFF_BYTES = int(
    os.environ.get("EDL_TRANSFER_CLIFF_BYTES", 7 << 20)
)
# dispatches cheaper than this don't need amortizing: k=1 keeps
# per-step hooks at full granularity
CHEAP_DISPATCH_SECS = 0.002
# scan-length cap: bounds compile time, host stacking memory, and hook
# (milestone/checkpoint) granularity
MAX_AUTO_K = 64

_DISPATCH_OVERHEAD: list = [None]
# one probe per process: the TaskPrefetcher producer thread (fast_pipeline
# auto sizing) and the main thread can both arrive here; concurrent probes
# would contend with each other and cache an inflated overhead
_DISPATCH_OVERHEAD_LOCK = threading.Lock()


def probe_dispatch_overhead(trials: int = 3) -> float:
    """Seconds per dispatch of a trivial jitted op on FRESH input
    buffers (best-of-``trials`` to shed contention), UNCACHED — the
    overhead measurement itself.  Fresh inputs are what the training
    path ships, so fresh inputs are what is timed.  Callers want the
    cached :func:`measured_dispatch_overhead`."""
    f = jax.jit(lambda x: x + 1)
    jax.device_get(f(np.zeros(256, np.float32)))  # compile
    best = float("inf")
    for i in range(trials):
        x = np.full(256, float(i + 1), np.float32)  # fresh buffer
        t0 = time.perf_counter()
        jax.device_get(f(x))
        best = min(best, time.perf_counter() - t0)
    return best


def measured_dispatch_overhead() -> float:
    """Cached-per-process :func:`probe_dispatch_overhead` — the
    per-dispatch floor the auto-k sizing amortizes (~3 round trips,
    measured once)."""
    with _DISPATCH_OVERHEAD_LOCK:
        if _DISPATCH_OVERHEAD[0] is None:
            _DISPATCH_OVERHEAD[0] = probe_dispatch_overhead()
        return _DISPATCH_OVERHEAD[0]


def warm_dispatch_overhead_async():
    """Warm the per-process dispatch-overhead cache on a background
    thread, so the first ``'auto'`` sizing (on the TaskPrefetcher's
    producer thread) finds the probe already measured instead of paying
    its compile + 3 round trips on the first dispatch's critical path.
    Runtimes call this at BUILD time — before data flows — so the probe
    normally finishes while the host is otherwise reading its first
    shard; if a trainer-build compile does overlap the tail of the
    probe, best-of-3 sheds most of the contention (the same exposure
    the old on-demand probe had on the producer thread).  A no-op once
    the cache is hot."""
    if _DISPATCH_OVERHEAD[0] is not None:
        return None
    thread = threading.Thread(
        target=measured_dispatch_overhead,
        name="dispatch-probe-warm",
        daemon=True,
    )
    thread.start()
    return thread


def auto_steps_per_dispatch(
    batch_bytes: int, dispatch_overhead_secs: float
) -> int:
    """THE sizing rule: k = 1 when dispatch is cheap; otherwise the most
    batches whose stacked transfer stays under the link's put-size
    target, capped.

    Pinned by tests/test_stacking_auto.py: on a 130ms-dispatch link,
    803KB f32 mnist batches -> k=9 (7MB target), the ~200KB uint8-wire
    form -> k=36, tiny CTR batches -> MAX_AUTO_K; sub-ms dispatch ->
    k=1 on any batch size."""
    if dispatch_overhead_secs < CHEAP_DISPATCH_SECS or batch_bytes <= 0:
        return 1
    return max(1, min(MAX_AUTO_K, TRANSFER_CLIFF_BYTES // batch_bytes))


def choose_stack_k(steps_per_dispatch, training: bool, allow_auto: bool = True):
    """THE stack_k selection rule for ``build_task_batches`` callers —
    one definition instead of one per runtime.

    Returns ``None`` (no pipeline-side stacking) outside training, for
    k <= 1, and for ``'auto'`` when ``allow_auto=False`` — lockstep
    worlds set that: the pipeline's auto sizing probes per-process wall
    clock, and a k disagreement between processes would compile
    different stacked programs and deadlock the collectives (their
    plain-batch path re-sizes deterministically inside
    ``run_stacked_steps`` instead)."""
    if not training:
        return None
    k = steps_per_dispatch or 1
    if k == "auto":
        return "auto" if allow_auto else None
    return k if isinstance(k, int) and k > 1 else None


def resolve_steps_per_dispatch(
    k, sample_batch=None, deterministic: bool = False
) -> int:
    """Resolve a ``--steps_per_dispatch`` value (int or ``'auto'``).

    ``sample_batch``: one (features, labels) pair — its leaf bytes are
    the per-step transfer size.

    ``deterministic=True`` (lockstep worlds) resolves from the batch
    bytes ALONE — a pure function of the data, identical on every
    process.  The wall-clock overhead probe is per-process: around the
    CHEAP_DISPATCH_SECS threshold two co-scheduled processes could
    measure opposite sides of it, compile different stacked programs,
    and hang each other's collectives.  The byte rule without the probe
    merely stacks on hosts that didn't need it — safe (the scan is
    semantically identical and cheap-link stacking still amortizes a
    little), whereas a k disagreement deadlocks the world.
    """
    if k != "auto":
        return int(k or 1)
    if sample_batch is None:
        return 1
    batch_bytes = sum(
        np.asarray(leaf).nbytes
        for leaf in jax.tree_util.tree_leaves(sample_batch)
    )
    if deterministic:
        return auto_steps_per_dispatch(batch_bytes, float("inf"))
    return auto_steps_per_dispatch(
        batch_bytes, measured_dispatch_overhead()
    )


def assemble_canonical_group(trainer, group, k, rows):
    """THE canonical-group assembly policy — one definition site shared
    by the serial flush below and the device-pipeline stager, so the
    pipelined path can never drift from the serial baseline its parity
    is gated against.  ``group`` is ``[(features, labels, n_real)]``;
    returns ``("stacked", (feats, labels, weights))`` — a full group of
    k >= 2 padded and stacked into one scan input — or
    ``("singles", [(feats, labels, mask)])`` for anything shorter (the
    trailing-partial rule: those dispatch through the already-compiled
    single-step program, never a new scan length).  Recorded on the
    timeline as ``assemble``, here, so every caller's is."""
    t0 = time.perf_counter_ns()
    padded = [
        (
            trainer.pad_to(f, rows),
            trainer.pad_to(l, rows),
            trainer.row_mask(n, rows),
        )
        for f, l, n in group
    ]
    if len(padded) >= 2 and len(padded) == k:
        stacked_f = jax.tree_util.tree_map(
            lambda *xs: np.stack(xs), *[p[0] for p in padded]
        )
        stacked_l = jax.tree_util.tree_map(
            lambda *xs: np.stack(xs), *[p[1] for p in padded]
        )
        stacked_w = np.stack([p[2] for p in padded])
        assembled = "stacked", (stacked_f, stacked_l, stacked_w)
    else:
        assembled = "singles", padded
    TIMELINE.record(PHASE_ASSEMBLE, t0)
    return assembled


def timed_hook(hook):
    """``pre_batch`` / ``post_group`` hooks (telemetry samples, the
    profiler, milestone checks) run between the dispatch's other spans:
    recorded as ``step_bookkeeping`` where the loops call them.  None
    for a None hook."""
    if hook is None:
        return None

    def timed(*args):
        t0 = time.perf_counter_ns()
        out = hook(*args)
        TIMELINE.record(PHASE_STEP_BOOKKEEPING, t0)
        return out

    return timed


def prestacked_weights(item: PreStacked) -> np.ndarray:
    """The all-ones ``(k, B)`` weight mask every PreStacked dispatch
    carries: ready-made groups hold full batches only, and the weights
    keep the ONE weighted scan shape shared with canonical plain
    groups.  One definition site for what was copied into each
    runtime's PreStacked branch."""
    leaf = jax.tree_util.tree_leaves(item.features)[0]
    return np.ones(leaf.shape[:2], np.float32)


def run_stacked_steps(
    get_trainer: Callable,
    batches: Iterable,
    k,
    pre_batch: Callable | None = None,
    post_group: Callable | None = None,
    dispatch_ctx: Callable | None = None,
    deterministic_auto: bool = False,
    *,
    canonical_rows: int,
    anatomy=None,
    device_prefetch: bool = False,
    pipeline_depth: int | None = None,
) -> int:
    """Drive ``batches`` of ``(features, labels)`` through the trainer in
    groups of ``k`` steps per dispatch; returns records processed.

    ``get_trainer``: called lazily (the runtimes create their trainer on
    the first batch — ``pre_batch`` is where that happens).
    ``pre_batch(features)``: per incoming batch (ensure-trainer,
    profiler hooks).  ``post_group()``: after every dispatch group
    (milestone hooks run at dispatch granularity, deviation D9a).
    ``dispatch_ctx()``: context manager wrapping each device dispatch
    (timing buckets).

    ``canonical_rows`` (required; the runtimes pass
    :func:`canonical_batch_rows`): every batch is padded to that fixed
    row count with a per-row zero/one weight mask threaded through the
    jitted step, so a task's ragged tail batch is just another masked
    group member instead of a new input shape.  The group never flushes
    on a shape change, the program cache holds exactly two entries (the
    weighted step + one scan-k variant), and in lockstep worlds every
    process dispatches identical shapes by construction — a tail shape
    disagreement cannot deadlock the collectives.  A trailing partial
    group (fewer than k leftovers) runs its members through the
    already-compiled single-step program rather than compiling a third
    scan length.  A :class:`PreStacked` item in the stream is a
    ready-made full group and dispatches as one.

    The host's timeline (telemetry/anatomy.py) is written on every
    path: ``host_fetch`` at the stream seam and ``step_bookkeeping``
    around the hooks, here; ``assemble``, ``h2d_transfer`` and
    ``enqueue`` by the callees that do that work.  Nothing of it blocks.

    ``anatomy`` (an installed
    :class:`~elasticdl_tpu.telemetry.anatomy.AnatomyRecorder`, or None):
    the blocking, sum-exact mode — each dispatch additionally blocks on
    its outputs so device time is measured, not queued, and each group
    commits the timeline's spans since the previous one as disjoint
    phases summing exactly to the group's wall time.  ``None`` (the
    default): ONE ``is None`` branch per dispatch, nothing blocks.

    ``device_prefetch`` (the runtimes resolve ``--device_prefetch`` /
    its forwarded env once at build): groups are assembled and PLACED
    on a background staging thread while the current group computes,
    and dispatch outputs retire one group behind in a bounded window
    (trainer/device_pipeline.py) — same grouping policy, same hook
    cadence, same accounting; the window is drained before this
    function returns, so callers report tasks only over retired groups.

    ``pipeline_depth`` (``--pipeline_depth``, default 2): the prefetch
    path's retire window / staging bound; unused on the serial path.
    """
    if device_prefetch:
        from elasticdl_tpu.trainer.device_pipeline import (
            run_pipelined_steps,
        )

        return run_pipelined_steps(
            get_trainer,
            batches,
            k,
            pre_batch=pre_batch,
            post_group=post_group,
            dispatch_ctx=dispatch_ctx,
            deterministic_auto=deterministic_auto,
            canonical_rows=canonical_rows,
            anatomy=anatomy,
            pipeline_depth=pipeline_depth,
        )
    # boundary-stall instrumentation (trainer/device_pipeline.py): the
    # first flush after a task boundary closes the pending mark — one
    # global load per flush when no mark is pending
    from elasticdl_tpu.trainer.device_pipeline import note_boundary_dispatch

    ctx = dispatch_ctx or contextlib.nullcontext
    group: list = []
    processed = 0
    # the timeline's seams (always on): the wait inside next(), and the
    # hooks; the blocking mode adds one wait per dispatch, below
    batches = TIMELINE.timed_fetches(batches)
    pre_batch = timed_hook(pre_batch)
    post_group = timed_hook(post_group)

    def _retire(trainer, steps, n_records):
        # what follows every dispatch group, stacked or singles
        nonlocal processed
        processed += n_records
        if post_group is not None:
            post_group()
        if anatomy is not None:
            anatomy.commit(
                steps=steps,
                records=n_records,
                step=getattr(trainer, "step", None),
            )

    def _dispatch_stacked(trainer, features, labels, weights, n_records):
        # THE stacked dispatch: an assembled plain group or a PreStacked
        # item, k steps in one scan
        with ctx():
            out = trainer.train_steps_stacked(
                trainer.place_stacked(features),
                trainer.place_stacked(labels),
                trainer.place_stacked(weights),
            )
            if anatomy is not None:
                anatomy.ready_wait(out)
        _retire(trainer, len(weights), n_records)  # weights: (k, rows)

    def _flush():
        if not group:
            return
        trainer = get_trainer()
        note_boundary_dispatch()
        n_records = sum(n for _f, _l, n in group)
        kind, assembled = assemble_canonical_group(
            trainer, group, k, canonical_rows
        )
        group.clear()
        if kind == "stacked":
            _dispatch_stacked(trainer, *assembled, n_records)
            return
        # trailing partial group: k' single weighted steps through the
        # one compiled program — never a scan-k' compile
        for features, labels, mask in assembled:
            with ctx():
                out = trainer.train_step(
                    trainer.place_batch(features),
                    trainer.place_batch(labels),
                    trainer.place_batch(mask),
                )
                if anatomy is not None:
                    anatomy.ready_wait(out)
        _retire(trainer, len(assembled), n_records)

    for item in batches:
        if isinstance(item, PreStacked):
            # a ready-made group: flush any pending plain batches (they
            # must dispatch in stream order), then dispatch directly
            _flush()
            if pre_batch is not None:
                # one call per STEP, matching the plain path's hook
                # cadence (profiler counts calls == steps)
                for _ in range(item.num_steps):
                    pre_batch(item.sample_features)
            trainer = get_trainer()
            note_boundary_dispatch()
            _dispatch_stacked(
                trainer,
                item.features,
                item.labels,
                prestacked_weights(item),
                item.num_records,
            )
            continue
        features, labels = item
        if pre_batch is not None:
            pre_batch(features)
        if k == "auto":  # sized from the first real batch's bytes
            k = resolve_steps_per_dispatch(
                k, (features, labels), deterministic=deterministic_auto
            )
        group.append((features, labels, _batch_size(labels)))
        if len(group) == k:
            _flush()
    _flush()
    return processed
