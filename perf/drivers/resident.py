"""Traffic mode ``resident``: one seeded batch, placed on the device once
with ``SPMDTrainer.place_batch`` and stepped by the trainer's own jitted
train step, one dispatch per step — the program the path runs, with the
dispatcher and the data plane taken out.  The rate of such a cell is the
step's alone, not the train path's.  A "task" is ``steps_per_task`` steps;
readings follow the same interval rule."""

from __future__ import annotations

import time

import jax

from perf import trafficgen
from perf.executor import WindowClosed


def prepare(cell, seed: int, work_dir: str) -> dict:
    """The plan's counts and the one batch, made from ``seed``; nothing is
    written."""
    counts = trafficgen.plan(cell.traffic, cell.chips)
    counts["batch"] = trafficgen.one_batch(
        cell.record_kind(), cell.traffic, counts["minibatch_size"], seed
    )
    return counts


def run(executor, probe, prepared: dict, traffic: dict) -> dict:
    """No dispatcher and no reader here, so no record can fail apart from
    the step that trains it, and a step that fails ends the run with no
    result: ``failed`` is 0 by construction and the path's two record
    checks do not apply.  What holds a record to "trained" is the probe's
    own check at every interval's close: ``state.step`` read back from the
    device equals the steps dispatched."""
    features, labels = prepared["batch"]
    steps_per_task = int(traffic["steps_per_task"])
    executor._ensure_trainer(features)
    trainer = executor.trainer
    rows = executor._canonical_rows
    records = int(jax.tree_util.tree_leaves(labels)[0].shape[0])
    placed = (
        trainer.place_batch(trainer.pad_to(features, rows)),
        trainer.place_batch(trainer.pad_to(labels, rows)),
        trainer.place_batch(trainer.row_mask(records, rows)),
    )
    try:
        while True:
            probe.before_task()
            for _ in range(steps_per_task):
                t0 = time.perf_counter_ns()
                trainer.train_step(*placed)
                probe.note_dispatch(t0, time.perf_counter_ns())
            probe.after_task(records * steps_per_task)
    except WindowClosed:
        pass
    finally:
        probe.abort_trace()
    return {"failed": 0, "checks": {}}
