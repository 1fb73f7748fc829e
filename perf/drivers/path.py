"""Traffic mode ``path`` (the default): the cell's shards through
``LocalExecutor.run`` — dispatcher, reader, host pipeline, dispatch, step —
as ``elasticdl_tpu train --distribution_strategy Local`` runs them."""

from __future__ import annotations

import os
from unittest import mock

from elasticdl_tpu.master.task_dispatcher import FAIL_COUNT, TaskDispatcher
from elasticdl_tpu.trainer import local_executor
from elasticdl_tpu.utils.constants import TaskType

from perf import trafficgen
from perf.executor import WindowClosed


def prepare(cell, seed: int, work_dir: str) -> dict:
    """Write the shards from ``seed`` and read each once: the plan's counts
    with the data directory."""
    return trafficgen.generate(
        cell.record_kind(), cell.traffic, cell.chips, seed,
        os.path.join(work_dir, "data"),
    )


class _CompletedRecords:
    """A task-lifecycle observer: records of the training tasks the
    dispatcher counted as done, and of those reported as failed."""

    def __init__(self):
        self.completed = 0
        self.failed = 0

    def on_task_done(self, _task_id, task, _worker_id, success, exec_counters):
        if task.type != TaskType.TRAINING:
            return
        if success:
            self.completed += task.num_records
        else:
            self.failed += task.num_records
        self.failed += exec_counters.get(FAIL_COUNT, 0)


def run(executor, probe, prepared: dict, traffic: dict) -> dict:
    """Run the job until the probe closes the window.  ``failed`` is the
    dispatcher's own count of failed training records; the checks hold its
    count of completed ones to the records the harness counted."""
    counted = _CompletedRecords()

    class Observed(TaskDispatcher):
        def __init__(self, *args, **kwargs):
            super().__init__(*args, **kwargs)
            self.add_observer(counted)

    try:
        with mock.patch.object(local_executor, "TaskDispatcher", Observed):
            executor.run()
    except WindowClosed:
        pass
    else:
        raise RuntimeError("the job ended before the window did")
    finally:
        probe.abort_trace()
    return {
        "failed": counted.failed,
        "checks": {
            "records_completed": counted.completed == probe.records_seen,
            "none_failed": counted.failed == 0,
        },
    }
