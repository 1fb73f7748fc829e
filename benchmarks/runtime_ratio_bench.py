"""Relative e2e data-plane throughput of the three training runtimes.

One host-CPU measurement on IDENTICAL data (deepfm/frappe shards — the
data-plane showcase config) for:

- ``LocalExecutor`` — the e2e reference point (``elasticdl train
  --distribution_strategy=Local``),
- the task-stream ``Worker`` against an in-process master — VERDICT r5
  #3's acceptance: its training throughput must sit within ~1.2x of
  LocalExecutor now that it shares the vectorized plane,
- a REAL 2-process lockstep world (``--num_workers 2``) — VERDICT r5
  #8: the every-process-reads-every-task design (worker/lockstep.py)
  has a host-decode cost that scales with world size; this records it
  as ``lockstep_e2e_vs_local`` instead of leaving it an assumption.
  On this one-core host the two processes also serialize their compute
  halves, so the ratio is a LOWER bound for multi-core hosts.

Window: first task-report -> last task-report (compile happens inside
the first task, so it is excluded), records = tasks-after-first x
records_per_task (all tasks equal-size by construction), with a final
device sync before the last mark.

Prints ONE JSON line:
  {"local_records_per_sec": L, "taskstream_records_per_sec": T,
   "taskstream_vs_local": T/L, "lockstep_records_per_sec": K,
   "lockstep_e2e_vs_local": K/L, ...}

Run standalone: ``python benchmarks/runtime_ratio_bench.py``; bench.py
invokes it in a ``JAX_PLATFORMS=cpu`` subprocess so it never touches
the TPU chip the throughput configs are timing.
"""

from __future__ import annotations

import json
import os
import sys
import tempfile
import threading
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS", "")

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

# pin via config too, BEFORE any backend initializes — this benchmark
# must never touch the chip bench.py's throughput configs are timing
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

MODEL_DEF = "deepfm_edl_embedding.deepfm_edl_embedding.custom_model"
NUM_RECORDS = 131072
RECORDS_PER_TASK = 16384
BATCH = 512
STEPS_PER_DISPATCH = 16


def _argv(train_dir: str, extra=()) -> list[str]:
    return [
        "--model_def",
        MODEL_DEF,
        "--training_data",
        train_dir,
        "--minibatch_size",
        str(BATCH),
        "--records_per_task",
        str(RECORDS_PER_TASK),
        "--num_epochs",
        "1",
        "--steps_per_dispatch",
        str(STEPS_PER_DISPATCH),
        "--compute_dtype",
        "float32",
        *extra,
    ]


class _TaskMarks:
    """Thread-safe (tid -> first-report wall time) recorder; lockstep
    worlds report each task once per process, so duplicates are
    ignored."""

    def __init__(self):
        self._lock = threading.Lock()
        self.marks: dict[int, float] = {}

    def record(self, tid: int):
        with self._lock:
            self.marks.setdefault(tid, time.perf_counter())

    def rate(self, final_sync=None) -> float:
        """Records/sec over the steady window (first report excluded —
        it absorbs the jit compile)."""
        times = sorted(self.marks.values())
        if len(times) < 2:
            raise RuntimeError(
                f"need >= 2 task reports for a window, got {len(times)}"
            )
        if final_sync is not None:
            final_sync()
            end = time.perf_counter()
        else:
            end = times[-1]
        return (len(times) - 1) * RECORDS_PER_TASK / (end - times[0])


def _measure_local(train_dir: str) -> float:
    from elasticdl_tpu.trainer.local_executor import LocalExecutor
    from elasticdl_tpu.utils.args import parse_master_args

    marks = _TaskMarks()

    class _Timed(LocalExecutor):
        def _train_task(self, task, batches=None):
            n = super()._train_task(task, batches)
            marks.record(id(task))
            return n

    executor = _Timed(parse_master_args(_argv(train_dir)))
    executor.run()

    def sync():
        import jax

        int(jax.device_get(executor.trainer.state.step))

    return marks.rate(final_sync=sync)


def _measure_taskstream(train_dir: str) -> float:
    from elasticdl_tpu.data.recordio_reader import RecordIODataReader
    from elasticdl_tpu.master.servicer import MasterServicer
    from elasticdl_tpu.master.task_dispatcher import TaskDispatcher
    from elasticdl_tpu.utils.args import parse_worker_args
    from elasticdl_tpu.utils.constants import JobType
    from elasticdl_tpu.worker.worker import Worker

    reader = RecordIODataReader(data_dir=train_dir)
    task_d = TaskDispatcher(
        reader.create_shards(), records_per_task=RECORDS_PER_TASK
    )
    master = MasterServicer(BATCH, task_d)
    marks = _TaskMarks()
    orig = master.report_task_result

    def recording(request):
        marks.record(request.task_id)
        return orig(request)

    master.report_task_result = recording
    worker = Worker(
        parse_worker_args(
            _argv(train_dir, extra=("--worker_id", "0"))
            + ["--master_addr", "inprocess"]
        ),
        master,
        job_type=JobType.TRAINING_ONLY,
    )
    worker.run()
    if not task_d.finished():
        raise RuntimeError("task-stream job did not finish")

    def sync():
        import jax

        int(jax.device_get(worker.trainer.state.step))

    return marks.rate(final_sync=sync)


def _measure_lockstep(train_dir: str) -> float:
    from elasticdl_tpu.master.main import build_master
    from elasticdl_tpu.utils.args import parse_master_args
    from elasticdl_tpu.utils.constants import TaskType

    args = parse_master_args(
        _argv(train_dir)
        + [
            "--distribution_strategy",
            "AllreduceStrategy",
            "--num_workers",
            "2",
            "--jax_platform",
            "cpu",
            "--envs",
            "JAX_PLATFORMS=cpu,XLA_FLAGS= ",
            "--port",
            "0",
        ]
    )
    master = build_master(args)
    marks = _TaskMarks()
    orig = master.task_d.report

    def recording(tid, success, **kw):
        out = orig(tid, success, **kw)
        marks.record(tid)
        return out

    master.task_d.report = recording
    master.prepare()
    rc = master.run()
    if rc != 0 or not master.task_d.finished():
        raise RuntimeError(f"lockstep job failed rc={rc}")
    counters = master.task_d.counters(TaskType.TRAINING)
    if counters.total_records != NUM_RECORDS:
        raise RuntimeError(
            f"lockstep processed {counters.total_records} != {NUM_RECORDS}"
        )
    # workers sync before reporting their last task; no device handle here
    return marks.rate()


def main():
    from elasticdl_tpu.data.recordio_gen import synthetic

    with tempfile.TemporaryDirectory() as td:
        train_dir = synthetic.gen_frappe(
            os.path.join(td, "train"),
            num_records=NUM_RECORDS,
            num_shards=8,
            seed=0,
        )
        local = _measure_local(train_dir)
        taskstream = _measure_taskstream(train_dir)
        lockstep = _measure_lockstep(train_dir)
    print(
        json.dumps(
            {
                "local_records_per_sec": round(local),
                "taskstream_records_per_sec": round(taskstream),
                "taskstream_vs_local": round(taskstream / local, 3),
                "lockstep_records_per_sec": round(lockstep),
                "lockstep_e2e_vs_local": round(lockstep / local, 3),
                "world_size": 2,
                "records": NUM_RECORDS,
                "batch": BATCH,
                "host_cores": os.cpu_count(),
                "platform": jax.default_backend(),
            }
        )
    )


if __name__ == "__main__":
    main()
