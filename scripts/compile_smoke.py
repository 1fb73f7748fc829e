#!/usr/bin/env python3
"""Tier-1 compile-count smoke (wired into scripts/run_tier1.sh).

The compile-once guarantee of shape-canonical batching
(docs/designs/shape_canonicalization.md): a LocalExecutor run over
several tasks whose sizes produce DISTINCT ragged tail lengths must
execute the whole step stream with

1. backend compiles ONLY inside the first dispatch of each program kind
   (first single weighted step, first stacked scan) — every later
   dispatch, including every tail, compiles nothing ("zero mid-task
   recompiles");
2. at most 2 compile-bearing train dispatches total (the train-step
   program plus the one scan-k variant);
3. a positive process-wide ``compile_tracker`` total (the counter that
   feeds ``elasticdl_compile_total``) and at least one ``compile`` span
   in the trace log.

Geometry: 24 mnist records, records_per_task=9, minibatch=4 ->
tasks of 9, 9 and 6 records = batch streams (4,4,1), (4,4,1), (4,2) —
two distinct tail lengths (1 and 2) — with ``--steps_per_dispatch 2``
exercising both the stacked scan and the single-step path.
"""

from __future__ import annotations

import os
import sys
import tempfile

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS", "")

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def main() -> int:
    from elasticdl_tpu.data.recordio_gen import synthetic
    from elasticdl_tpu.parallel.distributed import SPMDTrainer
    from elasticdl_tpu.telemetry import compile_tracker
    from elasticdl_tpu.telemetry.tracing import (
        SPAN_COMPILE,
        read_spans,
    )
    from elasticdl_tpu.trainer.local_executor import LocalExecutor
    from elasticdl_tpu.utils.args import parse_master_args

    compile_tracker.install()

    with tempfile.TemporaryDirectory() as workdir:
        train = synthetic.gen_mnist(
            os.path.join(workdir, "train"),
            num_records=24,
            num_shards=1,
            seed=1,
        )
        telemetry_dir = os.path.join(workdir, "telemetry")
        args = parse_master_args(
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
                "2",
                "--compute_dtype",
                "float32",
                "--telemetry_dir",
                telemetry_dir,
                "--trace_sample_rate",
                "1.0",
            ]
        )
        executor = LocalExecutor(args)

        # observe compiles per train dispatch by wrapping the two step
        # entry points (the counter is process-wide; snapshotting around
        # each dispatch isolates the train programs from init/utility
        # compiles)
        dispatch_log: list[tuple[str, int]] = []
        orig_single = SPMDTrainer.train_step
        orig_stacked = SPMDTrainer.train_steps_stacked

        def single(self, *a, **kw):
            before = compile_tracker.compile_count()
            result = orig_single(self, *a, **kw)
            dispatch_log.append(
                ("single", compile_tracker.compile_count() - before)
            )
            return result

        def stacked(self, *a, **kw):
            before = compile_tracker.compile_count()
            result = orig_stacked(self, *a, **kw)
            dispatch_log.append(
                ("stacked", compile_tracker.compile_count() - before)
            )
            return result

        SPMDTrainer.train_step = single
        SPMDTrainer.train_steps_stacked = stacked
        try:
            executor.run()
        finally:
            SPMDTrainer.train_step = orig_single
            SPMDTrainer.train_steps_stacked = orig_stacked

        if executor.state is None or int(executor.state.step) != 8:
            print(
                f"compile_smoke: expected 8 steps, got "
                f"{executor.state and int(executor.state.step)}",
                file=sys.stderr,
            )
            return 1
        kinds = {kind for kind, _ in dispatch_log}
        if kinds != {"single", "stacked"}:
            print(
                f"compile_smoke: expected both dispatch kinds, got "
                f"{sorted(kinds)} ({dispatch_log})",
                file=sys.stderr,
            )
            return 1
        first_seen: set[str] = set()
        compiling_dispatches = 0
        for index, (kind, compiles) in enumerate(dispatch_log):
            is_first = kind not in first_seen
            first_seen.add(kind)
            if compiles:
                compiling_dispatches += 1
            if not is_first and compiles:
                print(
                    f"compile_smoke: RECOMPILE at dispatch {index} "
                    f"({kind}): {compiles} compiles — canonical shapes "
                    f"should reuse the program ({dispatch_log})",
                    file=sys.stderr,
                )
                return 1
        if compiling_dispatches > 2:
            print(
                f"compile_smoke: {compiling_dispatches} compile-bearing "
                f"train dispatches (> 2): {dispatch_log}",
                file=sys.stderr,
            )
            return 1
        if compile_tracker.compile_count() <= 0:
            print("compile_smoke: counter never incremented", file=sys.stderr)
            return 1
        spans = read_spans(os.path.join(telemetry_dir, "spans.jsonl"))
        compile_spans = [s for s in spans if s.get("span") == SPAN_COMPILE]
        if not compile_spans:
            print("compile_smoke: no compile spans recorded", file=sys.stderr)
            return 1
    print(
        f"compile_smoke: OK ({len(dispatch_log)} train dispatches, "
        f"{compiling_dispatches} compiled; process total "
        f"{compile_tracker.compile_count()} compiles, "
        f"{len(compile_spans)} compile spans)"
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
