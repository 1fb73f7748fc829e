"""Accuracy-under-preemption gate (BASELINE.md config 5, conjunctive).

An elastic-recovery drive on the host CPU: its output is a pass/fail and a
count, not a ledger number (``perf/run.py`` is the benchmark); it stays
until ROADMAP B2's cell replaces it.

The reference's elastic acceptance is not "survives a kill" OR "reaches
accuracy" — it is both at once: a worker preempted mid-run must not cost
records (silently lost gradients) or double-train them (double-consumed
tasks), and the finished job must still clear the accuracy bar.  This
gate is a thin consumer of the chaos harness: ONE
``preempt_one_worker`` chaos job trains synthetic mnist to the accuracy
budget, the injected kill re-forms the world from hot standbys, the
harness asserts exactly-once record accounting, and the final
re-shardable checkpoint is restored into a single-process evaluator and
scored on a held-out split.

Prints ONE JSON line (schema unchanged since r3):
  {"accuracy": A, "records_ok": true, "reform_latency_secs": R,
   "threshold": 0.8, "pass": true}

Run: ``python benchmarks/preemption_accuracy_bench.py``.  It pins itself
to ``JAX_PLATFORMS=cpu``: the kill job never touches a chip.
"""

from __future__ import annotations

import json
import os
import sys
import tempfile

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS", "")

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

THRESHOLD = 0.8
# 1024 records x 2 epochs = 64 steps at batch 32: comfortably past the
# bar for the learnable synthetic mnist (0.94 observed at 32 steps in
# tests/test_trainer_local.py) while keeping the 2-process CPU job short
NUM_RECORDS = 1024
NUM_EPOCHS = 2


def measure(workdir: str) -> dict:
    from benchmarks.reform_bench import measure as reform_measure

    reform = reform_measure(
        workdir,
        num_records=NUM_RECORDS,
        num_epochs=NUM_EPOCHS,
        evaluate=True,
    )
    acc = float(reform.get("accuracy", 0.0))
    return {
        "accuracy": round(acc, 4),
        "records_ok": bool(reform["records_ok"]),
        "reform_latency_secs": reform["reform_latency_secs"],
        "standby_activated": reform["standby_activated"],
        "threshold": THRESHOLD,
        "pass": bool(reform["records_ok"]) and acc >= THRESHOLD,
    }


def main():
    with tempfile.TemporaryDirectory() as workdir:
        print(json.dumps(measure(workdir)))


if __name__ == "__main__":
    main()
