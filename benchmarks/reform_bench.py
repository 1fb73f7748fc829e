"""Elastic re-formation drive (BASELINE.md config 5).

An elastic-recovery drive on the host CPU: it shows that a killed worker's
world re-forms and loses no record; its seconds are a CPU's, not a ledger
number (``perf/run.py`` is the benchmark).  It stays until ROADMAP B2's
cell replaces it.

A thin consumer of the chaos harness (``elasticdl_tpu.chaos.harness``):
a real 2-process lockstep job on the host CPU backend runs under the
``preempt_one_worker`` fault plan — one worker SIGKILLs itself at a
deterministic training step — and the harness measures the mesh
re-formation the master performs plus checks the elastic invariants
(reference behavior: pod kill -> task re-queue -> relaunch,
``elasticdl/python/master/k8s_instance_manager.py:241-275``).

Prints ONE JSON line (schema unchanged since r3):
  {"reform_latency_secs": R, "kill_to_step_secs": T,
   "detect_secs": D, "records_ok": true}

- ``reform_latency_secs`` — detection -> first step-task pull of the new
  world (the re-form cost the framework controls).
- ``kill_to_step_secs``  — SIGKILL -> first post-re-form step pull (adds
  the heartbeat detection window, like the reference's k8s watch delay).

Run: ``python benchmarks/reform_bench.py``.  It pins itself to
``JAX_PLATFORMS=cpu``: the kill job never touches a chip.
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

HEARTBEAT_TIMEOUT_SECS = 3


def measure(
    workdir: str,
    num_records: int = 512,
    num_epochs: int = 2,
    evaluate: bool = False,
) -> dict:
    """Run the kill-and-reform lockstep job through the chaos harness;
    returns the reform metrics (plus ``accuracy`` when ``evaluate``).

    Parameterized so the accuracy-under-preemption gate
    (``preemption_accuracy_bench.py``) can reuse the exact same
    kill/re-form machinery on a to-accuracy training budget."""
    from elasticdl_tpu.chaos.harness import ChaosJobConfig, run_chaos_job
    from elasticdl_tpu.chaos.plan import named_plan

    report = run_chaos_job(
        ChaosJobConfig(
            plan=named_plan("preempt_one_worker", num_workers=2),
            workdir=workdir,
            num_records=num_records,
            num_epochs=num_epochs,
            heartbeat_timeout_secs=HEARTBEAT_TIMEOUT_SECS,
            evaluate=evaluate,
        )
    )
    out = {
        "reform_latency_secs": report["reform_latency_secs"],
        "detect_secs": report["detect_secs"],
        "kill_to_step_secs": report["kill_to_step_secs"],
        "records_ok": report["records_ok"],
        "heartbeat_timeout_secs": HEARTBEAT_TIMEOUT_SECS,
        # >0 proves the re-formed world came from the hot-standby pool
        # (the cold-start path would dominate reform_latency_secs)
        "standby_activated": report["standby_activated"],
    }
    if not out["records_ok"]:
        out["rc"] = [report["rc"]] if report["rc"] is not None else []
        out["total_records"] = report.get("total_records")
    if evaluate:
        out["accuracy"] = report.get("accuracy", 0.0)
    return out


def main():
    with tempfile.TemporaryDirectory() as workdir:
        print(json.dumps(measure(workdir)))


if __name__ == "__main__":
    main()
