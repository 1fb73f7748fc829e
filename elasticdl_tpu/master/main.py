"""Master process entry (reference elasticdl/python/master/main.py:7-11).

``python -m elasticdl_tpu.master.main --model_def=... --training_data=...``
starts the control plane and, when ``--num_workers > 0``, spawns local
worker subprocesses wired back over gRPC.
"""

from __future__ import annotations

import sys

from elasticdl_tpu.master.master import LocalInstanceManager, Master
from elasticdl_tpu.utils.args import build_worker_arguments, parse_master_args
from elasticdl_tpu.utils.log_utils import default_logger as logger


def build_master(args) -> Master:
    """Assemble a Master with the configured instance manager backend
    (exposed so tests and embedding callers can drive the lifecycle)."""

    def build_argv(worker_id, master_addr, **world_kwargs):
        argv = [
            "elasticdl_tpu.worker.main",
            *build_worker_arguments(args, worker_id, master_addr),
        ]
        # lockstep world coordinates (multi-process SPMD): the instance
        # manager assigns these per process / per generation
        for key, value in world_kwargs.items():
            argv.extend([f"--{key}", str(value)])
        return argv

    def im_factory(master):
        num_workers = getattr(args, "num_workers", 0) or 0
        backend = getattr(args, "instance_backend", "local") or "local"
        if num_workers <= 0 or backend == "none":
            return None
        lockstep = num_workers > 1
        max_reforms = getattr(args, "relaunch_on_worker_failure", 3)
        envs = dict(getattr(args, "envs_dict", {}) or {})
        telemetry_dir = getattr(args, "telemetry_dir", "") or ""
        if telemetry_dir:
            # workers append step samples to the shared event log; the
            # dir travels by env (like the chaos plan), not by argv
            from elasticdl_tpu.telemetry.tracing import (
                TRACE_SAMPLE_RATE_ENV,
            )
            from elasticdl_tpu.telemetry.worker_hooks import (
                TELEMETRY_DIR_ENV,
            )

            envs.setdefault(TELEMETRY_DIR_ENV, telemetry_dir)
            sample_rate = getattr(args, "trace_sample_rate", None)
            if sample_rate is not None:
                envs.setdefault(TRACE_SAMPLE_RATE_ENV, str(sample_rate))
        if getattr(args, "step_anatomy", None):
            # per-dispatch phase anatomy: enabled by env like the
            # telemetry dir (never argv — worker command lines stay
            # byte-identical when the flag is off)
            from elasticdl_tpu.telemetry.anatomy import STEP_ANATOMY_ENV

            envs.setdefault(STEP_ANATOMY_ENV, "1")
        if getattr(args, "slo_config", None):
            # the SLO watchdog evaluates in the master only, but the
            # config follows the env-forwarding contract (never argv)
            # so worker command lines stay byte-identical when off
            from elasticdl_tpu.telemetry.slo import SLO_CONFIG_ENV

            envs.setdefault(SLO_CONFIG_ENV, str(args.slo_config))
        if getattr(args, "device_prefetch", None):
            # device-path pipelining: same env-forwarding contract —
            # and because it changes the compiled step program (batch
            # donation), the env keeps the whole world uniform
            from elasticdl_tpu.trainer.device_pipeline import (
                DEVICE_PREFETCH_ENV,
            )

            envs.setdefault(DEVICE_PREFETCH_ENV, "1")
        if getattr(args, "boundary_fusion", None):
            # cross-task staging rides the same env contract (and the
            # same uniformity argument — the whole world fuses or none)
            from elasticdl_tpu.trainer.device_pipeline import (
                BOUNDARY_FUSION_ENV,
            )

            envs.setdefault(BOUNDARY_FUSION_ENV, "1")
        pipeline_depth = getattr(args, "pipeline_depth", None)
        if pipeline_depth is not None:
            # the tunable retire window / staging bound, env-forwarded
            # so worker argv stays byte-identical when unset
            from elasticdl_tpu.trainer.device_pipeline import (
                PIPELINE_DEPTH_ENV,
            )

            envs.setdefault(PIPELINE_DEPTH_ENV, str(pipeline_depth))
        journal_dir = getattr(args, "master_journal_dir", None) or ""
        retry_secs = getattr(args, "rpc_retry_secs", None)
        if journal_dir:
            # master HA: workers learn where to re-resolve the
            # control-plane address after a master restart — by env,
            # like the telemetry dir (never argv)
            from elasticdl_tpu.master.journal import (
                MASTER_ADDR_FILE_ENV,
                addr_file_path,
            )

            envs.setdefault(
                MASTER_ADDR_FILE_ENV, addr_file_path(journal_dir)
            )
        if journal_dir or retry_secs is not None:
            # the RPC retry budget that carries workers across an
            # outage: implied by HA (journal_dir), or requested alone by
            # --rpc_retry_secs — a gray network (transient UNAVAILABLE,
            # deadline expiries under --rpc_deadline_secs) deserves the
            # backoff loop even on a journal-less master
            from elasticdl_tpu.rpc.retry import (
                DEFAULT_RETRY_SECS,
                RETRY_SECS_ENV,
            )

            envs.setdefault(
                RETRY_SECS_ENV,
                str(
                    retry_secs
                    if retry_secs is not None
                    else DEFAULT_RETRY_SECS
                ),
            )
        deadline_secs = getattr(args, "rpc_deadline_secs", None)
        if deadline_secs is not None:
            # per-method deadlines (rpc/deadline.py): a blackholed
            # master link degrades to DEADLINE_EXCEEDED instead of
            # hanging the worker forever.  Env-forwarded like the retry
            # budget so worker argv stays byte-identical when unset
            from elasticdl_tpu.rpc.deadline import DEADLINE_SECS_ENV

            envs.setdefault(DEADLINE_SECS_ENV, str(deadline_secs))
        if backend == "k8s":
            import os

            from elasticdl_tpu.k8s.instance_manager import K8sInstanceManager

            return K8sInstanceManager(
                num_workers=num_workers,
                build_argv=build_argv,
                # lazy: the control-plane port binds in Master.prepare()
                master_addr=lambda: (
                    f"{os.environ.get('MY_POD_IP', 'localhost')}:"
                    f"{master.port}"
                ),
                image_name=getattr(args, "docker_image", "") or "",
                namespace=args.namespace,
                job_name=args.job_name,
                envs=envs,
                lockstep=lockstep,
                max_reforms=max_reforms,
                worker_resource_request=getattr(
                    args, "worker_resource_request", "cpu=1,memory=4096Mi"
                ),
                worker_resource_limit=getattr(
                    args, "worker_resource_limit", ""
                )
                or "",
                worker_pod_priority=getattr(args, "worker_pod_priority", "")
                or "",
                volume=getattr(args, "volume", "") or "",
                image_pull_policy=getattr(
                    args, "image_pull_policy", "Always"
                ),
                on_worker_failure=master.servicer.mark_worker_dead,
                standby_workers=getattr(args, "standby_workers", -1),
                # standby pods poll this mailbox for world assignments
                post_assignment=master.servicer.post_world_assignment,
                cluster_spec=getattr(args, "cluster_spec", "") or "",
            )
        return LocalInstanceManager(
            master,
            num_workers,
            build_argv,
            envs=envs,
            # N>1 workers = one jax.distributed world training ONE model
            lockstep=lockstep,
            max_reforms=max_reforms,
            standby_workers=getattr(args, "standby_workers", -1),
            # slice-granular elasticity: split the fleet into TPU slices
            # (forced layout on sliceless backends); None = 1
            num_slices=getattr(args, "num_slices", None) or 1,
        )

    return Master(args, instance_manager_factory=im_factory)


def run_job(args) -> tuple[int, dict]:
    """Run one job to completion; returns ``(exit code, job summary)``."""
    master = build_master(args)
    master.prepare()
    logger.info(
        "Master ready on port %d (job type %s)",
        master.port,
        master.job_type.value,
    )
    rc = master.run()
    summary = master.job_summary()
    logger.info("Job summary: %s", summary)
    return rc, summary


def main(argv=None) -> int:
    rc, _summary = run_job(parse_master_args(argv))
    return rc


if __name__ == "__main__":
    sys.exit(main())
