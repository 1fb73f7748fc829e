"""Job-submission API: the ``elasticdl_tpu`` CLI's backend.

Reference: ``elasticdl/python/elasticdl/api.py`` — ``train``/``evaluate``/
``predict`` either run a LocalExecutor in-process (LOCAL strategy,
api.py:20-22) or build+push a docker image and create a master pod on
Kubernetes (api.py:24-52,138-178).

The TPU build maps the strategies as:

- ``Local``: in-process :class:`LocalExecutor` — one jit loop on the local
  chip(s), no control plane.
- ``AllreduceStrategy`` / ``ParameterServerStrategy``: a master control
  plane in this process with SPMD workers as local subprocesses (the
  single-host analogue of the reference's pod cluster; each worker runs
  the same code a multi-host deployment runs per host).
- Kubernetes submission (``--namespace`` + kubernetes package installed):
  delegates to the image builder + k8s client (aux subsystem), creating a
  master pod that runs ``elasticdl_tpu.master.main``.
"""

from __future__ import annotations

from elasticdl_tpu.utils.constants import DistributionStrategy
from elasticdl_tpu.utils.log_utils import default_logger as logger


def _run_local(args) -> dict:
    from elasticdl_tpu.parallel.elastic import (
        configure_compilation_cache,
        describe_devices,
    )
    from elasticdl_tpu.trainer.local_executor import LocalExecutor

    configure_compilation_cache(getattr(args, "compilation_cache_dir", ""))
    executor = LocalExecutor(args)
    results = executor.run()
    # the final metrics, plus what ran and WHERE: the logged result must
    # prove which device a run used (a CPU run exits 0 just the same)
    return {
        **results,
        "steps": executor.trainer.step if executor.trainer else 0,
        "device": describe_devices(executor.mesh.devices.flat),
    }


def _run_distributed(args) -> dict:
    from elasticdl_tpu.master.main import run_job
    from elasticdl_tpu.utils.args import (
        build_arguments_from_parsed_result,
        parse_master_args,
    )

    # the argv round trip normalizes exactly like a master pod's command
    # line; the master itself never initializes a backend — its workers'
    # log lines and world_join spans name their devices
    rc, summary = run_job(
        parse_master_args(build_arguments_from_parsed_result(args))
    )
    if rc != 0:
        raise RuntimeError(f"master exited with {rc}")
    return {"exit_code": rc, **summary}


def _submit_k8s(args) -> dict:
    if getattr(args, "yaml", ""):
        # a manifest dump never touches the cluster: no SDK needed
        from elasticdl_tpu.k8s.submit import submit_master_pod

        return submit_master_pod(args)
    try:
        import kubernetes  # noqa: F401
    except ImportError as e:
        raise RuntimeError(
            "Kubernetes submission requires the 'kubernetes' package; "
            "use --distribution_strategy=Local or AllreduceStrategy for "
            "local execution"
        ) from e
    from elasticdl_tpu.k8s.submit import submit_master_pod

    return submit_master_pod(args)


def _dispatch(args) -> dict:
    strategy = getattr(args, "distribution_strategy", "") or (
        DistributionStrategy.LOCAL
    )
    if strategy == DistributionStrategy.LOCAL:
        return _run_local(args)
    if (
        getattr(args, "docker_image", "")
        or getattr(args, "docker_image_repository", "")
        or getattr(args, "yaml", "")
    ):
        # a prebuilt image OR a repository to build+push into means a
        # cluster submission (reference api.py:24-33); otherwise the job
        # runs as local subprocesses under an in-process master
        return _submit_k8s(args)
    return _run_distributed(args)


def train(args) -> dict:
    """Reference api.py:17-52."""
    if not getattr(args, "training_data", ""):
        raise ValueError("train requires --training_data")
    return _dispatch(args)


def evaluate(args) -> dict:
    """Reference api.py:55-84: evaluation-only job over a checkpoint."""
    if not getattr(args, "validation_data", ""):
        raise ValueError("evaluate requires --validation_data")
    args.training_data = ""
    return _dispatch(args)


def predict(args) -> dict:
    """Reference api.py:87-135.  With ``--serving_addr`` the batch
    predict becomes a client of a running serving endpoint
    (elasticdl_tpu/serving): shards decode locally, batches predict
    remotely; unset keeps the offline in-process path unchanged."""
    if not getattr(args, "prediction_data", ""):
        raise ValueError("predict requires --prediction_data")
    args.training_data = ""
    args.validation_data = ""
    if getattr(args, "serving_addr", None):
        from elasticdl_tpu.serving.predict_client import run_remote_predict

        return run_remote_predict(args)
    return _dispatch(args)


def clean(args) -> dict:
    """Reference clean: remove job docker images (image_builder.py:82-128);
    gated on the docker SDK, with a clear message when absent."""
    from elasticdl_tpu.image_builder import remove_images

    repository = getattr(args, "docker_image_repository", "") or ""
    try:
        removed = remove_images(docker_image_repository=repository)
    except RuntimeError as ex:
        logger.warning("%s; nothing to clean (local runs leave no images)", ex)
        removed = []
    return {"removed": removed}
