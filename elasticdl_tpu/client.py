"""The ``elasticdl_tpu`` command-line client.

Reference: ``elasticdl/python/elasticdl/client.py:13-47`` — argparse
subcommands ``train``/``evaluate``/``predict``/``clean`` registered as the
``elasticdl`` console script (setup.py:27-29).  Same surface here:

    elasticdl_tpu train --model_def=mnist_functional_api... \
        --training_data=/data/mnist --num_epochs=2
"""

from __future__ import annotations

import argparse
import os
import sys

from elasticdl_tpu import api
from elasticdl_tpu.utils.args import parse_master_args
from elasticdl_tpu.utils.log_utils import default_logger as logger

COMMANDS = ("train", "evaluate", "predict", "clean")


def _parse_clean_args(argv):
    parser = argparse.ArgumentParser(prog="elasticdl_tpu clean")
    parser.add_argument("--docker_image_repository", default="")
    parser.add_argument("--all", action="store_true")
    return parser.parse_args(argv)


def run(argv) -> dict:
    """Run one command (``argv[0]`` in :data:`COMMANDS`) and return its
    result — what :func:`main` logs.  A Local run's result names the
    platform, device kind and device count it ran on."""
    command, rest = argv[0], argv[1:]
    if command == "clean":
        return api.clean(_parse_clean_args(rest))
    # hand JAX_PLATFORMS to the config BEFORE any backend initializes;
    # --jax_platform still overrides later via the same
    # configure_platform call.  Gated to the compute commands so
    # clean/--help stay jax-free.
    if os.environ.get("JAX_PLATFORMS"):
        from elasticdl_tpu.parallel.elastic import configure_platform

        configure_platform(os.environ["JAX_PLATFORMS"])
    return getattr(api, command)(parse_master_args(rest))


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if not argv or argv[0] in ("-h", "--help"):
        print(
            "usage: elasticdl_tpu {train,evaluate,predict,clean} [options]\n"
            "Run '<command> --help' for command options."
        )
        return 0 if argv else 2
    if argv[0] not in COMMANDS:
        logger.error("Unknown command %r; expected one of %s", argv[0], COMMANDS)
        return 2
    result = run(argv)
    if result:
        logger.info("%s result: %s", argv[0], result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
