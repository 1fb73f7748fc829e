"""What the device holds while the step runs, for the five readers of
``peak_hbm_gb``'s layers under ``layer_metrics/`` (``state_hbm_gb``,
``step_temp_hbm_gb``, ``residuals_at_peak_hbm_gb``,
``head_loss_at_peak_hbm_gb``, ``hbm_unexplained_gb``).

The program hands out the byte side of its trainer
(``elasticdl_tpu/telemetry/memory.py::read_step_memory``): the state on the
fullest device, XLA's own sizes of each compiled train program, what is
alive at the largest program's peak by the model's scopes
(``telemetry/op_scopes.py::live_bytes``, held to XLA's peak) and the
allocator's figures.  It is read here after the window, once a ``run``, and
kept in it; every figure is in GB of 1e9 bytes, as ``peak_hbm_gb`` is.

``state_hbm_gb + what the step holds over its arguments at XLA's own peak +
the step's code + the other arrays alive + hbm_unexplained_gb`` is the
allocator's ``peak_bytes_in_use + peak_bytes_reserved``, the run's
``peak_hbm_gb``, by construction: :func:`account` has every term.  The
step's term is XLA's ``peak_memory_in_bytes - argument_size_in_bytes`` and
not ``step_temp_hbm_gb``: ``temp_size_in_bytes`` counts what XLA put in the
chip's other memory spaces too, 0.01-0.97 GB more than the chip reserves
(PERF.md, PR 55), and a remainder taken from it is negative by construction.
Taken from XLA's peak the remainder is the allocator's packing of the step's
one allocation, and is not below nought.

A reader returns None where the program has no such function (the parent of
the PR that added it: these files are laid over its checkout too), where the
allocator gives no figures, and, for the two that read the split at the
peak, where the reading is off XLA's figure by more than a tenth."""

from __future__ import annotations

import json
import os
import time

from perf.scope_shares import HEAD_AND_LOSS

_KEY = "_step_memory"
# a directory to leave the whole reading in, as ``<cell>.step_memory.json``
# (``--keep-trace``'s counterpart: the line has five numbers of it)
KEEP_ENV = "PERF_KEEP_STEP_MEMORY"
GB = 1e9


def reading(run) -> dict | None:
    """``read_step_memory()`` of the trainer the window ran, taken once per
    ``run``."""
    if _KEY not in run:
        run[_KEY] = None
        seconds = 0.0
        try:
            from elasticdl_tpu.telemetry import memory
        except ImportError:
            memory = None
        read = getattr(memory, "read_step_memory", None)
        if read is not None:
            started = time.perf_counter()
            run[_KEY] = read()
            seconds = time.perf_counter() - started
        keep = os.environ.get(KEEP_ENV)
        if keep and run[_KEY] is not None:
            os.makedirs(keep, exist_ok=True)
            name = f"{run['cell'].name}.step_memory.json"
            with open(os.path.join(keep, name), "w") as f:
                json.dump(
                    {**run[_KEY], "account": account(run), "read_s": seconds}, f
                )
    return run[_KEY]


def _largest(run) -> dict | None:
    """The train program with the most temporaries among those the window
    ran (``read_step_memory`` puts it first)."""
    found = reading(run)
    if found is None or not found["programs"] or not found["programs"][0].get("xla"):
        return None
    return found["programs"][0]


def account(run) -> dict | None:
    """The allocator's peak of the fullest device, term by term, in bytes:
    ``peak`` is ``state + other_arrays + step + code + unexplained``."""
    found, program = reading(run), _largest(run)
    if program is None or not found["allocator"]:
        return None
    xla, allocator = program["xla"], found["allocator"]
    terms = {
        "state": found["state"]["total"],
        # batches placed and not yet retired, the last step's metrics
        "other_arrays": found["other_arrays"],
        # what is alive at XLA's own peak beside the arguments: temporaries
        # in HBM and what the step writes in place of no argument
        "step": xla["peak"] - xla["argument"],
        "code": xla["generated_code"],
    }
    peak = allocator["peak_bytes_in_use"] + allocator["peak_bytes_reserved"]
    return {
        **terms, "peak": peak, "unexplained": peak - sum(terms.values()),
        # beside the sum: XLA's ``temp``, which counts the chip's other
        # memory spaces too and is what ``step_temp_hbm_gb`` reads, the
        # step's unaliased outputs, and what the allocator reserved
        "temp": xla["temp"],
        "outputs": xla["output"] - xla["alias"],
        "reserved": allocator["peak_bytes_reserved"],
    }


def _at_peak(run, chosen) -> float | None:
    """GB alive at the largest program's peak in the rows ``chosen(owner,
    phase, role)`` picks."""
    program = _largest(run)
    if program is None or program.get("live") is None:
        return None
    return sum(
        size for owner, phase, role, size in program["live"]
        if chosen(owner, phase, role)
    ) / GB


def state_hbm_gb(run) -> float | None:
    """Parameters, optimizer state and model buffers on the fullest chip."""
    found = reading(run)
    return None if found is None else found["state"]["total"] / GB


def step_temp_hbm_gb(run) -> float | None:
    """XLA's ``temp_size_in_bytes`` of the largest train program."""
    program = _largest(run)
    return None if program is None else program["xla"]["temp"] / GB


def residuals_at_peak_hbm_gb(run) -> float | None:
    """What the forward pass left for the passes after it, alive at the
    step's peak: the bytes recomputation trades against time."""
    return _at_peak(run, lambda owner, phase, role: role == "residual")


def head_loss_at_peak_hbm_gb(run) -> float | None:
    """The head's and the loss's own buffers alive at the step's peak (the
    logits and their gradient; the parts ``head_loss_share.scope_lm``
    times), the parameters apart."""
    return _at_peak(
        run,
        lambda owner, phase, role: role != "argument"
        and owner.split("/")[-1] in HEAD_AND_LOSS,
    )


def hbm_unexplained_gb(run) -> float | None:
    """The allocator's peak less everything that has a name, the step by
    XLA's ``peak_memory_in_bytes - argument_size_in_bytes`` (the module's
    text says why not by ``temp``): the allocator's packing."""
    found = account(run)
    return None if found is None else found["unexplained"] / GB
