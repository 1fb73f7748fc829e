"""Arithmetic shared by the per-layer readers under ``layer_metrics/``.

A reader is ``read(run) -> float | None``.  ``run`` is the dict
``perf/run.py`` builds: ``host`` (the harness's own timers on the
dispatching thread, for the untraced phase of the traced process), ``trace``
(``trace_reduce.reduce``'s result, or None), ``traced_steps``,
``flops_per_step_chip`` (the cell's ``flop_functions`` module per record
times the batch per chip: ``"train"`` and named parts) and ``peaks``.
A reader that finds nothing to read returns None and the harness leaves
the metric out of the line."""

from __future__ import annotations

from perf import trace_reduce

# the three Pallas flash kernels of ops/attention.py (forward, dQ, dK/dV),
# by the name each ``pallas_call`` gives its compiled custom-call on the op
# line (``flash_fwd.3``; ``kernel_rooflines.py`` tells them apart the same
# way): another model's other Mosaic kernel is not attention
FLASH_KERNELS = r"^flash_(fwd|dq|dkv)\b"


def input_wait_share(run) -> float | None:
    """Share of the untraced intervals' wall time the dispatching thread
    spent inside ``next()`` of the batch iterator handed to
    ``_train_task``: the harness's own timer, no recorder, no block."""
    host = run["host"]
    if not host["wall_s"] or not host["input_wait_s"]:
        return None  # no batch iterator in this traffic mode: nothing to read
    return 100.0 * host["input_wait_s"] / host["wall_s"]


def dispatch_ms(run) -> float | None:
    """Host time per dispatch between two ``next()`` calls: the step hooks,
    pad and mask, ``place_batch`` and the enqueue of the jitted step."""
    host = run["host"]
    if not host["batches"]:
        return None
    return 1e3 * host["dispatch_s"] / host["batches"]


def step_device_ms(run) -> float | None:
    trace = run["trace"]
    if trace is None or not run["traced_steps"]:
        return None
    return 1e3 * trace["busy_s"] / run["traced_steps"]


def step_mfu(run) -> float | None:
    """Model FLOPs of a step on one chip over its device-busy time and the
    table's bf16 peak; recomputation is not counted."""
    busy_ms = step_device_ms(run)
    if not busy_ms:
        return None
    peak = run["peaks"]["bf16_flops_per_s"]
    return 100.0 * run["flops_per_step_chip"]["train"] / (busy_ms / 1e3) / peak


def flash_seconds(run) -> float | None:
    if run["trace"] is None:
        return None
    return trace_reduce.matching_seconds(run["trace"], FLASH_KERNELS) or None


def flash_time_share(run) -> float | None:
    seconds = flash_seconds(run)
    if seconds is None or not run["trace"]["busy_s"]:
        return None
    return 100.0 * seconds / run["trace"]["busy_s"]


def flash_roofline(run) -> float | None:
    """Analytic causal-attention FLOPs of the traced steps (forward and
    backward, 6*L*B*T^2*d a step; the backward's recomputation of the score
    matrix adds about a sixth and is not counted) over the three kernels'
    summed time and the bf16 peak.  Compute bounds it: at d_head 64 the
    kernels do ~T/2 FLOPs per byte of Q, K, V they stream."""
    seconds = flash_seconds(run)
    attention = run["flops_per_step_chip"].get("causal_attention")
    if seconds is None or not attention:
        return None
    flops = attention * run["traced_steps"]
    return 100.0 * flops / seconds / run["peaks"]["bf16_flops_per_s"]


def collective_exposed_share(run) -> float | None:
    trace = run["trace"]
    if trace is None or trace["devices"] < 2 or not trace["window_s"]:
        return None
    return 100.0 * trace["collective_exposed_s"] / trace["window_s"]
