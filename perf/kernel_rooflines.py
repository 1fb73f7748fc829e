"""A roofline share for each of the three flash kernels of
``ops/attention.py``, told apart on the op line by the name its
``pallas_call`` gives the compiled custom-call (``flash_fwd.3``)."""

from __future__ import annotations

from perf import trace_reduce

# of the six analytic matmuls of causal attention forward and backward
# (``flops_per_step_chip["causal_attention"]`` = 6*L*B*T^2*d a step), two are
# the forward's (QK^T, PV), two dQ's (dP = dO V^T, dQ = dS K), two dK/dV's
# (dV = P^T dO, dK = dS^T Q); the score matrix each backward kernel
# recomputes is not counted, as in ``flash_roofline.lm``
KERNEL_SHARE_OF_ATTENTION = 1.0 / 3.0


def kernel_seconds(run, kernel: str) -> float | None:
    if run["trace"] is None:
        return None
    return (
        trace_reduce.matching_seconds(run["trace"], rf"^{kernel}\b") or None
    )


def flash_kernel_roofline(run, kernel: str) -> float | None:
    """A third of the analytic causal-attention FLOPs of the traced steps
    over ``kernel``'s self time and the bf16 peak.  Compute bounds it, as it
    does the three together."""
    seconds = kernel_seconds(run, kernel)
    if seconds is None:
        return None
    attention = run["flops_per_step_chip"].get("causal_attention")
    if not attention or not run["traced_steps"]:
        return None
    flops = KERNEL_SHARE_OF_ATTENTION * attention * run["traced_steps"]
    return 100.0 * flops / seconds / run["peaks"]["bf16_flops_per_s"]
