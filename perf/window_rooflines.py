"""Operations, least bytes and roofline shares of the three window-attention
kernels (``ops/attention.py``'s flash kernels under a window: ``swa_fwd``,
``swa_dq``, ``swa_dkv``), told apart on the op line by the name each
``pallas_call`` gives its compiled custom-call (``swa_fwd.3``), as
``kernel_rooflines.py`` tells the dense flash kernels apart; and the shares
of device-busy time of a stack that mixes window and full layers.

A kernel's least time is the larger of its operations over the bf16 peak and
its least bytes over the HBM peak; its share is the least time of a traced
step's calls over the kernel's self time.  The operations are the pairs
INSIDE the window (``flop_functions/afmoe.py::window_pairs``), two products a
kernel: a kernel that visits blocks it should skip reads a lower share, and
none can pass 100%.  Every layer is recomputed in the backward pass, so
``swa_fwd`` runs twice a layer and step under the one name; it is counted
once, as the dense kernels' readers count ``flash_fwd``.  All three come out
bound by compute (``least_seconds`` says so per kernel)."""

from __future__ import annotations

from perf import trace_reduce
from perf.flop_functions.afmoe import window_pairs
from perf.kernel_rooflines import kernel_seconds

KERNELS = ("swa_fwd", "swa_dq", "swa_dkv")
WINDOW_KERNELS = r"^swa_(fwd|dq|dkv)\b"
# the window layers' three and the full layers' three: a mixed stack's
# attention kernels
ATTENTION_KERNELS = r"^(flash|swa)_(fwd|dq|dkv)\b"


def kernel_flops(kernel: str, seq_len: int, spec: dict) -> float:
    """FLOPs a layer and step of one sequence of ``seq_len``: scores and
    values forward; dP and dQ; dV and dK."""
    pairs = window_pairs(seq_len, spec["window"])
    return 2.0 * pairs * spec["heads"] * 2 * spec["head_dim"]


def kernel_bytes(
    kernel: str, seq_len: int, spec: dict, activation_bytes: int = 2
) -> float:
    """Bytes a layer and step must move at least once: the operands and
    results as the layer holds them."""
    q = seq_len * spec["heads"] * spec["head_dim"] * activation_bytes
    kv = seq_len * spec["kv_heads"] * spec["head_dim"] * activation_bytes
    rows = seq_len * spec["heads"] * 4  # a float32 a head and query
    return {
        "swa_fwd": 2 * q + 2 * kv + rows,
        "swa_dq": 3 * q + 2 * kv + 2 * rows,
        # the gradients leave a query head each (summed over a group outside)
        "swa_dkv": 4 * q + 2 * kv + 2 * rows,
    }[kernel]


def least_seconds(kernel: str, seq_len: int, spec: dict, peaks: dict) -> dict:
    compute = kernel_flops(kernel, seq_len, spec) / peaks["bf16_flops_per_s"]
    memory = kernel_bytes(kernel, seq_len, spec) / peaks["hbm_bytes_per_s"]
    return {
        "compute_s": compute, "memory_s": memory,
        "least_s": max(compute, memory), "compute_bound": compute >= memory,
    }


def kernel_roofline(run, kernel: str) -> float | None:
    seconds = kernel_seconds(run, kernel)
    if seconds is None or not run["traced_steps"]:
        return None
    spec = run["cell"].config["flops"]
    if not spec.get("window_layers"):
        return None
    traffic = run["cell"].traffic
    per_step = traffic["batch_per_chip"] * spec["window_layers"]
    least = least_seconds(
        kernel, traffic["records"]["seq_len"], spec, run["peaks"]
    )["least_s"]
    return 100.0 * run["traced_steps"] * per_step * least / seconds


def _time_share(run, kernels: str) -> float | None:
    """Nothing where the program runs no window kernel (the parent of the
    PR that brought them, or a stack of full layers alone)."""
    trace = run.get("trace")
    if trace is None or not trace.get("busy_s"):
        return None
    if not trace_reduce.matching_seconds(trace, WINDOW_KERNELS):
        return None
    return 100.0 * trace_reduce.matching_seconds(trace, kernels) / trace["busy_s"]


def window_attention_time_share(run) -> float | None:
    """The three ``swa_*`` kernels, of device-busy time."""
    return _time_share(run, WINDOW_KERNELS)


def attention_kernels_time_share(run) -> float | None:
    """The six kernels of a mixed stack (``flash_*`` of its full layers,
    ``swa_*`` of its window layers), of device-busy time: how much of the
    step the mixed attention is."""
    return _time_share(run, ATTENTION_KERNELS)
