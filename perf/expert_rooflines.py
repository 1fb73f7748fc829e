"""Operations, bytes and roofline shares of the three grouped-matmul
kernels of ``ops/grouped_matmul.py``, told apart on the op line by the name
each ``pallas_call`` gives its compiled custom-call (``expert_gmm_fwd.3``),
as ``kernel_rooflines.py`` tells the flash kernels apart.

A token's ``k`` experts cost ``k * 3 * d * f`` multiply-accumulates forward
(gate, up, down).  The backward costs that twice: once for the input
gradients (``expert_gmm_dx``) and once for the weight gradients
(``expert_gmm_dw``); so each kernel computes a third of
``flops_per_step_chip["experts"]`` (``flop_functions/olmoe.py``: 6 * k * 3 *
d * f a token).  Rows a group is padded with are computed and not counted:
padding lowers the share."""

from __future__ import annotations

from perf.kernel_rooflines import kernel_seconds

# the three together, as ``kernel_seconds`` matches a kernel's name
EXPERT_KERNELS = "expert_gmm_(fwd|dx|dw)"
KERNEL_SHARE_OF_EXPERTS = 1.0 / 3.0


def kernel_flops(pairs: int, d_model: int, width: int) -> float:
    """FLOPs of one of the three kernels over ``pairs`` (token, slot)
    pairs: three ``d x f`` matmuls a pair at 2 FLOPs a MAC."""
    return 2.0 * 3 * pairs * d_model * width


def kernel_bytes(
    pairs: int, experts: int, d_model: int, width: int,
    activation_bytes: int = 2, weight_bytes: int = 2,
) -> dict:
    """Bytes each kernel must move at least once, padding not counted: the
    row buffers it reads and writes, and every expert's three matrices once
    (``dw`` writes them in float32)."""
    rows_d = pairs * d_model * activation_bytes
    rows_f = pairs * width * activation_bytes
    matrices = 3 * experts * d_model * width
    return {
        # reads x twice (gate, up) and h; writes gate, up and the output
        "expert_gmm_fwd": 3 * rows_d + 3 * rows_f + matrices * weight_bytes,
        # reads d_gate, d_up, d_out; writes two d_x parts and d_h
        "expert_gmm_dx": 3 * rows_d + 3 * rows_f + matrices * weight_bytes,
        # reads x twice, h, and the three gradients; writes float32
        "expert_gmm_dw": 3 * rows_d + 3 * rows_f + matrices * 4,
    }


def compute_bound(pairs, experts, d_model, width, peaks) -> dict:
    """Whether the chip's compute (True) or its memory bounds each kernel at
    a perfectly balanced load."""
    flops = kernel_flops(pairs, d_model, width)
    ridge = peaks["bf16_flops_per_s"] / peaks["hbm_bytes_per_s"]
    return {
        kernel: flops / moved > ridge
        for kernel, moved in kernel_bytes(pairs, experts, d_model, width).items()
    }


def expert_gmm_time_share(run) -> float | None:
    seconds = kernel_seconds(run, EXPERT_KERNELS)
    if seconds is None or not run["trace"]["busy_s"]:
        return None
    return 100.0 * seconds / run["trace"]["busy_s"]


def _roofline(run, kernel: str, share: float) -> float | None:
    seconds = kernel_seconds(run, kernel)
    experts = run["flops_per_step_chip"].get("experts")
    if seconds is None or not experts or not run["traced_steps"]:
        return None
    flops = share * experts * run["traced_steps"]
    return 100.0 * flops / seconds / run["peaks"]["bf16_flops_per_s"]


def expert_gmm_roofline(run) -> float | None:
    """The analytic expert FLOPs of the traced steps over the three
    kernels' summed time and the bf16 peak.  Compute bounds them."""
    return _roofline(run, EXPERT_KERNELS, 1.0)


def expert_kernel_roofline(run, kernel: str) -> float | None:
    return _roofline(run, kernel, KERNEL_SHARE_OF_EXPERTS)


def router_load_max_over_mean(run) -> float | None:
    """The busiest expert's pairs over the mean, of the newest step, from
    the program's own counter (``telemetry/router_load.py``), which is read
    here, after the window.  A program without it reads nothing.  A pair the
    dispatch gave no row to is an error: none may be dropped."""
    try:
        from elasticdl_tpu.telemetry import router_load
    except ImportError:
        return None
    load = router_load.read()
    if load is None:
        return None
    if load["dropped_pairs"]:
        raise RuntimeError(f"the expert dispatch dropped pairs: {load}")
    return load["max_over_mean"]
