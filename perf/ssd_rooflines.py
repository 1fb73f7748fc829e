"""Operations, least bytes and roofline shares of the two chunked-scan
kernels of ``ops/ssd.py``, told apart on the op line by the name each
``pallas_call`` gives its compiled custom-call (``ssd_fwd.3``), as
``kernel_rooflines.py`` tells the flash kernels apart.

These kernels sit near the ridge (about 90 FLOP a byte against 240), so the
bound is stated per kernel: the larger of operations over the bf16 peak and
least bytes over the HBM peak is the least time the chip could take, and the
share is that over the kernel's self time.  The forward computes
``flops_per_step_chip["ssd"] / 3`` and the backward twice that; ``C B^T``
and the decays, which the backward computes again, are recomputation and
not counted."""

from __future__ import annotations

from perf.kernel_rooflines import kernel_seconds

KERNEL_SHARE_OF_SSD = {"ssd_fwd": 1.0 / 3.0, "ssd_bwd": 2.0 / 3.0}


def _named(kernels) -> str:
    """Any of ``kernels``, as ``kernel_seconds`` matches a kernel's name."""
    return "(" + "|".join(kernels) + ")"


def kernel_flops(kernel: str, tokens: int, spec: dict) -> float:
    """FLOPs of one call of ``kernel`` over ``tokens`` steps of one layer's
    heads (``flop_functions/nemotron_h.py``: a chunk's products at 2 FLOPs a
    MAC; the backward's are the forward's twice)."""
    chunk, states = spec["chunk"], spec["ssm_state"]
    macs = spec["ssm_groups"] * chunk * states + spec["mamba_heads"] * spec[
        "mamba_head_dim"
    ] * (chunk + 2 * states)
    return 2.0 * tokens * macs * (3 * KERNEL_SHARE_OF_SSD[kernel])


def kernel_bytes(
    kernel: str, tokens: int, spec: dict, activation_bytes: int = 2
) -> float:
    """Bytes one call must move at least once.  Forward: reads ``dt x``,
    ``B``, ``C`` and the float32 running sums, writes ``y`` and the float32
    state each chunk starts from.  Backward: reads those five and ``dy``,
    writes the gradients of ``dt x``, ``B`` and ``C`` and a 128-lane row of
    float32 a head and chunk."""
    heads, width = spec["mamba_heads"], spec["mamba_head_dim"]
    per_head = tokens * heads * width * activation_bytes
    per_group = tokens * spec["ssm_groups"] * spec["ssm_state"] * activation_bytes
    sums = tokens * heads * 4
    chunks = tokens // spec["chunk"]
    starts = chunks * heads * spec["ssm_state"] * width * 4
    if kernel == "ssd_fwd":
        return 2 * per_head + 2 * per_group + sums + starts
    return 3 * per_head + 4 * per_group + sums + starts + chunks * heads * 128 * 4


def least_seconds(kernel: str, tokens: int, spec: dict, peaks: dict) -> dict:
    """The two times of the roofline for one call, and which one bounds."""
    compute = kernel_flops(kernel, tokens, spec) / peaks["bf16_flops_per_s"]
    memory = kernel_bytes(kernel, tokens, spec) / peaks["hbm_bytes_per_s"]
    return {
        "compute_s": compute, "memory_s": memory,
        "least_s": max(compute, memory), "compute_bound": compute >= memory,
    }


def _calls(run):
    """``(tokens a call, calls in the traced window, spec)``: a call is one
    Mamba-2 layer of one step; None for a configuration without them."""
    spec = run["cell"].config["flops"]
    layers = str(spec.get("pattern", "")).count("M")
    if not layers or not run["traced_steps"]:
        return None
    traffic = run["cell"].traffic
    tokens = traffic["batch_per_chip"] * traffic["records"]["seq_len"]
    return tokens, layers * run["traced_steps"], spec


def ssd_time_share(run) -> float | None:
    seconds = kernel_seconds(run, _named(KERNEL_SHARE_OF_SSD))
    if seconds is None or not run["trace"]["busy_s"]:
        return None
    return 100.0 * seconds / run["trace"]["busy_s"]


def _roofline(run, kernels: tuple) -> float | None:
    seconds = kernel_seconds(run, _named(kernels))
    if seconds is None:
        return None
    found = _calls(run)
    if found is None:
        return None
    tokens, calls, spec = found
    least = sum(
        least_seconds(kernel, tokens, spec, run["peaks"])["least_s"]
        for kernel in kernels
    )
    return 100.0 * calls * least / seconds


def ssd_roofline(run) -> float | None:
    """The least time the chip could take for both kernels' calls of the
    traced steps over the kernels' summed self time."""
    return _roofline(run, tuple(KERNEL_SHARE_OF_SSD))


def ssd_kernel_roofline(run, kernel: str) -> float | None:
    return _roofline(run, (kernel,))


def held_pair_share(run) -> float | None:
    """The share of the newest step's (token, slot) pairs routed to experts
    held here, from the program's own counter (``telemetry/router_load.py``),
    read here after the window: 100 x held / all at a balanced load.  A
    program without the counter (the parent commit), or a model whose layers
    hold all their experts' pairs without saying so, reads nothing.  A pair of
    a held expert that the dispatch gave no row to is an error."""
    try:
        from elasticdl_tpu.telemetry import router_load
    except ImportError:
        return None
    load = router_load.read()
    if load is None or "held_pairs" not in load:
        return None
    if load["dropped_pairs"]:
        raise RuntimeError(f"the expert dispatch dropped pairs: {load}")
    return 100.0 * load["held_pairs"] / load["pairs"]
