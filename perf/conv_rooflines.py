"""Least bytes and roofline shares of the gated short convolution's two
kernels (``ops/short_conv.py``: ``short_conv_fwd``, ``short_conv_bwd``), told
apart on the op line by the name each ``pallas_call`` gives its compiled
custom-call (``short_conv_fwd.3``), as ``kernel_rooflines.py`` tells the
flash kernels apart; and the operator's share of device-busy time.

The pass is bound by bytes: ``taps + 2`` multiply-accumulates a channel and
step against 8 bytes moved forward and 14 backward.  A call's least time is
its least bytes over the HBM peak, and a kernel's share is the least time of
a traced step's calls over the kernel's self time.  The least bytes are the
streams as the layer holds them and the taps, no more: the forward reads
``B``, ``C``, ``X`` and writes one array; the backward reads those three and
``dOut`` and writes three gradients; the 16 rows a tile reads again before
itself are not counted.  Where every layer is recomputed in the backward pass
(``remat_layers``) the forward kernel runs twice a layer and step, and both
calls' bytes are counted: this is a share of a bandwidth, not of the model's
operations."""

from __future__ import annotations

from perf import scope_shares, trace_reduce
from perf.kernel_rooflines import kernel_seconds

KERNELS = ("short_conv_fwd", "short_conv_bwd")
CONV_KERNELS = r"^short_conv_(fwd|bwd)\b"
# arrays of batch x T x channels a call reads and writes
STREAMS = {"short_conv_fwd": 4, "short_conv_bwd": 7}


def kernel_bytes(
    kernel: str, tokens: int, spec: dict, activation_bytes: int = 2
) -> float:
    """Bytes one call over ``tokens`` steps must move at least once: the
    streams in the activations' dtype, the taps in float32 (read; the
    backward writes their gradient too)."""
    taps = spec["conv_taps"] * spec["d_model"] * 4
    return (
        STREAMS[kernel] * tokens * spec["d_model"] * activation_bytes
        + taps * (2 if kernel == "short_conv_bwd" else 1)
    )


def calls_per_step(kernel: str, config: dict) -> int:
    """A call a convolution layer, and the forward's second where the layers
    are recomputed."""
    layers = config["flops"].get("conv_layers", 0)
    again = bool(config["run"]["model_params"].get("remat_layers"))
    return layers * (2 if kernel == "short_conv_fwd" and again else 1)


def kernel_roofline(run, kernel: str) -> float | None:
    seconds = kernel_seconds(run, kernel)
    if seconds is None or not run["traced_steps"]:
        return None
    config = run["cell"].config
    calls = calls_per_step(kernel, config)
    if not calls:
        return None
    traffic = run["cell"].traffic
    tokens = traffic["batch_per_chip"] * traffic["records"]["seq_len"]
    least = kernel_bytes(kernel, tokens, config["flops"]) / run["peaks"][
        "hbm_bytes_per_s"
    ]
    return 100.0 * run["traced_steps"] * calls * least / seconds


def short_conv_time_share(run) -> float | None:
    """The two kernels, of device-busy time; nothing where the program runs
    neither."""
    trace = run.get("trace")
    if trace is None or not trace.get("busy_s"):
        return None
    seconds = trace_reduce.matching_seconds(trace, CONV_KERNELS)
    return 100.0 * seconds / trace["busy_s"] if seconds else None


def conv_operator_share(run) -> float | None:
    """All device time under a block's ``conv`` part (its two projections,
    the pass, forward, recomputed and backward), of device-busy time: what
    the ``attention_*`` shares are for the attention part."""
    return scope_shares.share(
        run, lambda part, phase, kind: "conv" in part.split("/")
    )
