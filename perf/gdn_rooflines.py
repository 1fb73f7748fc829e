"""Operations, least bytes and roofline shares of the two gated-delta-rule
kernels of ``ops/gated_delta.py``, told apart on the op line by the name each
``pallas_call`` gives its compiled custom-call (``gdn_fwd.3``), as
``ssd_rooflines.py`` tells the scan kernels apart; the operator's share of
device-busy time; and the sown state counter.

The kernels' operations are the CHUNKED form's, counted from shapes: a chunk
of ``C`` steps of a key head makes ``K K^T`` and ``Q K^T`` once (``2 C^2
dk``), and each value head it serves the solve (ten ``C x C`` products at
``C`` = 64, :func:`solve_products`, counted ONCE each though a float32 product
is three passes of the matrix unit: the passes are how the chip reaches the
precision, not operations of the algorithm), ``W`` and ``U`` (``C^2 (dk +
dv)``), ``W S`` and ``Q S`` (``2 C dk dv``), the scores' product with ``V'``
(``C^2 dv``) and the state's update (``C dk dv``).  The backward computes the
forward's ``A``, ``T``, ``W``, ``U`` and ``V'`` again, which is recomputation
and NOT counted, and eighteen products of its own (:func:`chunk_macs`).
Where every layer is recomputed in the backward pass (``remat_layers``) the
forward kernel runs twice a layer and step; the second call is recomputation
too, so a step's forward operations are counted once and set against BOTH
calls' time: the share is of the model's work, as ``ssd_rooflines.py``
counts.  The larger of operations over the bf16 peak and least bytes over
the HBM peak is the least time the chip could take."""

from __future__ import annotations

from perf import scope_shares
from perf.kernel_rooflines import kernel_seconds

KERNELS = ("gdn_fwd", "gdn_bwd")
GDN_KERNELS = "gdn_(fwd|bwd)"
_BLOCK = 16  # the diagonal blocks the solve inverts first


def solve_products(chunk: int) -> int:
    """``C x C`` products the two-level finite product makes for a chunk: the
    16 x 16 diagonal blocks (a squaring and a factor a doubling up to 16),
    the level above (``T_D L``, a squaring and a factor a doubling of the
    blocks, the last product with ``T_D``)."""
    count, width = 0, 2
    while width < min(_BLOCK, chunk):
        count, width = count + 2, width * 2
    if chunk <= _BLOCK:
        return count
    count, width = count + 1, 2 * _BLOCK
    while width < chunk:
        count, width = count + 2, width * 2
    return count + 1


def chunk_macs(kernel: str, spec: dict) -> float:
    """Multiply-accumulates of one chunk of one layer's heads in ``kernel``."""
    chunk = spec["chunk"]
    keys, values = spec["linear_key_heads"], spec["linear_value_heads"]
    dk, dv = spec["linear_key_dim"], spec["linear_value_dim"]
    square = chunk * chunk
    if kernel == "gdn_fwd":
        return keys * 2 * square * dk + values * (
            solve_products(chunk) * square * chunk
            + square * (dk + dv) + 2 * chunk * dk * dv
            + square * dv + chunk * dk * dv
        )
    # dV' (2), dP, dQ (2), dK, dS (2), dK_left, dW, dT (2), dK_d, dV_b,
    # dA (2), dK through K K^T (2)
    return values * (
        square * (4 * dv + 6 * dk) + 6 * chunk * dk * dv + 2 * square * chunk
    )


def kernel_flops(kernel: str, tokens: int, spec: dict) -> float:
    return 2.0 * (tokens // spec["chunk"]) * chunk_macs(kernel, spec)


def kernel_bytes(
    kernel: str, tokens: int, spec: dict, activation_bytes: int = 2
) -> float:
    """Bytes one call must move at least once.  Forward: reads ``q``, ``k``,
    ``v`` and the two float32 rows a value head, writes ``o`` and the float32
    state each chunk starts from.  Backward: reads those, ``do`` and the
    states, writes three gradients and two float32 rows."""
    keys = tokens * spec["linear_key_heads"] * spec["linear_key_dim"]
    values = tokens * spec["linear_value_heads"] * spec["linear_value_dim"]
    rows = tokens * spec["linear_value_heads"] * 4
    starts = (
        (tokens // spec["chunk"]) * spec["linear_value_heads"]
        * spec["linear_key_dim"] * spec["linear_value_dim"] * 4
    )
    if kernel == "gdn_fwd":
        return (2 * keys + 2 * values) * activation_bytes + 2 * rows + starts
    return (4 * keys + 4 * values) * activation_bytes + 4 * rows + starts


def least_seconds(kernel: str, tokens: int, spec: dict, peaks: dict) -> dict:
    """The two times of the roofline for one call, and which one bounds."""
    compute = kernel_flops(kernel, tokens, spec) / peaks["bf16_flops_per_s"]
    memory = kernel_bytes(kernel, tokens, spec) / peaks["hbm_bytes_per_s"]
    return {
        "compute_s": compute, "memory_s": memory,
        "least_s": max(compute, memory), "compute_bound": compute >= memory,
    }


def _calls(run):
    """``(tokens a call, counted calls in the traced window, spec)``: a
    counted call is one Gated DeltaNet layer of one step; None for a
    configuration without them."""
    spec = run["cell"].config["flops"]
    layers = spec.get("linear_layers", 0)
    if not layers or "linear_value_heads" not in spec or not run["traced_steps"]:
        return None
    traffic = run["cell"].traffic
    tokens = traffic["batch_per_chip"] * traffic["records"]["seq_len"]
    return tokens, layers * run["traced_steps"], spec


def delta_kernel_roofline(run, kernel: str) -> float | None:
    """The least time the chip could take for ``kernel``'s counted calls of
    the traced steps over the kernel's self time."""
    seconds = kernel_seconds(run, kernel)
    found = _calls(run)
    if seconds is None or found is None:
        return None
    tokens, calls, spec = found
    least = least_seconds(kernel, tokens, spec, run["peaks"])["least_s"]
    return 100.0 * calls * least / seconds


def delta_rule_time_share(run) -> float | None:
    seconds = kernel_seconds(run, GDN_KERNELS)
    if seconds is None or not run["trace"]["busy_s"]:
        return None
    return 100.0 * seconds / run["trace"]["busy_s"]


def linear_attention_operator_share(run) -> float | None:
    """All device time under a block's ``gdn`` part (its projections, the
    convolution, the scan, the gated norm; forward, recomputed and backward),
    of device-busy time; nothing where the program has no such part."""
    share = scope_shares.share(
        run, lambda part, phase, kind: "gdn" in part.split("/")
    )
    return share or None


def state_decay_mean(run) -> float | None:
    """The newest step's mean decay ``exp(g)`` over the Gated DeltaNet
    layers, from the program's own counter (``telemetry/router_load.py``),
    read here after the window: near 1 the state is live over many steps,
    at 0 it forgets everything a step.  A program without the counter (the
    parent commit) reads nothing."""
    try:
        from elasticdl_tpu.telemetry import router_load
    except ImportError:
        return None
    read = getattr(router_load, "read_delta_state", None)
    state = read() if read is not None else None
    return None if state is None else state["decay_mean"]
