"""Operations, least bytes and roofline shares of the five sparse-attention
kernels (``ops/sparse_attention.py``: ``dsa_index``, ``dsa_kl``;
``ops/attention.py``'s three flash kernels over a selected set: ``dsa_fwd``,
``dsa_dq``, ``dsa_dkv``), told apart on the op line by the name each
``pallas_call`` gives its compiled custom-call (``dsa_fwd.3``), as
``kernel_rooflines.py`` tells the flash kernels apart; and the cell's time
shares by the model's own scopes (``scope_shares.py``).

A kernel's least time is the larger of its operations over the bf16 peak and
its least bytes over the HBM peak; its share is the least time of a traced
step's calls over the kernel's self time.  What counts as operations is what
``flop_functions/keye_vl2.py`` counts, once a step: the SELECTED pairs for
the three attention kernels (they mask and skip no block, so each reads
about the selected share of the causal pairs, 23.4% at 16,384 tokens, of
what a dense flash kernel reads), the causal pairs' index scores for
``dsa_index`` (its 46 compare-and-count passes of the radix select are no
operations of the roofline), the selected pairs' two gradient products for
``dsa_kl`` (the main attention's scores and the index scores it computes
again are recomputation).  Every layer is recomputed in the backward pass, so
``dsa_index`` (the recomputed pass as ``dsa_index_hinted``) and ``dsa_fwd``
run twice a layer and step; ``dsa_kl`` runs ONCE a layer and step since PR 43
(the first forward pass makes the indexer's loss and its parameter gradients
in one call and hands them to the recomputed pass); each is counted once.
All five come out bound by compute (``least_seconds`` says
so per kernel); none is near its bound: they are the baseline a perf_opt PR
starts from."""

from __future__ import annotations

from perf import scope_shares
from perf.kernel_rooflines import kernel_seconds

KERNELS = ("dsa_index", "dsa_fwd", "dsa_dq", "dsa_dkv", "dsa_kl")
# the scopes of layers/attention.py::MultiHeadSelfAttention._sparse_attend
INDEXER_SCOPES = ("indexer", "indexer_kl")
SELECTION_SCOPES = ("index_select",)


def _pairs(spec: dict, seq_len: int) -> tuple[int, int]:
    k = min(spec["index_topk"], seq_len)
    return k * (k + 1) // 2 + (seq_len - k) * k, seq_len * (seq_len + 1) // 2


def kernel_flops(kernel: str, seq_len: int, spec: dict) -> float:
    """FLOPs a layer and step of one sequence of ``seq_len``."""
    chosen, causal = _pairs(spec, seq_len)
    index = 2.0 * spec["index_heads"] * spec["index_head_dim"]
    if kernel == "dsa_index":
        return index * causal
    if kernel == "dsa_kl":
        return 2 * index * chosen
    # scores and values forward; dP and dQ; dV and dK
    return 2.0 * chosen * spec["heads"] * 2 * spec["head_dim"]


def kernel_bytes(
    kernel: str, seq_len: int, spec: dict, activation_bytes: int = 2
) -> float:
    """Bytes a layer and step must move at least once: the operands and
    results as the layer holds them, and the int8 mask once."""
    q = seq_len * spec["heads"] * spec["head_dim"] * activation_bytes
    kv = seq_len * spec["kv_heads"] * spec["head_dim"] * activation_bytes
    qi = seq_len * spec["index_heads"] * spec["index_head_dim"] * activation_bytes
    ki = seq_len * spec["index_head_dim"] * activation_bytes
    w = seq_len * spec["index_heads"] * 4
    rows = seq_len * spec["heads"] * 4  # a float32 a head and query
    mask = seq_len * seq_len
    return {
        "dsa_index": qi + ki + w + mask + 3 * seq_len * 4,
        "dsa_fwd": 2 * q + 2 * kv + mask + rows,
        "dsa_dq": 3 * q + 2 * kv + mask + 2 * rows,
        # the gradients leave a query head each (summed over a group outside)
        "dsa_dkv": 4 * q + 2 * kv + mask + 2 * rows,
        "dsa_kl": q + kv // 2 + mask + rows + 3 * qi + 2 * ki + 2 * w,
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
    if not spec.get("index_topk"):
        return None
    traffic = run["cell"].traffic
    per_step = traffic["batch_per_chip"] * spec["layers"]
    least = least_seconds(
        kernel, traffic["records"]["seq_len"], spec, run["peaks"]
    )["least_s"]
    return 100.0 * run["traced_steps"] * per_step * least / seconds


def _under_any(part: str, scopes) -> bool:
    return any(scope in part.split("/") for scope in scopes)


def _has_indexer(run) -> bool:
    found = scope_shares.attributed(run)
    return found is not None and any(
        _under_any(part, INDEXER_SCOPES + SELECTION_SCOPES)
        for part, _, _ in found["scopes"]
    )


def _scoped_share(run, chosen) -> float | None:
    """A share by scopes; nothing for a program without an indexer."""
    if not _has_indexer(run):
        return None
    return scope_shares.share(run, chosen)


def indexer_time_share(run) -> float | None:
    """The indexer's own work outside the selection: its three projections,
    its norm and rotary positions (scope ``indexer``) and its loss with the
    gradients to its queries, keys and weights (``indexer_kl``: the
    ``dsa_kl`` kernel), forward, backward and recomputed."""
    return _scoped_share(
        run, lambda p, ph, k: _under_any(p, INDEXER_SCOPES)
    )


def selection_time_share(run) -> float | None:
    """Scope ``index_select``: the ``dsa_index`` kernel (index scores into
    VMEM and the exact select out of it, one kernel: the scores never cross
    HBM, so the two are not timed apart) and the mask's transpose."""
    return _scoped_share(
        run, lambda p, ph, k: _under_any(p, SELECTION_SCOPES)
    )


def sparse_attention_time_share(run) -> float | None:
    """Indexer, selection and the three selected-set attention kernels (the
    kernels under ``attn`` outside those scopes), of device-busy time."""
    scopes = INDEXER_SCOPES + SELECTION_SCOPES
    return _scoped_share(
        run,
        lambda p, ph, k: _under_any(p, scopes)
        or (k == "kernel" and _under_any(p, ("attn",))),
    )
