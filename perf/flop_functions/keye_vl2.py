"""Model FLOPs of ``keye_vl2`` (Keye-VL-2.0's language model: a Qwen3-MoE
decoder whose attention is DeepSeek sparse attention), from shapes.
Training counts the forward pass once and the backward pass twice (3x
forward); recomputation is never counted.  One multiply-accumulate is 2
FLOPs.  Of the routed experts only what the experts HELD here compute at a
balanced load is counted: ``k * held / all`` experts a token.

The main attention is counted over the SELECTED (query, key) pairs, ``sum_t
min(t + 1, topk)`` a sequence, and not over the causal ones, so that no share
computed from it can pass 100% when a kernel skips what is not selected; the
kernels of this PR mask and skip nothing.  The index scores are computed for
every causal pair in the forward pass (the selection needs them all) and
their gradient exists on the selected pairs alone (the KL is over the set)."""

from __future__ import annotations


def selected_pairs(seq_len: int, topk: int) -> int:
    """``sum_{t < T} min(t + 1, topk)``: 31,458,304 at 16,384 and 2,048."""
    k = min(topk, seq_len)
    return k * (k + 1) // 2 + (seq_len - k) * k


def causal_pairs(seq_len: int) -> int:
    return seq_len * (seq_len + 1) // 2


def macs_per_token(spec: dict) -> dict:
    """Multiply-accumulates a token in ONE layer's part (``head``: the one
    pass), forward."""
    d, width = spec["d_model"], spec["head_dim"]
    return {
        "attention_projections": 2 * d * spec["heads"] * width
        + 2 * d * spec["kv_heads"] * width,
        "indexer_projections": d * spec["index_heads"] * spec["index_head_dim"]
        + d * spec["index_head_dim"] + d * spec["index_heads"],
        "experts": spec["experts_per_token"] * spec["experts_held"]
        / spec["num_experts"] * 3 * d * spec["expert_width"],
        "router": d * spec["num_experts"],
        "head": d * spec["vocab"],
    }


def per_record(spec: dict, traffic: dict) -> dict:
    """A record is one sequence of the traffic file's ``seq_len``.
    ``selected_attention`` is scores and values over the selected pairs,
    forward and backward (a third each to the forward kernel, dQ and
    dK/dV); ``index_scores`` is ``heads x width`` a causal pair forward and
    twice that a selected pair backward."""
    seq_len = traffic["records"]["seq_len"]
    layers = spec["layers"]
    macs = macs_per_token(spec)
    parts = {
        name: 6.0 * seq_len * (1 if name == "head" else layers) * count
        for name, count in macs.items()
    }
    chosen = selected_pairs(seq_len, spec["index_topk"])
    parts["selected_attention"] = (
        6.0 * layers * chosen * spec["heads"] * 2 * spec["head_dim"]
    )
    index_macs = spec["index_heads"] * spec["index_head_dim"]
    parts["index_scores"] = (
        2.0 * layers * index_macs * (causal_pairs(seq_len) + 2 * chosen)
    )
    return {"train": sum(parts.values()), **parts}
