"""Model FLOPs of ``lfm2_moe`` (Liquid AI's LFM2 MoE stack: gated short
convolutions in three layers of four, grouped-query attention in the fourth,
a leading dense SwiGLU layer, sigmoid-routed SwiGLU experts with no shared
expert, a tied head), from shapes.  Training counts the forward pass once and
the backward pass twice (3x forward); recomputation is never counted.  One
multiply-accumulate is 2 FLOPs.  Of the routed experts only what the experts
HELD here compute at a balanced load is counted: ``k * held / all`` experts a
token.  The tied head is one product (the embedding's other use is a
gather).

``causal_attention`` is the attention layers at the dense causal count, ``T
(T + 1) / 2`` pairs a head: what ``kernel_rooflines.py`` divides the
``flash_*`` kernels' time into.  ``conv_taps`` is the pass between the
convolution operator's two projections at its multiply-accumulates (the taps
and the two gates, ``taps + 2`` a channel): 0.06% of the model, and bound by
bytes (``perf/conv_rooflines.py``)."""

from __future__ import annotations


def causal_pairs(seq_len: int) -> int:
    """``T (T + 1) / 2``: 8,390,656 at 4,096."""
    return seq_len * (seq_len + 1) // 2


def macs_per_token(spec: dict) -> dict:
    """Multiply-accumulates a token in ONE layer's part (``head``: the one
    pass), forward."""
    d, width = spec["d_model"], spec["head_dim"]
    return {
        # the input projection to three streams and the output projection
        "conv_projections": d * 3 * d + d * d,
        "conv_taps": (spec["conv_taps"] + 2) * d,
        # q and the output projection; k and v
        "attention_projections": 2 * d * spec["heads"] * width
        + 2 * d * spec["kv_heads"] * width,
        "dense_mlp": 3 * d * spec["dense_width"],
        "experts": spec["experts_per_token"] * spec["experts_held"]
        / spec["num_experts"] * 3 * d * spec["expert_width"],
        "router": d * spec["num_experts"],
        "head": d * spec["vocab"],
    }


def per_record(spec: dict, traffic: dict) -> dict:
    """A record is one sequence of the traffic file's ``seq_len``.  Scores
    and values over a layer's causal pairs, forward and backward, are ``6 *
    pairs * heads * 2 * head_dim`` (a third each to the forward kernel, dQ
    and dK/dV; the flash backward's recomputed scores are not counted)."""
    seq_len = traffic["records"]["seq_len"]
    macs = macs_per_token(spec)
    counts = {
        "conv_projections": spec["conv_layers"],
        "conv_taps": spec["conv_layers"],
        "attention_projections": spec["attention_layers"],
        "dense_mlp": spec["dense_layers"],
        "experts": spec["expert_layers"],
        "router": spec["expert_layers"],
        "head": 1,
    }
    parts = {
        name: 6.0 * seq_len * count * macs[name] for name, count in counts.items()
    }
    parts["causal_attention"] = (
        6.0 * spec["heads"] * 2 * spec["head_dim"]
        * spec["attention_layers"] * causal_pairs(seq_len)
    )
    return {"train": sum(parts.values()), **parts}
