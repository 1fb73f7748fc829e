"""Model FLOPs of ``qwen3_next`` (Qwen3-Next: Gated DeltaNet layers and gated
softmax layers mixed, softmax-routed SwiGLU experts and one gated shared
expert in every layer), from shapes, as ``mellum.py`` counts its family.
Training counts the forward pass once and the backward pass twice (3x
forward); recomputation is never counted.  One multiply-accumulate is 2
FLOPs.  Of the routed experts only what the experts HELD here compute at a
balanced load is counted: ``k * held / all`` experts a token.

``delta_rule`` is the RECURRENCE's count, not the chunked form's: a step of a
value head reads its state once for the error (``S^T k``), writes the rank-one
correction and reads it again for the output (``S^T q``): three
multiply-accumulates a (key, value) pair of the state.  What the chunked
kernels compute to get there (the scores inside a chunk, the solve) is
``perf/gdn_rooflines.py``'s to count; it is more, so a share of the peak
made from this count cannot flatter the kernels.  The L2 norms, the gates,
the norm a head and the rotary positions are elementwise and not counted."""

from __future__ import annotations

from perf.flop_functions.afmoe import causal_pairs


def macs_per_token(spec: dict) -> dict:
    """Multiply-accumulates a token in ONE layer's part (``head``: the one
    pass), forward."""
    d = spec["d_model"]
    keys = spec["linear_key_heads"] * spec["linear_key_dim"]
    values = spec["linear_value_heads"] * spec["linear_value_dim"]
    attention = spec["heads"] * spec["head_dim"]
    return {
        # q, k, v, z; b, a; the depthwise taps over [q | k | v]; the output
        "delta_projections": d * (2 * keys + 2 * values)
        + d * 2 * spec["linear_value_heads"]
        + spec["conv_kernel"] * (2 * keys + values) + values * d,
        "delta_rule": 3 * spec["linear_value_heads"]
        * spec["linear_key_dim"] * spec["linear_value_dim"],
        # q, its gate and the output projection; k and v
        "attention_projections": 3 * d * attention
        + 2 * d * spec["kv_heads"] * spec["head_dim"],
        "experts": spec["experts_per_token"] * spec["experts_held"]
        / spec["num_experts"] * 3 * d * spec["expert_width"],
        # three matrices and the gate's one column
        "shared_expert": 3 * d * spec["shared_expert_width"] + d,
        "router": d * spec["num_experts"],
        "head": d * spec["vocab"],
    }


LAYERS_OF = {
    "delta_projections": ("linear_layers",),
    "delta_rule": ("linear_layers",),
    "attention_projections": ("full_layers",),
    "experts": ("linear_layers", "full_layers"),
    "shared_expert": ("linear_layers", "full_layers"),
    "router": ("linear_layers", "full_layers"),
}


def per_record(spec: dict, traffic: dict) -> dict:
    """A record is one sequence of the traffic file's ``seq_len``.  Scores
    and values over the full layers' visible pairs, forward and backward, are
    ``6 * pairs * heads * 2 * head_dim`` (a third each to the forward kernel,
    dQ and dK/dV; the flash backward's recomputed scores are not counted)."""
    seq_len = traffic["records"]["seq_len"]
    macs = macs_per_token(spec)
    parts = {
        name: 6.0 * seq_len * count
        * (sum(spec[key] for key in LAYERS_OF[name]) if name in LAYERS_OF else 1)
        for name, count in macs.items()
    }
    parts["causal_attention"] = (
        6.0 * spec["heads"] * 2 * spec["head_dim"] * spec["full_layers"]
        * causal_pairs(seq_len)
    )
    return {"train": sum(parts.values()), **parts}
