"""Model FLOPs of ``afmoe`` (Arcee's AFMoE, Trinity-Mini: grouped-query
attention with an output gate, window layers and full layers mixed, a leading
dense SwiGLU layer, sigmoid-routed SwiGLU experts with a shared expert), from
shapes.  Training counts the forward pass once and the backward pass twice
(3x forward); recomputation is never counted.  One multiply-accumulate is 2
FLOPs.  Of the routed experts only what the experts HELD here compute at a
balanced load is counted: ``k * held / all`` experts a token.

The two kinds of attention layer are counted apart.  ``causal_attention`` is
the FULL layers alone at the dense causal count, ``T (T + 1) / 2`` pairs a
head: what ``kernel_rooflines.py`` divides the ``flash_*`` kernels' time
into.  ``window_attention`` is the window layers at the pairs INSIDE the
window, ``sum_t min(t + 1, window)`` a head, so that no share computed from
it can pass 100% whether the kernels skip what lies behind the window or
not."""

from __future__ import annotations


def window_pairs(seq_len: int, window: int) -> int:
    """``sum_{t < T} min(t + 1, window)``: 31,458,304 at 16,384 and 2,048."""
    w = min(window, seq_len)
    return w * (w + 1) // 2 + (seq_len - w) * w


def causal_pairs(seq_len: int) -> int:
    """``T (T + 1) / 2``: 134,225,920 at 16,384."""
    return seq_len * (seq_len + 1) // 2


def macs_per_token(spec: dict) -> dict:
    """Multiply-accumulates a token in ONE layer's part (``head``: the one
    pass), forward."""
    d, width = spec["d_model"], spec["head_dim"]
    return {
        # q, the gate and the output projection; k and v
        "attention_projections": 3 * d * spec["heads"] * width
        + 2 * d * spec["kv_heads"] * width,
        "dense_mlp": 3 * d * spec["dense_width"],
        "shared_expert": 3 * d * spec["shared_width"],
        "experts": spec["experts_per_token"] * spec["experts_held"]
        / spec["num_experts"] * 3 * d * spec["expert_width"],
        "router": d * spec["num_experts"],
        "head": d * spec["vocab"],
    }


def per_record(spec: dict, traffic: dict) -> dict:
    """A record is one sequence of the traffic file's ``seq_len``.  Scores
    and values over a layer's visible pairs, forward and backward, are ``6 *
    pairs * heads * 2 * head_dim`` (a third each to the forward kernel, dQ
    and dK/dV; the flash backward's recomputed scores are not counted)."""
    seq_len = traffic["records"]["seq_len"]
    attention_layers = spec["window_layers"] + spec["full_layers"]
    macs = macs_per_token(spec)
    counts = {
        "attention_projections": attention_layers,
        "dense_mlp": spec["dense_layers"],
        "shared_expert": spec["expert_layers"],
        "experts": spec["expert_layers"],
        "router": spec["expert_layers"],
        "head": 1,
    }
    parts = {
        name: 6.0 * seq_len * count * macs[name] for name, count in counts.items()
    }
    a_pair = 6.0 * spec["heads"] * 2 * spec["head_dim"]
    parts["causal_attention"] = (
        a_pair * spec["full_layers"] * causal_pairs(seq_len)
    )
    parts["window_attention"] = (
        a_pair * spec["window_layers"] * window_pairs(seq_len, spec["window"])
    )
    return {"train": sum(parts.values()), **parts}
