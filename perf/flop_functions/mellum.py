"""Model FLOPs of ``mellum`` (JetBrains' Mellum 2: grouped-query attention,
window layers and full layers mixed, softmax-routed SwiGLU experts in every
layer and no shared expert), from shapes, as ``afmoe.py`` counts its family.
Training counts the forward pass once and the backward pass twice (3x
forward); recomputation is never counted.  One multiply-accumulate is 2
FLOPs.  Of the routed experts only what the experts HELD here compute at a
balanced load is counted: ``k * held / all`` experts a token.

The two kinds of attention layer are counted apart.  ``causal_attention`` is
the FULL layers alone at the dense causal count, ``T (T + 1) / 2`` pairs a
head: what ``kernel_rooflines.py`` divides the ``flash_*`` kernels' time
into.  ``window_attention`` is the window layers at the pairs INSIDE the
window, ``sum_t min(t + 1, window)`` a head (``window_rooflines.py`` counts
the ``swa_*`` kernels the same way from the ``flops`` group's ``window``), so
that no share computed from it can pass 100%.  The rotary positions, YaRN's
tables among them, are elementwise and not counted."""

from __future__ import annotations

from perf.flop_functions.afmoe import causal_pairs, window_pairs


def macs_per_token(spec: dict) -> dict:
    """Multiply-accumulates a token in ONE layer's part (``head``: the one
    pass), forward."""
    d, width = spec["d_model"], spec["head_dim"]
    return {
        # q and the output projection; k and v
        "attention_projections": 2 * d * spec["heads"] * width
        + 2 * d * spec["kv_heads"] * width,
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
    layers = spec["window_layers"] + spec["full_layers"]
    macs = macs_per_token(spec)
    parts = {
        name: 6.0 * seq_len * (1 if name == "head" else layers) * count
        for name, count in macs.items()
    }
    a_pair = 6.0 * spec["heads"] * 2 * spec["head_dim"]
    parts["causal_attention"] = (
        a_pair * spec["full_layers"] * causal_pairs(seq_len)
    )
    parts["window_attention"] = (
        a_pair * spec["window_layers"] * window_pairs(seq_len, spec["window"])
    )
    return {"train": sum(parts.values()), **parts}
