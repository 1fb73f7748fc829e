"""Model FLOPs of ``ouro`` (ByteDance's looped language model: a stack of
dense layers run ``passes`` times on one set of weights, an exit gate and the
untied head after every pass), from shapes.  The count follows the work, not
the parameters: every layer is counted once a pass, and so are the head and
the gate.  Training counts the forward pass once and the backward pass twice
(3x forward); recomputation (each layer's second forward, the head's second
product in the loss) is never counted.  One multiply-accumulate is 2 FLOPs.

``causal_attention`` is the layers' attention at the dense causal count, ``T
(T + 1) / 2`` pairs a head, a pass and a layer: what ``kernel_rooflines.py``
divides the ``flash_*`` kernels' time into (the kernels run ``layers x
passes`` times a step, and so does the count)."""

from __future__ import annotations


def causal_pairs(seq_len: int) -> int:
    """``T (T + 1) / 2``: 8,390,656 at 4,096."""
    return seq_len * (seq_len + 1) // 2


def macs_per_token(spec: dict) -> dict:
    """Multiply-accumulates a token in ONE application of a part, forward."""
    d = spec["d_model"]
    return {
        # q, k, v and the output projection, then gate, up and down
        "layers": 4 * d * spec["heads"] * spec["head_dim"]
        + 3 * d * spec["mlp_width"],
        "head": d * spec["vocab"],
        "gate": d,
    }


def per_record(spec: dict, traffic: dict) -> dict:
    """A record is one sequence of the traffic file's ``seq_len``.  Scores
    and values over a layer's causal pairs, forward and backward, are ``6 *
    pairs * heads * 2 * head_dim`` (a third each to the forward kernel, dQ
    and dK/dV; the flash backward's recomputed scores are not counted)."""
    seq_len = traffic["records"]["seq_len"]
    passes, layers = spec["passes"], spec["layers"]
    macs = macs_per_token(spec)
    counts = {"layers": layers * passes, "head": passes, "gate": passes}
    parts = {
        name: 6.0 * seq_len * count * macs[name] for name, count in counts.items()
    }
    parts["causal_attention"] = (
        6.0 * spec["heads"] * 2 * spec["head_dim"]
        * layers * passes * causal_pairs(seq_len)
    )
    return {"train": sum(parts.values()), **parts}
