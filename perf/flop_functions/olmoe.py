"""Model FLOPs of the sparse-expert decoder LM (OLMoE), from shapes.
Training counts the forward pass once and the backward pass twice (3x
forward); recomputation is never counted.  One multiply-accumulate is 2
FLOPs.  Only the experts a token is routed to are counted."""

from __future__ import annotations


def dense_macs_per_token(spec: dict) -> dict:
    """Multiply-accumulates a token and layer, by part: the four attention
    projections ``4 d^2``, the router ``d E``, the token's ``k`` SwiGLU
    experts ``k * 3 * d * f``; and the untied head ``d V`` once."""
    d = spec["d_model"]
    return {
        "attention_projections": 4 * d * d,
        "router": d * spec["num_experts"],
        "experts": spec["experts_per_token"] * 3 * d * spec["expert_width"],
        "head": d * spec["vocab"],
    }


def per_record(spec: dict, traffic: dict) -> dict:
    """A record is one sequence of the traffic file's ``seq_len``.
    ``causal_attention`` is ``6*L*T*d`` a token as in ``transformer_lm``
    (the flash backward's recomputed scores are not counted); ``experts``
    is what the three grouped-matmul kernels compute between them."""
    seq_len = traffic["records"]["seq_len"]
    layers = spec["layers"]
    macs = dense_macs_per_token(spec)
    parts = {
        "causal_attention": 6.0 * layers * seq_len * spec["d_model"],
        "experts": 6.0 * layers * macs["experts"],
        "head": 6.0 * macs["head"],
    }
    per_token = (
        6.0 * layers * (macs["attention_projections"] + macs["router"])
        + sum(parts.values())
    )
    return {"train": seq_len * per_token, **{k: seq_len * v for k, v in parts.items()}}
