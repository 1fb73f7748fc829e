"""Model FLOPs of the dense decoder LM, from shapes.  Training counts the
forward pass once and the backward pass twice (3x forward); recomputation
is never counted.  One multiply-accumulate is 2 FLOPs."""

from __future__ import annotations


def train_flops_per_token(
    layers: int, d_model: int, vocab: int, seq_len: int
) -> float:
    """``6*(12*L*d^2 + d*V) + 6*L*T*d``: per token, the block matmuls
    (QKV 3d^2, attention output d^2, MLP 8d^2) and the untied head d*V at
    2 FLOPs a MAC times 3 for training, plus causal attention (below).
    The embedding is a gather and is not counted."""
    dense = 6.0 * (12.0 * layers * d_model * d_model + d_model * vocab)
    return dense + causal_attention_train_flops_per_token(
        layers, d_model, seq_len
    )


def causal_attention_train_flops_per_token(
    layers: int, d_model: int, seq_len: int
) -> float:
    """``6*L*T*d`` a token (``6*L*B*T^2*d`` a step).  Forward QK^T and PV
    over the causal half are 2*T*d a token and layer; the backward's dQ, dK,
    dV and dP are twice that.  The flash backward also recomputes the score
    matrix (another ~T*d): recomputation, so not counted."""
    return 6.0 * layers * seq_len * d_model


def per_record(spec: dict, traffic: dict) -> dict:
    """A record is one sequence of the traffic file's ``seq_len``."""
    seq_len = traffic["records"]["seq_len"]
    layers, d_model = spec["layers"], spec["d_model"]
    return {
        "train": seq_len
        * train_flops_per_token(layers, d_model, spec["vocab"], seq_len),
        "causal_attention": seq_len
        * causal_attention_train_flops_per_token(layers, d_model, seq_len),
    }
