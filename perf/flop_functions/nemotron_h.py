"""Model FLOPs of the ``nemotron_h`` hybrid stack (Mamba-2 / routed experts
with a shared expert / grouped-query attention, one mixer a layer), from
shapes.  Training counts the forward pass once and the backward pass twice
(3x forward); recomputation is never counted.  One multiply-accumulate is 2
FLOPs.  Of the routed experts only what the experts HELD here compute at a
balanced load is counted: ``k * held / all`` experts a token."""

from __future__ import annotations


def macs_per_token(spec: dict) -> dict:
    """Multiply-accumulates a token in ONE layer of the part's kind (the
    head: once), forward."""
    d = spec["d_model"]
    inner = spec["mamba_heads"] * spec["mamba_head_dim"]
    conv_width = inner + 2 * spec["ssm_groups"] * spec["ssm_state"]
    attention_width = spec["heads"] * spec["head_dim"]
    kv_width = spec["kv_heads"] * spec["head_dim"]
    chunk, states = spec["chunk"], spec["ssm_state"]
    return {
        # in_proj to [z | xBC | dt], the k-tap depthwise convolution, out_proj
        "mamba_projections": d * (inner + conv_width + spec["mamba_heads"])
        + spec["conv_kernel"] * conv_width + inner * d,
        # a chunk's products, per token: C B^T once a group; the masked
        # product with x, the chunk's state and the states' part of y a head
        "ssd": spec["ssm_groups"] * chunk * states
        + spec["mamba_heads"] * spec["mamba_head_dim"] * (chunk + 2 * states),
        "attention_projections": d * (2 * attention_width + 2 * kv_width),
        "shared_expert": 2 * d * spec["shared_width"],
        # two matrices an expert (up, down: relu^2 has no gate)
        "experts": spec["experts_per_token"] * spec["experts_held"]
        / spec["num_experts"] * 2 * d * spec["expert_width"],
        "router": d * spec["num_experts"],
        "head": d * spec["vocab"],
    }


LAYER_PARTS = {
    "M": ("mamba_projections", "ssd"),
    "*": ("attention_projections",),
    "E": ("shared_expert", "experts", "router"),
}


def per_record(spec: dict, traffic: dict) -> dict:
    """A record is one sequence of the traffic file's ``seq_len``.
    ``causal_attention`` is ``6 * T * heads * head_dim`` a token and ``*``
    layer, as in ``transformer_lm`` (the flash backward's recomputed scores
    are not counted); ``ssd`` is what the two scan kernels compute between
    them, ``experts`` what the three grouped-matmul kernels do."""
    seq_len = traffic["records"]["seq_len"]
    pattern = spec["pattern"]
    macs = macs_per_token(spec)
    parts = {
        name: 6.0 * pattern.count(kind) * macs[name]
        for kind, names in LAYER_PARTS.items() for name in names
    }
    parts["head"] = 6.0 * macs["head"]
    parts["causal_attention"] = (
        6.0 * pattern.count("*") * seq_len * spec["heads"] * spec["head_dim"]
    )
    return {
        "train": seq_len * sum(parts.values()),
        **{k: seq_len * v for k, v in parts.items()},
    }
