"""Model FLOPs of ``joyai_llm_flash`` (DeepSeek-V3's layers: multi-head
latent attention, a leading dense SwiGLU layer, sigmoid-routed SwiGLU experts
with a shared expert, multi-token-prediction modules that share embedding and
head), from shapes.  Training counts the forward pass once and the backward
pass twice (3x forward); recomputation is never counted.  One
multiply-accumulate is 2 FLOPs.  Of the routed experts only what the experts
HELD here compute at a balanced load is counted: ``k * held / all`` experts a
token.  A multi-token-prediction module is one more attention part, one more
expert part, its projection and one more pass through the head."""

from __future__ import annotations


def macs_per_token(spec: dict) -> dict:
    """Multiply-accumulates a token in ONE layer's part (``head``: one pass;
    ``mtp_projection``: one module), forward."""
    d, heads = spec["d_model"], spec["heads"]
    qk = spec["qk_nope_head_dim"] + spec["qk_rope_head_dim"]
    return {
        # q down and up, kv down (latent and the one rotary key), kv up to
        # [k_nope | v], the output projection
        "attention_projections": d * spec["q_lora_rank"]
        + spec["q_lora_rank"] * heads * qk
        + d * (spec["kv_lora_rank"] + spec["qk_rope_head_dim"])
        + spec["kv_lora_rank"] * heads
        * (spec["qk_nope_head_dim"] + spec["v_head_dim"])
        + heads * spec["v_head_dim"] * d,
        "dense_mlp": 3 * d * spec["dense_width"],
        "shared_expert": 3 * d * spec["shared_width"],
        "experts": spec["experts_per_token"] * spec["experts_held"]
        / spec["num_experts"] * 3 * d * spec["expert_width"],
        "router": d * spec["num_experts"],
        "mtp_projection": 2 * d * d,
        "head": d * spec["vocab"],
    }


def per_record(spec: dict, traffic: dict) -> dict:
    """A record is one sequence of the traffic file's ``seq_len``.
    ``causal_attention`` is ``3 * T * heads * (d_qk + d_v)`` a token and
    attention part: the scores over ``d_qk``, the values over ``d_v``, half
    the square visible, so each of the three flash kernels is still a third
    (the flash backward's recomputed scores are not counted); ``experts`` is
    what the three grouped-matmul kernels do at a balanced load."""
    seq_len = traffic["records"]["seq_len"]
    modules = spec["mtp_modules"]
    expert_parts = spec["expert_layers"] + modules
    attention_parts = spec["dense_layers"] + expert_parts
    macs = macs_per_token(spec)
    counts = {
        "attention_projections": attention_parts,
        "dense_mlp": spec["dense_layers"],
        "shared_expert": expert_parts,
        "experts": expert_parts,
        "router": expert_parts,
        "mtp_projection": modules,
        "head": 1 + modules,
    }
    parts = {name: 6.0 * count * macs[name] for name, count in counts.items()}
    parts["causal_attention"] = (
        3.0 * attention_parts * seq_len * spec["heads"]
        * (spec["qk_nope_head_dim"] + spec["qk_rope_head_dim"] + spec["v_head_dim"])
    )
    return {
        "train": seq_len * sum(parts.values()),
        **{k: seq_len * v for k, v in parts.items()},
    }
