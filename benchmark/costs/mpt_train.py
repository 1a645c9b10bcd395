"""Operations one token of an MPT training step requires: forward and
backward, causal attention at half the square, nothing recomputed."""

from benchmark.costs import flash_attention


def flops_per_token(model: dict) -> float:
    d, L = model["d_model"], model["n_layers"]
    s, v = model["max_seq_len"], model["vocab_size"]
    hidden = model["expansion_ratio"] * d
    weights = L * (3 * d * d + d * d + 2 * d * hidden) + d * v  # tied head counted once
    dense = 6.0 * weights  # 2 forward + 4 backward per weight
    attention = L * flash_attention.training_flops(
        batch=1, heads=model["n_heads"], seq=s, d_head=model["d_head"]) / s
    return dense + attention
