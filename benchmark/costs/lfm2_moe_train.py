"""Operations one token of an LFM2 (``lfm2_moe``) training step requires on
this chip: forward and backward, causal attention at half the square in the
attention layers only, the conv layers' mix by ``costs/short_conv.py``,
nothing recomputed. The routed experts count by the rows that were routed to
the experts held here (a counter of the program, summed over both expert
stacks), not by an expected share; everything else is fixed by the shapes."""

from benchmark.costs import flash_attention, moe_grouped_matmul, short_conv


def layer_counts(model: dict) -> tuple[int, int]:
    """``(conv layers, attention layers)`` of ``model["layer_types"]``."""
    kinds = [k.strip() for k in model["layer_types"].split(",")]
    return kinds.count("conv"), kinds.count("attention")


def parts_per_token(model: dict, routed_rows_per_token: float) -> dict[str, float]:
    """Forward + backward operations a token, by part of the model."""
    d, L, nd = model["d_model"], model["n_layers"], model["first_k_dense"]
    h, kv, dh = model["n_heads"], model["n_kv_heads"], model["d_head"]
    s, v = model["max_seq_len"], model["vocab_size"]
    n_conv, n_attn = layer_counts(model)
    # 2 forward + 4 backward operations per weight and token
    return {
        "conv_projections": 6.0 * n_conv * (d * 3 * d + d * d),
        "conv_mix": n_conv * short_conv.training_flops(s, d, model["conv_kernel_size"]) / s,
        "attention_projections": 6.0 * n_attn * (d * (h + 2 * kv) * dh + h * dh * d),
        "flash_core": n_attn * flash_attention.training_flops(
            batch=1, heads=h, seq=s, d_head=dh) / s,
        "dense_mlp": 6.0 * nd * 3 * d * model["dense_mlp_hidden_size"],
        "router": 6.0 * (L - nd) * d * model["moe_num_experts"],
        "routed_experts": moe_grouped_matmul.training_flops(
            routed_rows_per_token, d, model["mlp_hidden_size"]),
        "head": 6.0 * d * v,  # the tied head; the embedding is a gather
    }


def flops_per_token(model: dict, routed_rows_per_token: float) -> float:
    return sum(parts_per_token(model, routed_rows_per_token).values())


def expected_routed_rows_per_token(model: dict) -> float:
    """Rows a token sends to the experts held here, summed over the expert
    layers, if routing were uniform: ``top_k * held / routed`` a layer."""
    held = model["moe_experts_held"] or model["moe_num_experts"]
    return ((model["n_layers"] - model["first_k_dense"]) * model["moe_top_k"]
            * held / model["moe_num_experts"])
