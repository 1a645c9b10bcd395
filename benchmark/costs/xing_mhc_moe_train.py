"""Operations one token of a Xing4.0-29B-A4B (``xing4_0``) training step
requires on this chip: forward and backward, causal attention at half the
square with q / k and v at their own widths (``costs/flash_attention_dv.py``),
the hyper-connections' maps (one projection of all ``n`` streams to ``2n +
n^2`` numbers a sublayer) and their mixing (``costs/mhc_mix.py``), nothing
recomputed. The routed experts count by the rows that were routed to the
experts held here (a counter of the program), not by an expected share;
everything else is fixed by the shapes."""

from benchmark.costs import flash_attention_dv, mhc_mix, moe_grouped_matmul


def parts_per_token(model: dict, routed_rows_per_token: float) -> dict[str, float]:
    """Forward + backward operations a token, by part of the model."""
    d, L, nd = model["d_model"], model["n_layers"], model["first_k_dense"]
    h, s, v = model["n_heads"], model["max_seq_len"], model["vocab_size"]
    nope, rope, dv = (model["qk_nope_head_dim"], model["qk_rope_head_dim"],
                      model["v_head_dim"])
    rq, rkv = model["q_lora_rank"], model["kv_lora_rank"]
    fe, n = model["mlp_hidden_size"], model["hc_mult"]
    low_rank = (d * rq + rq * h * (nope + rope) + d * (rkv + rope)
                + rkv * h * (nope + dv) + h * dv * d)
    # 2 forward + 4 backward operations per weight and token
    return {
        "low_rank_projections": 6.0 * L * low_rank,
        "flash_core": L * flash_attention_dv.training_flops(
            batch=1, heads=h, seq=s, d_qk=nope + rope, d_v=dv) / s,
        "dense_mlp": 6.0 * nd * 3 * d * model["dense_mlp_hidden_size"],
        "router": 6.0 * (L - nd) * d * model["moe_num_experts"],
        "shared_expert": 6.0 * (L - nd) * model["moe_shared_experts"] * 3 * d * fe,
        "routed_experts": moe_grouped_matmul.training_flops(
            routed_rows_per_token, d, fe),
        "hyper_connection_maps": 6.0 * 2 * L * n * d * (2 * n + n * n),
        "hyper_connection_mix": 2 * L * mhc_mix.training_flops(1, d, n),
        "head": 6.0 * d * v,  # the untied head; the embedding is a gather
    }


def flops_per_token(model: dict, routed_rows_per_token: float) -> float:
    return sum(parts_per_token(model, routed_rows_per_token).values())


def expected_routed_rows_per_token(model: dict) -> float:
    """Rows a token sends to the experts held here, summed over the expert
    layers, if routing were uniform: ``top_k * held / routed`` a layer."""
    held = model["moe_experts_held"] or model["moe_num_experts"]
    return ((model["n_layers"] - model["first_k_dense"]) * model["moe_top_k"]
            * held / model["moe_num_experts"])
