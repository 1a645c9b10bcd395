"""Operations one token of a Nemotron-H (``nemotron_h``) training step with
experts requires on this chip: forward and backward, a layer by its ONE branch
(``layer_types``: ``mamba``, ``attention``, ``moe``), causal attention at half
the square (``costs/flash_attention.py``), the state-space scan's products with
``C B^T`` once a group (``costs/ssd_scan_grouped.py``), nothing recomputed. The
routed experts (ungated: two products a row, ``costs/moe_grouped_matmul_ungated
.py``) count by the rows that were routed to the experts held here (a counter
of the program, summed over the expert layers), not by an expected share;
everything else is fixed by the shapes."""

from benchmark.costs import flash_attention, moe_grouped_matmul_ungated, ssd_scan_grouped


def layer_counts(model: dict) -> tuple[int, int, int]:
    """``(Mamba-2, attention, expert)`` layers of ``model["layer_types"]``."""
    kinds = [k.strip() for k in model["layer_types"].split(",")]
    return kinds.count("mamba"), kinds.count("attention"), kinds.count("moe")


def parts_per_token(model: dict, routed_rows_per_token: float) -> dict[str, float]:
    """Forward + backward operations a token, by part of the model."""
    d, s, v = model["d_model"], model["max_seq_len"], model["vocab_size"]
    h, kv, dh = model["n_heads"], model["n_kv_heads"], model["head_dim"]
    mh, g = model["mamba_n_heads"], model["mamba_n_groups"]
    p, n = model["mamba_d_head"], model["mamba_d_state"]
    inner, bc = mh * p, g * n
    n_mamba, n_attn, n_moe = layer_counts(model)
    # 2 forward + 4 backward operations per weight and token
    return {
        "mamba_projections": 6.0 * n_mamba * (d * (2 * inner + 2 * bc + mh) + inner * d),
        "mamba_conv": 3.0 * n_mamba * 2 * model["mamba_d_conv"] * (inner + 2 * bc),
        "ssd_scan": n_mamba * ssd_scan_grouped.training_flops(
            s, mh, g, p, n, model["mamba_chunk_size"]) / s,
        "attention_projections": 6.0 * n_attn * (d * (h + 2 * kv) * dh + h * dh * d),
        "flash_core": n_attn * flash_attention.training_flops(
            batch=1, heads=h, seq=s, d_head=dh) / s,
        "router": 6.0 * n_moe * d * model["moe_num_experts"],
        "shared_expert": 6.0 * n_moe * 2 * d * model["moe_shared_hidden_size"],
        "routed_experts": moe_grouped_matmul_ungated.training_flops(
            routed_rows_per_token, d, model["mlp_hidden_size"]),
        "head": 6.0 * d * v,  # the untied head; the embedding is a gather
    }


def flops_per_token(model: dict, routed_rows_per_token: float) -> float:
    return sum(parts_per_token(model, routed_rows_per_token).values())


def expected_routed_rows_per_token(model: dict) -> float:
    """Rows a token sends to the experts held here, summed over the expert
    layers, if routing were uniform: ``top_k * held / routed`` a layer."""
    held = model["moe_experts_held"] or model["moe_num_experts"]
    return layer_counts(model)[2] * model["moe_top_k"] * held / model["moe_num_experts"]
