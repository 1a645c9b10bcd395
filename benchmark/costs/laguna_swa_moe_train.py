"""Operations one token of a Laguna-XS.2 (``laguna``) training step requires on
this chip: forward and backward, every attention layer by its kind (a
``full_attention`` layer's 48 heads at the causal half of the square,
``costs/flash_attention.py``; a ``sliding_attention`` layer's 64 heads over
the band, ``costs/flash_attention_window.py``), the headwise gate's ``[D, H]``
product, nothing recomputed. The routed experts count by the rows that were
routed to the experts held here (a counter of the program, summed over both
expert stacks), not by an expected share; everything else is fixed by the
shapes."""

from benchmark.costs import flash_attention, flash_attention_window, moe_grouped_matmul

SLIDING = "sliding_attention"


def layer_counts(model: dict) -> tuple[int, int]:
    """``(full layers, sliding layers)`` of ``model["layer_types"]``."""
    kinds = [k.strip() for k in model["layer_types"].split(",")]
    return len(kinds) - kinds.count(SLIDING), kinds.count(SLIDING)


def parts_per_token(model: dict, routed_rows_per_token: float) -> dict[str, float]:
    """Forward + backward operations a token, by part of the model."""
    d, L, nd = model["d_model"], model["n_layers"], model["first_k_dense"]
    h_full, h_swa = model["n_heads"], model["swa_n_heads"] or model["n_heads"]
    kv, dh = model["n_kv_heads"], model["d_head"]
    s, v, fe = model["max_seq_len"], model["vocab_size"], model["mlp_hidden_size"]
    n_full, n_swa = layer_counts(model)
    heads = n_full * h_full + n_swa * h_swa  # query heads, all layers
    # 2 forward + 4 backward operations per weight and token
    return {
        "attention_projections": 6.0 * (heads * 2 + L * 2 * kv) * d * dh,
        "attention_gate": 6.0 * heads * d if model.get("attn_gate") else 0.0,
        "flash_full": n_full * flash_attention.training_flops(
            batch=1, heads=h_full, seq=s, d_head=dh) / s,
        "flash_band": n_swa * flash_attention_window.training_flops(
            batch=1, heads=h_swa, seq=s, d_head=dh, window=model["sliding_window"]) / s,
        "dense_mlp": 6.0 * nd * 3 * d * model["dense_mlp_hidden_size"],
        "router": 6.0 * (L - nd) * d * model["moe_num_experts"],
        "shared_expert": 6.0 * (L - nd) * model["moe_shared_experts"] * 3 * d * fe,
        "routed_experts": moe_grouped_matmul.training_flops(routed_rows_per_token, d, fe),
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
