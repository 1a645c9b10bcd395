"""Operations one token of a Keye-VL-2.0-30B-A3B (``KeyeVL2``) language-model
training step requires on this chip: forward and backward, nothing
recomputed. Attention counts the (query, key) pairs the indexers picked and
the routed experts the rows that were routed to the experts held here (two
counters of the program), not expected shares; everything else is fixed by
the shapes.

The indexer's three projections read a detached input: forward and the
weight's gradient, no input gradient (4 operations a weight and token where
the others have 6). The alignment loss needs the heads' probabilities over
the picked pairs, which the attention's forward already forms: no operation
of its own is required.
"""

from benchmark.costs import dsa_indexer, moe_grouped_matmul, sparse_attention


def parts_per_token(model: dict, routed_rows_per_token: float,
                    picked_pairs_per_token: float) -> dict[str, float]:
    """Forward + backward operations a token, by part of the model. The two
    counts are summed over the layers."""
    d, L, s, v = model["d_model"], model["n_layers"], model["max_seq_len"], model["vocab_size"]
    h, g, dh = model["n_heads"], model["n_kv_heads"], model["head_dim"]
    j, di = model["dsa_index_heads"], model["dsa_index_head_dim"]
    causal_pairs_per_token = L * (s + 1) / 2.0
    # 2 forward + 4 backward operations per weight and token
    return {
        "attention_projections": 6.0 * L * (d * (h + 2 * g) * dh + h * dh * d),
        "indexer_projections": 4.0 * L * d * (j * di + di + j),
        "index_scores": dsa_indexer.training_flops(
            causal_pairs_per_token, picked_pairs_per_token, j, di),
        "sparse_attention": sparse_attention.training_flops(picked_pairs_per_token, h, dh),
        "router": 6.0 * L * d * model["moe_num_experts"],
        "routed_experts": moe_grouped_matmul.training_flops(
            routed_rows_per_token, d, model["mlp_hidden_size"]),
        "head": 6.0 * d * v,  # the untied head; the embedding is a gather
    }


def flops_per_token(model: dict, routed_rows_per_token: float,
                    picked_pairs_per_token: float) -> float:
    return sum(parts_per_token(model, routed_rows_per_token,
                               picked_pairs_per_token).values())


def expected_routed_rows_per_token(model: dict) -> float:
    """Rows a token sends to the experts held here, summed over the layers,
    if routing were uniform: ``top_k * held / routed`` a layer."""
    held = model["moe_experts_held"] or model["moe_num_experts"]
    return model["n_layers"] * model["moe_top_k"] * held / model["moe_num_experts"]


def expected_picked_pairs_per_token(model: dict) -> float:
    """Pairs a token's query picks, summed over the layers, without ties:
    ``min(t + 1, topk)`` at position ``t``, averaged over a row."""
    s, k = model["max_seq_len"], min(model["dsa_topk"], model["max_seq_len"])
    return model["n_layers"] * (k * (k + 1) / 2.0 + (s - k) * k) / s
