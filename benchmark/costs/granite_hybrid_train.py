"""Operations one token of a Granite-4.0-H (``granitemoehybrid``, dense)
training step requires on this chip: forward and backward, causal attention at
half the square, the state-space scan's products by ``costs/ssd_scan.py``,
nothing recomputed. Everything is fixed by the shapes."""

from benchmark.costs import flash_attention, ssd_scan


def layer_counts(model: dict) -> tuple[int, int]:
    """``(Mamba-2 layers, attention layers)`` of ``model["layer_types"]``."""
    kinds = [k.strip() for k in model["layer_types"].split(",")]
    return kinds.count("mamba"), kinds.count("attention")


def parts_per_token(model: dict) -> dict[str, float]:
    """Forward + backward operations a token, by part of the model."""
    d, s, v = model["d_model"], model["max_seq_len"], model["vocab_size"]
    h, kv, dh = model["n_heads"], model["n_kv_heads"], model["d_head"]
    mh, p, n = model["mamba_n_heads"], model["mamba_d_head"], model["mamba_d_state"]
    inner = mh * p
    n_mamba, n_attn = layer_counts(model)
    # 2 forward + 4 backward operations per weight and token
    return {
        "mamba_projections": 6.0 * n_mamba * (d * (2 * inner + 2 * n + mh) + inner * d),
        "mamba_conv": 3.0 * n_mamba * 2 * model["mamba_d_conv"] * (inner + 2 * n),
        "ssd_scan": n_mamba * ssd_scan.training_flops(
            s, mh, p, n, model["mamba_chunk_size"]) / s,
        "attention_projections": 6.0 * n_attn * (d * (h + 2 * kv) * dh + h * dh * d),
        "flash_core": n_attn * flash_attention.training_flops(
            batch=1, heads=h, seq=s, d_head=dh) / s,
        "mlp": 6.0 * (n_mamba + n_attn) * 3 * d * model["mlp_hidden_size"],
        "head": 6.0 * d * v,  # the tied head; the embedding is a gather
    }


def flops_per_token(model: dict) -> float:
    return sum(parts_per_token(model).values())
