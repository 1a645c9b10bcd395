"""Checkpoint interop, inbound: load a HuggingFace ``LlamaForCausalLM``
checkpoint into a photon-tpu parameter tree — the warm-start path a
reference user gets from llm-foundry's ``hf_causal_lm`` wrapper (train a
public llama-family base model with the federated stack).

Inverse of :mod:`photon_tpu.checkpoint.hf_export`: torch ``Linear [out,
in]`` weights transpose back to JAX ``[in, out]`` kernels, per-layer
entries restack onto the ``[n_layers, ...]`` scan axis, and separate
q/k/v either stay separate (GQA) or fuse back into ``wqkv`` (MHA).
Reads ``model.safetensors`` or ``pytorch_model.bin`` (single-file or
indexed shards).

CLI (writes the repo's npz dump, usable anywhere ``--params-npz`` is)::

    python -m photon_tpu.checkpoint.hf_import --hf-dir /path/llama \
        --out params.npz [--config cfg.yaml]

Without ``--config``, the model config is derived from the HF
``config.json`` and printed as YAML next to the npz.
"""

from __future__ import annotations

import argparse
import json
import pathlib
from typing import Any

import numpy as np

from photon_tpu.config.schema import Config, ModelConfig, refuse_training_only_family


def model_config_from_hf(hf_cfg: dict) -> ModelConfig:
    """Derive the family knobs from an HF llama/mixtral config.json."""
    kind = hf_cfg.get("model_type")
    if kind not in ("llama", "mixtral"):
        raise ValueError(f"expected model_type=llama|mixtral, got {kind!r}")
    m = ModelConfig()
    m.name = f"{kind}-import"
    m.d_model = int(hf_cfg["hidden_size"])
    m.n_layers = int(hf_cfg["num_hidden_layers"])
    m.n_heads = int(hf_cfg["num_attention_heads"])
    n_kv = int(hf_cfg.get("num_key_value_heads", m.n_heads))
    m.n_kv_heads = 0 if n_kv == m.n_heads else n_kv
    m.max_seq_len = int(hf_cfg["max_position_embeddings"])
    m.vocab_size = int(hf_cfg["vocab_size"])
    m.mlp_hidden_size = int(hf_cfg["intermediate_size"])
    m.rope = True
    m.rope_theta = float(hf_cfg.get("rope_theta", 10000.0))
    m.learned_pos_emb = False
    m.norm = "rmsnorm"
    if kind == "mixtral":
        m.mlp = "moe"
        m.moe_mlp_act = "swiglu"
        m.moe_num_experts = int(hf_cfg["num_local_experts"])
        m.moe_top_k = int(hf_cfg.get("num_experts_per_tok", 2))
        m.moe_aux_weight = float(hf_cfg.get("router_aux_loss_coef", 0.001))
        if hf_cfg.get("sliding_window") is not None:
            # windowed attention would silently diverge from our full
            # attention past the window (Mixtral-8x7B ships null here)
            raise ValueError(
                f"sliding_window={hf_cfg['sliding_window']} is unsupported — "
                "only full-attention mixtral checkpoints import faithfully"
            )
        # Mixtral routes without capacity; a drop-free factor (E/k) keeps
        # the imported model's forward equal to HF's
        m.moe_capacity_factor = m.moe_num_experts / m.moe_top_k
    else:
        m.mlp = "swiglu"
    m.tie_embeddings = bool(hf_cfg.get("tie_word_embeddings", False))
    if m.tie_embeddings:
        raise ValueError("tied-embedding llama checkpoints are not supported yet")
    if hf_cfg.get("attention_bias") or hf_cfg.get("mlp_bias"):
        raise ValueError("biased llama checkpoints are not supported (no_bias)")
    if hf_cfg.get("head_dim") and int(hf_cfg["head_dim"]) != m.d_model // m.n_heads:
        raise ValueError(
            f"head_dim {hf_cfg['head_dim']} != d_model/n_heads "
            f"{m.d_model // m.n_heads} — decoupled head_dim is unsupported"
        )
    if hf_cfg.get("rope_scaling"):
        # llama3/linear/dynamic scaling changes the frequencies; importing
        # with plain-theta rope would silently diverge from HF
        raise ValueError(
            f"rope_scaling={hf_cfg['rope_scaling']} is unsupported — "
            "only plain rope_theta checkpoints import faithfully"
        )
    m.norm_eps = float(hf_cfg.get("rms_norm_eps", 1.0e-5))
    return m


def _load_state_dict(hf_dir: pathlib.Path) -> dict:
    """Weights from safetensors (preferred) or torch .bin, sharded or not."""
    def load_one(p: pathlib.Path) -> dict:
        if p.suffix == ".safetensors":
            from safetensors.numpy import load_file

            return dict(load_file(str(p)))
        import torch

        sd = torch.load(str(p), map_location="cpu", weights_only=True)
        # .float() first: bf16 tensors have no direct numpy dtype, and the
        # tree is cast to fp32 downstream anyway
        return {k: v.float().numpy() for k, v in sd.items()}

    for index_name in ("model.safetensors.index.json", "pytorch_model.bin.index.json"):
        idx = hf_dir / index_name
        if idx.exists():
            shards = sorted(set(json.loads(idx.read_text())["weight_map"].values()))
            out: dict = {}
            for s in shards:
                out.update(load_one(hf_dir / s))
            return out
    for name in ("model.safetensors", "pytorch_model.bin"):
        p = hf_dir / name
        if p.exists():
            return load_one(p)
    raise FileNotFoundError(f"no weights found under {hf_dir}")


def llama_params_from_hf(sd: dict, cfg: ModelConfig) -> Any:
    """HF llama state dict → photon-tpu param tree (fp32 numpy leaves)."""
    refuse_training_only_family(cfg, "HF import (no import map)")

    def t(key: str) -> np.ndarray:  # torch [out, in] -> jax [in, out]
        return np.ascontiguousarray(np.asarray(sd[key]).T.astype(np.float32))

    def w(key: str) -> np.ndarray:
        return np.asarray(sd[key]).astype(np.float32)

    L = cfg.n_layers
    n_kv = cfg.n_kv_heads or cfg.n_heads

    def stack(fmt: str, transpose: bool = True) -> np.ndarray:
        fn = t if transpose else w
        return np.stack([fn(fmt.format(i=i)) for i in range(L)])

    block: dict = {
        "out_proj": {"kernel": stack("model.layers.{i}.self_attn.o_proj.weight")},
        "ln_1": {"scale": stack("model.layers.{i}.input_layernorm.weight", False)},
        "ln_2": {"scale": stack("model.layers.{i}.post_attention_layernorm.weight", False)},
    }
    if cfg.mlp == "moe":
        # Mixtral block_sparse_moe: gate=router, experts w1/w3/w2
        E = cfg.moe_num_experts
        block["router"] = stack("model.layers.{i}.block_sparse_moe.gate.weight")
        for ours, theirs in (("moe_gate", "w1"), ("moe_up", "w3"),
                             ("moe_down", "w2")):
            block[ours] = np.stack([
                np.stack([t(f"model.layers.{i}.block_sparse_moe.experts.{e}."
                            f"{theirs}.weight") for e in range(E)])
                for i in range(L)
            ])
    else:
        block["gate_proj"] = {"kernel": stack("model.layers.{i}.mlp.gate_proj.weight")}
        block["up_proj"] = {"kernel": stack("model.layers.{i}.mlp.up_proj.weight")}
        block["down_proj"] = {"kernel": stack("model.layers.{i}.mlp.down_proj.weight")}
    q = stack("model.layers.{i}.self_attn.q_proj.weight")
    k = stack("model.layers.{i}.self_attn.k_proj.weight")
    v = stack("model.layers.{i}.self_attn.v_proj.weight")
    if n_kv == cfg.n_heads:
        # MHA: fuse back into the wqkv layout the model uses
        block["wqkv"] = {"kernel": np.concatenate([q, k, v], axis=-1)}
    else:
        block["q_proj"] = {"kernel": q}
        block["k_proj"] = {"kernel": k}
        block["v_proj"] = {"kernel": v}

    return {
        "wte": {"embedding": w("model.embed_tokens.weight")},
        "blocks": {"block": block},
        "ln_f": {"scale": w("model.norm.weight")},
        "lm_head": {"kernel": t("lm_head.weight")},
    }


def load_hf_llama(hf_dir: str, cfg: ModelConfig | None = None) -> tuple[ModelConfig, Any]:
    """(model_config, params) from an HF llama directory."""
    d = pathlib.Path(hf_dir)
    hf_cfg = json.loads((d / "config.json").read_text())
    derived = model_config_from_hf(hf_cfg)
    if cfg is not None:
        for field in ("d_model", "n_layers", "n_heads", "vocab_size",
                      "n_kv_heads", "mlp_hidden_size", "mlp",
                      "moe_num_experts", "moe_top_k", "moe_mlp_act"):
            if getattr(cfg, field) != getattr(derived, field):
                raise ValueError(
                    f"config mismatch on {field}: yours={getattr(cfg, field)} "
                    f"checkpoint={getattr(derived, field)}"
                )
        derived = cfg
    return derived, llama_params_from_hf(_load_state_dict(d), derived)


def main(argv: list[str] | None = None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    ap.add_argument("--hf-dir", required=True)
    ap.add_argument("--out", required=True, help="output params npz path")
    ap.add_argument("--config", help="optional photon-tpu config yaml to check against")
    args = ap.parse_args(argv)

    # host-side tensor renaming only — never take a chip
    import jax

    jax.config.update("jax_platforms", "cpu")

    from photon_tpu.checkpoint import arrays_to_npz
    from photon_tpu.codec import params_to_ndarrays

    cfg = Config.from_yaml(args.config).validate().model if args.config else None
    model_cfg, params = load_hf_llama(args.hf_dir, cfg)
    meta, arrays = params_to_ndarrays(params)
    out = pathlib.Path(args.out)
    out.write_bytes(arrays_to_npz(meta, arrays))
    yaml_path = out.with_suffix(".model.yaml")
    full = Config()
    full.model = model_cfg
    full.to_yaml(str(yaml_path))
    print(json.dumps({
        "out": str(out), "model_yaml": str(yaml_path),
        "n_arrays": meta.n_arrays,
        "n_params": int(sum(int(np.prod(a.shape)) for a in arrays)),
    }))


if __name__ == "__main__":
    main()
