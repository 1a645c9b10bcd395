"""Checkpoint interop: export photon-tpu parameters to torch ecosystems.

Two targets, matching where a reference user's checkpoints live:

- **llama** — a HuggingFace ``LlamaForCausalLM`` directory (``config.json``
  + ``pytorch_model.bin``) loadable by ``transformers`` with no custom
  code. The llama-family knobs (RoPE rotate-half, RMSNorm, SwiGLU, GQA,
  untied head) map onto HF's implementation exactly, so exported logits
  match to float tolerance (``tests/test_hf_export.py``). This unlocks
  lighteval/vLLM/HF-eval workflows on trained checkpoints
  (``eval/configs/lighteval/``).
- **mpt-foundry** — a state dict in llm-foundry's MPT naming
  (``model.transformer.blocks.{i}.attn.Wqkv.weight`` ...), the layout the
  reference trains and checkpoints (its Composer checkpoints store this
  module tree; ``photon/clients/utils.py:739-868`` walks it). Includes the
  learned ``wpe`` that HF's Mpt port lacks. Intended for migrating weights
  back INTO the reference stack; note the GELU variant differs (foundry
  uses exact gelu, this repo tanh-approximate), so expect ~1e-3-level
  activation deltas, not bit equality.

Dense kernels are stored ``[in, out]`` here (JAX convention) and
transposed to torch's ``Linear [out, in]``; the stacked ``[n_layers, ...]``
scan axis is unstacked into per-layer entries.

CLI::

    python -m photon_tpu.checkpoint.hf_export --params-npz params_final.npz \
        --preset llama-1b --out /tmp/hf_llama [--format llama|mpt-foundry]
"""

from __future__ import annotations

import argparse
import json
import pathlib
from typing import Any

import numpy as np

from photon_tpu.config.schema import Config, ModelConfig, refuse_training_only_family


def _t(arr: np.ndarray) -> "Any":
    """JAX Dense kernel [in, out] → torch Linear weight [out, in]."""
    import torch

    # ascontiguousarray of the transpose already copies; no second copy
    return torch.from_numpy(np.ascontiguousarray(np.asarray(arr).T))


def _w(arr: np.ndarray) -> "Any":
    import torch

    a = np.ascontiguousarray(np.asarray(arr))
    if not a.flags.writeable:  # torch.from_numpy requires writable memory
        a = a.copy()
    return torch.from_numpy(a)


def _hf_llama_family_common(params: Any, cfg: ModelConfig, kind: str,
                            mlp_emit) -> dict:
    """Embed/attention/norm/head tensors shared by the llama and mixtral
    exporters (HF ``model.layers.{i}`` naming); ``mlp_emit(sd, prefix, i)``
    fills in each layer's MLP block."""
    if not cfg.rope or cfg.norm != "rmsnorm":
        raise ValueError(
            f"{kind} export needs rope=true, norm=rmsnorm "
            f"(got rope={cfg.rope}, norm={cfg.norm})"
        )
    if cfg.tie_embeddings:
        raise ValueError(f"{kind} export expects tie_embeddings=false")
    if not cfg.no_bias:
        # trained bias tensors would be silently zero-initialized by
        # from_pretrained (missing keys only warn) — refuse instead
        raise ValueError(f"{kind} export supports no_bias=true configs only")
    blocks = params["blocks"]["block"]
    sd: dict = {"model.embed_tokens.weight": _w(params["wte"]["embedding"])}
    for i in range(cfg.n_layers):
        p = f"model.layers.{i}."
        if "wqkv" in blocks:  # fused MHA layout
            wqkv = np.asarray(blocks["wqkv"]["kernel"][i])  # [D, 3D]
            q, k, v = np.split(wqkv, 3, axis=-1)
        else:
            q = np.asarray(blocks["q_proj"]["kernel"][i])
            k = np.asarray(blocks["k_proj"]["kernel"][i])
            v = np.asarray(blocks["v_proj"]["kernel"][i])
        sd[p + "self_attn.q_proj.weight"] = _t(q)
        sd[p + "self_attn.k_proj.weight"] = _t(k)
        sd[p + "self_attn.v_proj.weight"] = _t(v)
        sd[p + "self_attn.o_proj.weight"] = _t(blocks["out_proj"]["kernel"][i])
        mlp_emit(sd, p, i)
        sd[p + "input_layernorm.weight"] = _w(blocks["ln_1"]["scale"][i])
        sd[p + "post_attention_layernorm.weight"] = _w(blocks["ln_2"]["scale"][i])
    sd["model.norm.weight"] = _w(params["ln_f"]["scale"])
    sd["lm_head.weight"] = _t(params["lm_head"]["kernel"])
    return sd


def llama_state_dict(params: Any, cfg: ModelConfig) -> dict:
    """HF ``LlamaForCausalLM`` state dict from a llama-family param tree."""
    refuse_training_only_family(cfg, "HF llama export (no export map)")
    if cfg.mlp != "swiglu":
        raise ValueError(f"llama export needs mlp=swiglu (got mlp={cfg.mlp})")
    blocks = params["blocks"]["block"]

    def mlp(sd, p, i):
        sd[p + "mlp.gate_proj.weight"] = _t(blocks["gate_proj"]["kernel"][i])
        sd[p + "mlp.up_proj.weight"] = _t(blocks["up_proj"]["kernel"][i])
        sd[p + "mlp.down_proj.weight"] = _t(blocks["down_proj"]["kernel"][i])

    return _hf_llama_family_common(params, cfg, "llama", mlp)


def llama_hf_config(cfg: ModelConfig, bos_token_id: int = 0,
                    eos_token_id: int = 0) -> dict:
    """HF config dict. ``bos/eos_token_id`` default to 0 (the NeoX-style
    ``<|endoftext|>`` id this repo's vocab convention uses) — pass the real
    ids for your tokenizer, and ship tokenizer files alongside the export
    before running generation-based evals (no tokenizer is bundled)."""
    hidden = cfg.mlp_hidden_size or cfg.expansion_ratio * cfg.d_model
    return {
        "bos_token_id": bos_token_id,
        "eos_token_id": eos_token_id,
        "architectures": ["LlamaForCausalLM"],
        "model_type": "llama",
        "hidden_size": cfg.d_model,
        "intermediate_size": hidden,
        "num_hidden_layers": cfg.n_layers,
        "num_attention_heads": cfg.n_heads,
        "num_key_value_heads": cfg.n_kv_heads or cfg.n_heads,
        "head_dim": cfg.d_head,
        "max_position_embeddings": cfg.max_seq_len,
        "vocab_size": cfg.vocab_size,
        "rope_theta": cfg.rope_theta,
        "rms_norm_eps": cfg.norm_eps,
        "hidden_act": "silu",
        "attention_bias": not cfg.no_bias,
        "mlp_bias": not cfg.no_bias,
        "tie_word_embeddings": False,
        "torch_dtype": "float32",
    }


def mixtral_state_dict(params: Any, cfg: ModelConfig) -> dict:
    """HF ``MixtralForCausalLM`` state dict from a llama-family MoE tree.

    Layout match: photon-tpu's SwiGLU experts (``moe_gate``/``moe_up``/
    ``moe_down``) are exactly Mixtral's w1/w3/w2, and the router is
    ``block_sparse_moe.gate``. Routing math matches too (softmax → top-k →
    renormalize); Mixtral has no capacity concept, so exact logit parity
    needs a capacity_factor ≥ E/top_k (drop-free routing) — the exporter
    does not enforce that, it is a property of the eval batch.
    """
    refuse_training_only_family(cfg, "HF mixtral export (no export map)")
    if cfg.mlp != "moe" or cfg.moe_mlp_act != "swiglu":
        raise ValueError(
            "mixtral export needs mlp='moe' with moe_mlp_act='swiglu' "
            f"(got mlp={cfg.mlp}, moe_mlp_act={cfg.moe_mlp_act})"
        )
    drop_free = cfg.moe_num_experts / cfg.moe_top_k
    if cfg.moe_capacity_factor < drop_free:
        import warnings

        warnings.warn(
            f"mixtral export: moe_capacity_factor={cfg.moe_capacity_factor} "
            f"< moe_num_experts/moe_top_k={drop_free:g}: this model was "
            "trained with capacity-dropped routing, but HF Mixtral routes "
            "drop-free — exported logits will diverge from training-time "
            "behavior on batches that overflow expert capacity",
            stacklevel=2,
        )
    blocks = params["blocks"]["block"]

    def mlp(sd, p, i):
        sd[p + "block_sparse_moe.gate.weight"] = _t(blocks["router"][i])
        for e in range(cfg.moe_num_experts):
            ep = p + f"block_sparse_moe.experts.{e}."
            sd[ep + "w1.weight"] = _t(blocks["moe_gate"][i, e])
            sd[ep + "w3.weight"] = _t(blocks["moe_up"][i, e])
            sd[ep + "w2.weight"] = _t(blocks["moe_down"][i, e])

    return _hf_llama_family_common(params, cfg, "mixtral", mlp)


def mixtral_hf_config(cfg: ModelConfig, bos_token_id: int = 0,
                      eos_token_id: int = 0) -> dict:
    hidden = cfg.mlp_hidden_size or cfg.expansion_ratio * cfg.d_model
    return {
        "bos_token_id": bos_token_id,
        "eos_token_id": eos_token_id,
        "architectures": ["MixtralForCausalLM"],
        "model_type": "mixtral",
        "hidden_size": cfg.d_model,
        "intermediate_size": hidden,
        "num_hidden_layers": cfg.n_layers,
        "num_attention_heads": cfg.n_heads,
        "num_key_value_heads": cfg.n_kv_heads or cfg.n_heads,
        "head_dim": cfg.d_head,
        "max_position_embeddings": cfg.max_seq_len,
        "vocab_size": cfg.vocab_size,
        "rope_theta": cfg.rope_theta,
        "rms_norm_eps": cfg.norm_eps,
        "num_local_experts": cfg.moe_num_experts,
        "num_experts_per_tok": cfg.moe_top_k,
        "router_aux_loss_coef": cfg.moe_aux_weight,
        "hidden_act": "silu",
        "attention_bias": False,
        "tie_word_embeddings": False,
        "torch_dtype": "float32",
    }


def save_hf_mixtral(params: Any, cfg: ModelConfig, out_dir: str,
                    bos_token_id: int = 0, eos_token_id: int = 0) -> pathlib.Path:
    import torch

    out = pathlib.Path(out_dir)
    out.mkdir(parents=True, exist_ok=True)
    (out / "config.json").write_text(
        json.dumps(mixtral_hf_config(cfg, bos_token_id, eos_token_id), indent=2)
    )
    torch.save(mixtral_state_dict(params, cfg), out / "pytorch_model.bin")
    return out


def foundry_mpt_state_dict(params: Any, cfg: ModelConfig) -> dict:
    """llm-foundry MPT naming (the reference's checkpoint module tree)."""
    refuse_training_only_family(cfg, "HF mpt-foundry export (no export map)")
    if cfg.rope or cfg.norm != "layernorm" or cfg.mlp != "gelu":
        raise ValueError("mpt-foundry export is for the MPT family config")
    blocks = params["blocks"]["block"]
    pre = "model.transformer."
    sd: dict = {pre + "wte.weight": _w(params["wte"]["embedding"])}
    if "wpe" in params:
        sd[pre + "wpe.weight"] = _w(params["wpe"])
    for i in range(cfg.n_layers):
        p = f"{pre}blocks.{i}."
        sd[p + "attn.Wqkv.weight"] = _t(blocks["wqkv"]["kernel"][i])
        sd[p + "attn.out_proj.weight"] = _t(blocks["out_proj"]["kernel"][i])
        sd[p + "ffn.up_proj.weight"] = _t(blocks["up_proj"]["kernel"][i])
        sd[p + "ffn.down_proj.weight"] = _t(blocks["down_proj"]["kernel"][i])
        sd[p + "norm_1.weight"] = _w(blocks["ln_1"]["scale"][i])
        sd[p + "norm_2.weight"] = _w(blocks["ln_2"]["scale"][i])
        if not cfg.no_bias:
            sd[p + "attn.Wqkv.bias"] = _w(blocks["wqkv"]["bias"][i])
            sd[p + "attn.out_proj.bias"] = _w(blocks["out_proj"]["bias"][i])
            sd[p + "ffn.up_proj.bias"] = _w(blocks["up_proj"]["bias"][i])
            sd[p + "ffn.down_proj.bias"] = _w(blocks["down_proj"]["bias"][i])
            sd[p + "norm_1.bias"] = _w(blocks["ln_1"]["bias"][i])
            sd[p + "norm_2.bias"] = _w(blocks["ln_2"]["bias"][i])
    sd[pre + "norm_f.weight"] = _w(params["ln_f"]["scale"])
    if not cfg.no_bias:
        sd[pre + "norm_f.bias"] = _w(params["ln_f"]["bias"])
    # foundry ties lm_head to wte; nothing extra to emit for tied configs
    if not cfg.tie_embeddings:
        sd["model.lm_head.weight"] = _t(params["lm_head"]["kernel"])
    return sd


def save_hf_llama(params: Any, cfg: ModelConfig, out_dir: str,
                  bos_token_id: int = 0, eos_token_id: int = 0) -> pathlib.Path:
    """Write a transformers-loadable LlamaForCausalLM directory (weights +
    config only; supply tokenizer files separately for generation evals)."""
    import torch

    out = pathlib.Path(out_dir)
    out.mkdir(parents=True, exist_ok=True)
    (out / "config.json").write_text(
        json.dumps(llama_hf_config(cfg, bos_token_id, eos_token_id), indent=2)
    )
    torch.save(llama_state_dict(params, cfg), out / "pytorch_model.bin")
    return out


def save_foundry_mpt(params: Any, cfg: ModelConfig, out_dir: str) -> pathlib.Path:
    import torch

    out = pathlib.Path(out_dir)
    out.mkdir(parents=True, exist_ok=True)
    torch.save(foundry_mpt_state_dict(params, cfg), out / "mpt_foundry_state_dict.pt")
    return out


def main(argv: list[str] | None = None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    ap.add_argument("--params-npz", required=True)
    src = ap.add_mutually_exclusive_group(required=True)
    src.add_argument("--preset")
    src.add_argument("--config")
    ap.add_argument("--out", required=True)
    ap.add_argument("--format", default="llama",
                    choices=["llama", "mixtral", "mpt-foundry"])
    ap.add_argument("--bos-token-id", type=int, default=0)
    ap.add_argument("--eos-token-id", type=int, default=0)
    args = ap.parse_args(argv)

    # pure host-side weight renaming: never claim the (single-claimant) TPU
    import jax

    jax.config.update("jax_platforms", "cpu")

    from photon_tpu.checkpoint import npz_to_arrays
    from photon_tpu.codec import params_from_ndarrays
    from photon_tpu.config import load_preset
    from photon_tpu.models.mpt import init_params

    cfg = Config.from_yaml(args.config) if args.config else load_preset(args.preset)
    cfg.validate()
    meta, arrays = npz_to_arrays(pathlib.Path(args.params_npz).read_bytes())
    template = init_params(cfg.model, seed=0)
    params = params_from_ndarrays(template, meta, arrays)
    if args.format == "llama":
        out = save_hf_llama(params, cfg.model, args.out,
                            args.bos_token_id, args.eos_token_id)
    elif args.format == "mixtral":
        out = save_hf_mixtral(params, cfg.model, args.out,
                              args.bos_token_id, args.eos_token_id)
    else:
        out = save_foundry_mpt(params, cfg.model, args.out)
    print(json.dumps({"format": args.format, "out": str(out),
                      "n_arrays": meta.n_arrays}))


if __name__ == "__main__":
    main()
