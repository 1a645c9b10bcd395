"""MoE-vs-dense convergence at matched ACTIVE parameters (byte-scale, CPU).

Trains two tiny byte-level LMs on the same real-text corpus with the same
step budget: a dense baseline and an MoE variant whose top-k routing keeps
the per-token active parameter count comparable while total capacity is
E/k times larger. The claim under test: the MoE path (ops/moe.py — routing,
capacity, aux loss, grad flow through dispatch) optimizes properly, i.e.
its val loss is at least on par with dense. No reference analog (the
reference has no MoE); the anchor is this repo's own dense model.

Usage: python scripts/moe_convergence_run.py [--steps 300] [--out MOE_CONVERGENCE.json]
Writes one JSON artifact with both loss curves.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

REPO = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))  # photon_tpu importable when not installed


def build_corpus() -> "np.ndarray":
    """Real-English byte tokens (site-packages docstrings — the zero-egress
    corpus recipe from scripts/make_local_corpus.py), cached as uint8."""
    import numpy as np

    cache = REPO / ".bench_corpus_v1.npy"
    if cache.exists():
        return np.load(cache)
    print("generating real-text corpus (site-packages docstrings, ~35s)...",
          file=sys.stderr, flush=True)
    import make_local_corpus  # a sibling in scripts/, the script's own directory

    tmp_txt = REPO / ".bench_corpus_v1.txt"
    make_local_corpus.main(["--out", str(tmp_txt), "--max-mb", "24"])
    toks = np.frombuffer(tmp_txt.read_bytes(), np.uint8).copy()
    tmp_txt.unlink()
    np.save(cache, toks)
    return toks


def run(kind: str, steps: int, toks) -> dict:
    import jax
    import numpy as np

    from photon_tpu.config.schema import Config
    from photon_tpu.train.trainer import Trainer

    cfg = Config()
    cfg.model.d_model = 128
    cfg.model.n_layers = 2
    cfg.model.n_heads = 4
    cfg.model.max_seq_len = 256
    cfg.model.vocab_size = 257
    cfg.model.attn_impl = "xla"
    cfg.model.compute_dtype = "float32"
    if kind == "moe":
        # 4 experts, top-2: active MLP params/token == dense (2 experts of
        # half the dense hidden each), total MLP capacity 2x dense
        cfg.model.mlp = "moe"
        cfg.model.moe_num_experts = 4
        cfg.model.moe_top_k = 2
        cfg.model.mlp_hidden_size = cfg.model.d_model * 2  # half of dense 4x
    cfg.train.global_batch_size = 8
    cfg.train.device_microbatch_size = 8
    cfg.train.loss_chunk_tokens = 2048
    cfg.scheduler.t_warmup = 20
    cfg.scheduler.t_max = max(steps, 100)
    cfg.validate()

    trainer = Trainer(cfg, init_seed=0)
    per = cfg.train.global_batch_size * cfg.model.max_seq_len
    n_val = 4
    val = toks[-n_val * per:]
    train = toks[: -n_val * per]
    val_batches = [
        val[i * per:(i + 1) * per]
        .reshape(cfg.train.global_batch_size, cfg.model.max_seq_len)
        .astype("int32")
        for i in range(n_val)
    ]
    curve = []
    t0 = time.perf_counter()
    for step in range(1, steps + 1):
        lo = ((step - 1) * per) % (len(train) - per)
        batch = train[lo:lo + per].reshape(
            cfg.train.global_batch_size, cfg.model.max_seq_len
        ).astype("int32")
        trainer.state, m = trainer._train_step(trainer.state, batch)
        if step % 50 == 0 or step == steps:
            ev = trainer.evaluate(iter(val_batches))
            curve.append([step, round(float(m["loss"]), 4),
                          round(float(ev["eval/loss"]), 4)])
            print(f"[{kind}] step {step}/{steps}: "
                  f"train {m['loss']:.3f} val {ev['eval/loss']:.3f}",
                  file=sys.stderr, flush=True)
    n_params = sum(int(np.prod(p.shape))
                   for p in jax.tree.leaves(trainer.state.params))
    return {"curve": curve, "n_params": n_params,
            "wall_s": round(time.perf_counter() - t0, 1)}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--out", default=str(REPO / "MOE_CONVERGENCE.json"))
    args = ap.parse_args()

    toks = build_corpus()
    res = {
        "recipe": "byte-level d128/2L/4H seq 256 on 24 MB real English text, "
                  "GBS 8, ADOPT; dense (4x gelu MLP) vs MoE (4 experts, "
                  "top-2, 2x hidden each -> equal ACTIVE MLP params/token)",
        "dense": run("dense", args.steps, toks),
        "moe": run("moe", args.steps, toks),
    }
    d_final = res["dense"]["curve"][-1][2]
    m_final = res["moe"]["curve"][-1][2]
    res["val_gap_moe_minus_dense"] = round(m_final - d_final, 4)
    pathlib.Path(args.out).write_text(json.dumps(res, indent=2))
    print(json.dumps({"dense_val": d_final, "moe_val": m_final,
                      "gap": res["val_gap_moe_minus_dense"],
                      "out": args.out}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
