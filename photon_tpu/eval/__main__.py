"""Standalone evaluation CLI — the ``eval_gauntlet_only.sh`` analog.

Loads parameters from a server round checkpoint, a client/centralized
checkpoint, or a raw ``.npz`` dump, then runs C4-style validation loss over a
PTS dataset and/or the ICL gauntlet over jsonl task files.

Examples::

    python -m photon_tpu.eval --params-npz /run/params_final.npz \
        --preset mpt-125m --dataset /data/c4_8c --split val

    python -m photon_tpu.eval --store /runs/store --run my-run --round -1 \
        --preset mpt-125m --icl-tasks tasks/*.jsonl --tokenizer gpt2
"""

from __future__ import annotations

import argparse
import glob
import json
import pathlib



def load_params(args):
    from photon_tpu.checkpoint import FileStore, npz_to_arrays
    from photon_tpu.checkpoint.server import ServerCheckpointManager
    from photon_tpu.train.param_ops import has_momenta, split_momenta

    if args.params_npz:
        meta, arrays = npz_to_arrays(pathlib.Path(args.params_npz).read_bytes())
    elif args.store and args.run is not None:
        store = FileStore(args.store)
        if args.round is not None:
            mgr = ServerCheckpointManager(store, args.run)
            rnd = mgr.resolve_resume_round(args.round)
            meta, arrays, _, _ = mgr.load_round(rnd)
        else:
            from photon_tpu.federation.server import centralized_warm_start

            meta, arrays = centralized_warm_start(store, args.run)
    else:
        raise SystemExit("need --params-npz or --store/--run")
    if has_momenta(meta):
        meta, arrays, _, _ = split_momenta(meta, arrays)
    return meta, arrays


def main(argv: list[str] | None = None) -> None:
    ap = argparse.ArgumentParser(prog="photon_tpu.eval", description="evaluate a checkpoint")
    src = ap.add_argument_group("checkpoint source")
    src.add_argument("--params-npz")
    src.add_argument("--store", help="object-store root")
    src.add_argument("--run", help="run_uuid inside the store")
    src.add_argument("--round", type=int, default=None, help="server round (negative = latest)")
    ap.add_argument("--preset", default="mpt-125m")
    ap.add_argument("--config", default=None, help="config YAML (overrides --preset)")
    ap.add_argument("--dataset", default=None, help="PTS root (client_*/split) for val loss")
    ap.add_argument("--split", default="val")
    ap.add_argument("--eval-batches", type=int, default=16)
    ap.add_argument("--icl-tasks", nargs="*", default=[], help="jsonl task files/globs")
    ap.add_argument("--tasks-yaml", default=None,
                    help="icl_tasks suite YAML (reference tasks_v0.3.yaml format)")
    ap.add_argument("--gauntlet-yaml", default=None,
                    help="eval_gauntlet YAML (categories/weights/baselines)")
    ap.add_argument("--tasks-root", default=None,
                    help="root_dir for dataset_uri resolution (default: suite YAML's)")
    ap.add_argument("--icl-max-rows", type=int, default=None)
    ap.add_argument("--tokenizer", default="byte-fallback")
    args = ap.parse_args(argv)

    from photon_tpu.utils.compile_cache import use_compile_cache

    use_compile_cache()
    from photon_tpu.config import load_preset
    from photon_tpu.config.schema import Config
    from photon_tpu.models.mpt import MPTModel, init_params
    from photon_tpu.codec import params_from_ndarrays

    cfg = Config.from_yaml(args.config) if args.config else load_preset(args.preset)
    meta, arrays = load_params(args)
    template = init_params(cfg.model, seed=0)
    params = params_from_ndarrays(template, meta, arrays)
    model = MPTModel(cfg.model)

    out: dict[str, float] = {}

    if args.dataset:
        from photon_tpu.centralized import build_dataset
        from photon_tpu.data import StreamingLoader
        from photon_tpu.train.trainer import Trainer

        cfg.dataset.local_path = args.dataset
        cfg.dataset.split_eval = args.split
        trainer = Trainer(cfg, params=params)
        loader = StreamingLoader(
            build_dataset(cfg, args.split), batch_size=cfg.train.global_batch_size,
            seed=0, shuffle=False,
        )
        batches = [next(loader) for _ in range(args.eval_batches)]
        out.update(trainer.evaluate(batches))

    if args.tasks_yaml:
        from photon_tpu.data.tokenizer import load_tokenizer
        from photon_tpu.eval.gauntlet import run_gauntlet_suite

        tok = load_tokenizer(args.tokenizer)

        def apply(p, tokens):
            return model.apply({"params": p}, tokens)

        out.update(
            run_gauntlet_suite(
                args.tasks_yaml, args.gauntlet_yaml, tok, apply, params,
                root_dir=args.tasks_root,
                seq_len=min(cfg.model.max_seq_len, 512),
                max_rows=args.icl_max_rows,
                model_cfg=cfg.model,
            )
        )

    if args.icl_tasks:
        from photon_tpu.data.tokenizer import load_tokenizer
        from photon_tpu.eval.icl import ICLTask, run_gauntlet

        files: list[str] = []
        for pattern in args.icl_tasks:
            files.extend(sorted(glob.glob(pattern)))
        if not files:
            raise SystemExit(f"no task files match {args.icl_tasks}")
        tasks = [ICLTask.from_jsonl(f) for f in files]
        tok = load_tokenizer(args.tokenizer)

        def apply(p, tokens):
            return model.apply({"params": p}, tokens)

        out.update(
            run_gauntlet(
                tasks, tok, apply, params,
                seq_len=min(cfg.model.max_seq_len, 512),
                max_rows=args.icl_max_rows,
                model_cfg=cfg.model,
            )
        )

    print(json.dumps({k: round(float(v), 6) for k, v in out.items()}))


if __name__ == "__main__":
    main()
