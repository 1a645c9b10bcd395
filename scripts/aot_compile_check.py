"""Offline TPU compile check: compile the training step for a v5e topology
on a CPU-only box, with no chip attached.

This harness drives the SAME XLA:TPU + Mosaic compiler the chip uses, locally,
via ``jax.experimental.topologies`` and the in-image ``libtpu.so``: what the
compiler would refuse on the chip (VMEM, HBM, tiling) it refuses here, and a
clean run gives the compile cost plus an AOT memory/FLOPs analysis for any
config. The cases worth keeping are tests (tests/test_tpu_compile.py); this is
the by-hand tool for everything else.

Also compiles the SHARDED multi-chip step against a real multi-device TPU
topology (``--mesh fsdp=4`` over ``--topo v5e:2x2x1``): the Mosaic/XLA:TPU
compiler lays out the actual ICI collectives and reports per-device HBM —
much stronger evidence for the sharding design than the virtual-CPU-device
dryrun, and obtainable with zero chips.

Usage:
    python scripts/aot_compile_check.py [--micro 2] [--gbs 256] [--impl pallas]
        [--block 256] [--chunk 2048] [--remat] [--layers N] [--seq N]
        [--preset mpt-1b] [--mesh data=1,fsdp=4,tensor=1,sequence=1,pipe=1]
        [--topo v5e:2x2x1]

Prints one JSON line: {"ok", "lower_s", "compile_s", "hbm_gib", ...}.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

import jax

jax.config.update("jax_platforms", "cpu")  # compile only; never take a chip

import numpy as np  # noqa: E402


def log(msg: str) -> None:
    print(f"[aot] {msg}", file=sys.stderr, flush=True)


def hbm_gib(compiled) -> float | None:
    """args + outputs + temps in GiB (naive sum: donated aliases are
    double-counted, so the true peak is lower; the compiler's own budget
    check is the pass/fail signal)."""
    try:
        ma = compiled.memory_analysis()
        return round((ma.argument_size_in_bytes + ma.output_size_in_bytes
                      + ma.temp_size_in_bytes) / 2**30, 2)
    except Exception as e:  # noqa: BLE001 — analysis is best-effort
        log(f"memory_analysis unavailable: {e}")
        return None


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--micro", type=int, default=2)
    ap.add_argument("--gbs", type=int, default=256)
    ap.add_argument("--impl", default="pallas", choices=["pallas", "xla"])
    ap.add_argument("--block", type=int, default=0, help="flash tile (q=k)")
    ap.add_argument("--block-k", type=int, default=0,
                    help="flash k tile (asymmetric; overrides --block for k)")
    ap.add_argument("--chunk", type=int, default=2048, help="loss chunk tokens (0 = off)")
    ap.add_argument("--remat", action="store_true")
    ap.add_argument("--layers", type=int, default=0, help="override n_layers")
    ap.add_argument("--seq", type=int, default=0, help="override max_seq_len")
    ap.add_argument("--preset", default="", help="config preset name (default: 125M recipe)")
    ap.add_argument("--mesh", default="", help="axis sizes, e.g. 'fsdp=4' or "
                    "'data=2,fsdp=2' (unnamed axes default to 1)")
    ap.add_argument("--topo", default="v5e:2x2x1",
                    help="TPU topology to compile against")
    ap.add_argument("--program", default="train",
                    choices=["train", "eval", "decode", "collective"],
                    help="train = the jitted train step; eval = the chunked "
                    "eval step (convergence-stage val pass); decode = the "
                    "KV-cache prefill + per-token decode_step pair the "
                    "gauntlet's generation scorer compiles on-chip; "
                    "collective = the federated weighted-psum aggregation "
                    "over a clients axis spanning the whole topology")
    ap.add_argument("--batch", type=int, default=8, help="decode batch rows")
    args = ap.parse_args()
    if ":" not in args.topo:
        ap.error(f"--topo must look like 'v5e:2x2x1', got {args.topo!r}")
    if args.program in ("decode", "collective") and args.mesh:
        # decode runs single-chip; collective builds its OWN 1-D clients
        # mesh over every topology device — a tp/fsdp mesh would compile a
        # program neither stage ever builds
        ap.error(f"--program {args.program} ignores --mesh; drop it")

    from jax.sharding import NamedSharding

    from photon_tpu.config import load_preset
    from photon_tpu.config.schema import Config
    from photon_tpu.models import MPTModel, init_params
    from photon_tpu.optim import build_optimizer
    from photon_tpu.train import init_train_state
    from photon_tpu.train.train_step import make_train_step

    # force the REAL Mosaic lowering: pallas_supported() sees a CPU default
    # backend under AOT tracing and would silently fall back to XLA attention
    import photon_tpu.ops.flash_attention as fa

    fa.pallas_supported = lambda x: True  # noqa: ARG005

    cfg = load_preset(args.preset) if args.preset else Config()
    cfg.model.attn_impl = args.impl
    cfg.model.remat = args.remat
    # nothing reads flash_block_q/k since PR 28 (ops/flash_attention.pick_tiles derives the tile): this sets a dead field (ROADMAP S3)
    if args.block:
        cfg.model.flash_block_q = args.block
        cfg.model.flash_block_k = args.block
    if args.block_k:
        cfg.model.flash_block_k = args.block_k
    if args.layers:
        cfg.model.n_layers = args.layers
    if args.seq:
        cfg.model.max_seq_len = args.seq
    # eval/decode have no microbatch scan — keep config validation happy
    cfg.train.device_microbatch_size = args.micro if args.program == "train" \
        else args.gbs
    cfg.train.global_batch_size = args.gbs
    cfg.train.loss_chunk_tokens = args.chunk
    cfg.validate()

    # env incantation + topology construction shared with the tests
    # (photon_tpu.parallel.topo)
    from photon_tpu.parallel.topo import abstract_tpu_devices

    class _Topo:  # adapter: downstream code reads .devices
        devices = abstract_tpu_devices(args.topo)

    topo = _Topo()
    dev = topo.devices[0]
    log(f"abstract device: {dev.device_kind} x{len(topo.devices)}")

    # decode/collective build their own device layout (single chip / 1-D
    # clients mesh) — dispatch before the training-mesh construction
    if args.program == "decode":
        return _compile_decode(args, cfg, topo, dev)
    if args.program == "collective":
        return _compile_collective(args, cfg, topo, dev)

    from photon_tpu.config.schema import MeshConfig
    from photon_tpu.parallel.context import use_mesh
    from photon_tpu.parallel.mesh import make_mesh
    from photon_tpu.parallel.sharding import batch_spec, state_shardings

    axes = {"data": 1, "fsdp": 1, "tensor": 1, "sequence": 1, "pipe": 1,
            "expert": 1}
    if args.mesh:
        for kv in args.mesh.split(","):
            k, _, v = kv.partition("=")
            if k.strip() not in axes:
                raise SystemExit(f"unknown mesh axis {k!r}")
            axes[k.strip()] = int(v)
    mesh_cfg = MeshConfig(**axes)
    cfg.mesh = mesh_cfg
    cfg.validate()
    mesh = make_mesh(mesh_cfg, devices=list(topo.devices))

    # mesh-driven attn_impl fallbacks (pipe→xla, sequence→ring) — same
    # step-construction resolution the Trainer applies; validate() itself
    # never mutates the config of record
    from photon_tpu.config.schema import effective_model_config

    model_cfg = effective_model_config(cfg.model, mesh_cfg)
    model = MPTModel(model_cfg)
    tx, _ = build_optimizer(cfg.optimizer, cfg.scheduler)
    params = jax.eval_shape(lambda: init_params(model_cfg, seed=0))
    state = jax.eval_shape(lambda p: init_train_state(model, tx, p), params)
    shardings = state_shardings(state, mesh)
    state = jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        state, shardings,
    )
    tok = jax.ShapeDtypeStruct(
        (args.gbs, cfg.model.max_seq_len), jax.numpy.int32,
        sharding=NamedSharding(mesh, batch_spec(mesh)),
    )
    # trainer semantics (trainer.py rows_per_scan): each scan step consumes
    # micro rows PER data-parallel shard. Eval has no microbatch scan — it
    # only needs the batch to split over the data-parallel shards.
    dp_degree = axes["data"] * axes["fsdp"] * axes["expert"]
    rows_per_scan = args.micro * dp_degree if args.program == "train" else dp_degree
    if args.gbs % rows_per_scan:
        raise SystemExit(f"gbs {args.gbs} not divisible by "
                         f"{'micro*dp' if args.program == 'train' else 'dp'} "
                         f"({rows_per_scan})")
    if args.program == "eval":
        from photon_tpu.train.train_step import make_eval_step

        step = make_eval_step(model, loss_chunk_tokens=args.chunk)
        jitted = jax.jit(step)
        jit_args = (state.params, tok)
    elif axes["pipe"] > 1:
        from photon_tpu.parallel.pipeline import make_pipeline_train_step

        step = make_pipeline_train_step(
            model, tx, mesh, n_microbatches=args.gbs // rows_per_scan,
            loss_chunk_tokens=args.chunk,
        )
        jitted = jax.jit(step, donate_argnums=0)
        jit_args = (state, tok)
    else:
        step = make_train_step(
            model, tx, n_microbatches=args.gbs // rows_per_scan,
            loss_chunk_tokens=args.chunk,
        )
        jitted = jax.jit(step, donate_argnums=0)
        jit_args = (state, tok)

    from photon_tpu.utils.heartbeat import heartbeat

    t0 = time.perf_counter()
    with heartbeat("[aot] still compiling"), use_mesh(mesh):
        lowered = jitted.lower(*jit_args)
        t1 = time.perf_counter()
        log(f"lowered in {t1 - t0:.1f}s")
        compiled = lowered.compile()
    t2 = time.perf_counter()
    log(f"compiled in {t2 - t1:.1f}s")

    out = {
        "ok": True,
        "program": args.program,
        "preset": args.preset or "125m-default",
        "topo": args.topo,
        "mesh": {k: v for k, v in axes.items() if v > 1} or None,
        "n_devices": len(topo.devices),
        "impl": args.impl,
        "block": args.block or cfg.model.flash_block_q,
        "block_k": cfg.model.flash_block_k,
        "chunk": args.chunk,
        "micro": args.micro,
        "gbs": args.gbs,
        "remat": args.remat,
        "layers": cfg.model.n_layers,
        "seq": cfg.model.max_seq_len,
        "lower_s": round(t1 - t0, 1),
        "compile_s": round(t2 - t1, 1),
        "device_kind": dev.device_kind,
    }
    out["hbm_gib"] = hbm_gib(compiled)
    try:
        out["temp_gib"] = round(
            compiled.memory_analysis().temp_size_in_bytes / 2**30, 2)
    except Exception:  # noqa: BLE001 — analysis is best-effort
        out["temp_gib"] = None
    print(json.dumps(out), flush=True)
    return 0


def _compile_decode(args, cfg, topo, dev) -> int:
    """Compile the gauntlet's inference pair (prefill + decode_step) for
    the TPU topology — an eval on the chip compiles exactly these jits
    (models/decode.py:make_cached_generate_fn), so verifying them offline
    de-risks it the same way the train-step matrix de-risks a training
    cell."""
    import jax.numpy as jnp

    from jax.sharding import NamedSharding, PartitionSpec
    from photon_tpu.models import init_params
    from photon_tpu.models.decode import DecodeState, decode_step, prefill
    from photon_tpu.utils.heartbeat import heartbeat

    mcfg = cfg.model
    b, s = args.batch, mcfg.max_seq_len
    n_kv = mcfg.n_kv_heads or mcfg.n_heads
    # decode consumes the stacked-layer param tree exactly as trained
    params = jax.eval_shape(lambda: init_params(mcfg, seed=0))
    from jax.sharding import Mesh

    mesh1 = Mesh(np.asarray(topo.devices[:1]), ("d",))
    repl = NamedSharding(mesh1, PartitionSpec())
    as_abstract = lambda t: jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=repl), t)
    params = as_abstract(params)
    tokens = jax.ShapeDtypeStruct((b, s), jnp.int32, sharding=repl)
    lengths = jax.ShapeDtypeStruct((b,), jnp.int32, sharding=repl)
    cache_dtype = jnp.dtype(mcfg.compute_dtype)
    state = DecodeState(
        cache_k=jax.ShapeDtypeStruct(
            (mcfg.n_layers, b, s, n_kv, mcfg.d_head), cache_dtype, sharding=repl),
        cache_v=jax.ShapeDtypeStruct(
            (mcfg.n_layers, b, s, n_kv, mcfg.d_head), cache_dtype, sharding=repl),
        lengths=lengths,
    )
    token = jax.ShapeDtypeStruct((b,), jnp.int32, sharding=repl)

    t0 = time.perf_counter()
    with heartbeat("[aot] still compiling"):
        pre = jax.jit(lambda p, t, l: prefill(p, t, l, mcfg))
        pre_c = pre.lower(params, tokens, lengths).compile()
        t1 = time.perf_counter()
        step = jax.jit(lambda p, st, tok: decode_step(p, st, tok, mcfg),
                       donate_argnums=1)
        step_c = step.lower(params, state, token).compile()
    t2 = time.perf_counter()

    print(json.dumps({
        "ok": True,
        "program": "decode",
        "preset": args.preset or "125m-default",
        "topo": args.topo,
        "mesh": None,  # inference pair is single-device (see ap.error above)
        "batch": b,
        "seq": s,
        "impl": mcfg.attn_impl,
        "prefill_compile_s": round(t1 - t0, 1),
        "decode_step_compile_s": round(t2 - t1, 1),
        "prefill_hbm_gib": hbm_gib(pre_c),
        "decode_step_hbm_gib": hbm_gib(step_c),
        "device_kind": dev.device_kind,
    }), flush=True)
    return 0


def _compile_collective(args, cfg, topo, dev) -> int:
    """Compile the federated weighted-psum aggregation — the TPU-native
    replacement for the reference's S3 upload/download plane
    (``parallel/collective_agg.py``) — with one client per topology device
    and the FULL preset param pytree as the round payload."""
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec

    from photon_tpu.models import init_params
    from photon_tpu.parallel.collective_agg import (
        CLIENT_AXIS,
        collective_weighted_average,
        make_client_mesh,
    )
    from photon_tpu.utils.heartbeat import heartbeat

    n = len(topo.devices)
    mesh = make_client_mesh(n, devices=list(topo.devices))
    params = jax.eval_shape(lambda: init_params(cfg.model, seed=0))
    row = NamedSharding(mesh, PartitionSpec(CLIENT_AXIS))
    stacked = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct((n,) + s.shape, s.dtype, sharding=row),
        params)
    counts = jax.ShapeDtypeStruct((n,), jnp.int32, sharding=row)

    t0 = time.perf_counter()
    with heartbeat("[aot] still compiling"):
        compiled = jax.jit(
            lambda sp, c: collective_weighted_average(sp, c, mesh,
                                                      return_total=True)
        ).lower(stacked, counts).compile()
    dt = time.perf_counter() - t0

    print(json.dumps({
        "ok": True,
        "program": "collective",
        "preset": args.preset or "125m-default",
        "topo": args.topo,
        "n_clients": n,
        "compile_s": round(dt, 1),
        "hbm_gib": hbm_gib(compiled),
        "device_kind": dev.device_kind,
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
