"""Does the main path still start on the chip?

One process on one TPU: a federated round loop on mpt-125m at full width
through ``photon_tpu.federated``, then the serving daemon on the checkpoint it
wrote, answering HTTP ``/generate`` requests. Every phase checks what came out
and the first failure ends the run with a traceback and a non-zero exit code.
There is no CPU fallback: without a TPU the script fails before any phase.

    python chip_smoke.py [--out DIR] [--seed N]     # one chip
    python chip_smoke.py --chips 4                  # the two cross-chip paths only

Each phase prints one JSON line; the last line of stdout is the verdict the
driver reads. Rates in the phase lines are labelled "smoke, not a
measurement": eight cold steps and six requests measure nothing.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import math
import pathlib
import shutil
import time
import urllib.request
from concurrent.futures import ThreadPoolExecutor

HERE = pathlib.Path(__file__).resolve().parent
NOT_A_MEASUREMENT = "smoke, not a measurement"

# mpt-125m exactly as the preset has it (d768/12L/12H, seq 2048, vocab 50368,
# bf16, Pallas flash attention); only the job around it is cut to a smoke.
TRAIN_SETS = (
    "dataset.synthetic=true",
    "fl.n_total_clients=2",
    "fl.n_clients_per_round=2",
    "fl.local_steps=4",
    # federated eval before round 1 and after round 2: the round-0 loss is
    # the untrained model's, the one loss whose value is known beforehand
    "fl.eval_interval_rounds=2",
    "train.eval_batches=2",
    "train.global_batch_size=16",
    "train.device_microbatch_size=2",
    "photon.checkpoint=true",
)
PROMPT_LENS = (32, 200, 1000)
MAX_NEW = 32
LOGIT_TOL = 2e-2  # kernel-vs-XLA parity on the chip's default matmul precision
# psum tree-reduce vs the host's streaming average, fp32 (the same bound
# tests/test_collective_round.py holds the two planes to)
PLANE_RTOL, PLANE_ATOL = 1e-3, 5e-5
SHARD_RTOL = 2e-2


def emit(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


class BackendCompileClock:
    """Seconds XLA spent compiling, as JAX's own monitoring events count
    them; a program found in the persistent cache adds nothing."""

    def __init__(self) -> None:
        import jax.monitoring

        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, secs: float, **_kw) -> None:
        if event.endswith("backend_compile_duration"):
            self.seconds += secs

    def lap(self) -> float:
        out, self.seconds = self.seconds, 0.0
        return round(out, 2)


@dataclasses.dataclass
class Run:
    """What every phase of one invocation shares."""

    out: pathlib.Path
    devices: list
    seed: int
    clock: BackendCompileClock

    def record(self, phase: str, t0: float, **extra) -> None:
        """The phase's JSON line: it passed, and what it cost."""
        import jax

        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                 for d in self.devices]
        emit({
            "phase": phase, "ok": True,
            "wall_s": round(time.monotonic() - t0, 2),
            "backend_compile_s": self.clock.lap(),
            "peak_bytes_in_use": peaks[0] if len(peaks) == 1 else peaks,
            "compile_cache_dir": jax.config.jax_compilation_cache_dir,
            **extra,
        })


def check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"chip_smoke: {what}")


def require_tpu(n_chips: int) -> list:
    """The devices to run on — or no run at all."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(
            f"chip_smoke: needs a TPU, JAX found platform={devices[0].platform!r} "
            f"({devices[0].device_kind}); there is no CPU fallback"
        )
    if len(devices) != n_chips:
        raise SystemExit(
            f"chip_smoke: --chips {n_chips} but JAX found {len(devices)} device(s)"
        )
    return devices


def load_config(preset: str, overrides, save_path: pathlib.Path):
    from photon_tpu.centralized import _apply_override
    from photon_tpu.config import load_preset

    cfg = load_preset(preset)
    for kv in overrides:
        key, _, value = kv.partition("=")
        _apply_override(cfg, key, value)
    cfg.photon.save_path = str(save_path)
    return cfg.validate()


# ---------------------------------------------------------------------------
# one chip: train -> checkpoint -> serve
# ---------------------------------------------------------------------------


def phase_train(run: Run, overrides, *, rounds: int = 2):
    """Federated rounds through ``photon_tpu.federated`` (in-process driver);
    returns the config of record the serve phase loads the checkpoint with."""
    import numpy as np

    from photon_tpu import federated

    t0 = time.monotonic()
    save = run.out / "fed"
    shutil.rmtree(save, ignore_errors=True)
    cfg = load_config("mpt-125m", overrides, save)
    app = federated.build_app(cfg, n_nodes=1)
    initial = [np.array(a) for a in app.strategy.current_parameters]
    try:
        history = app.run(rounds)
        trainer = app.driver._agents["node0"].runtime.trainer
        hlo = trainer.lower_train_step().compile().as_text()
    finally:
        app.driver.shutdown()

    losses = [v for _, v in history.series("loss")]
    evals = history.series("server/eval_loss")
    check(len(losses) == rounds and evals and evals[0][0] == 0
          and all(math.isfinite(v) for v in losses + [v for _, v in evals]),
          f"train: losses not finite: train {losses}, eval {evals}")
    # the synthetic tokens are Zipf-distributed, so a few ADOPT steps already
    # pull the train loss well under ln(vocab): only the untrained model's
    # eval loss (round 0) has a value known beforehand
    ln_v = math.log(cfg.model.vocab_size)
    check(abs(evals[0][1] - ln_v) < 0.5,
          f"train: untrained eval loss {evals[0][1]:.3f} not within 0.5 of "
          f"ln(vocab)={ln_v:.3f}")
    pg = history.latest("server/pseudo_grad_norm")
    check(pg is not None and pg > 0, f"train: server/pseudo_grad_norm={pg}")
    final = app.strategy.current_parameters
    check(any(not np.array_equal(a, b) for a, b in zip(initial, final)),
          "train: global parameters unchanged after the rounds")
    check((save / "config.yaml").is_file(), "train: no config.yaml on disk")
    ckpt = save / "store" / cfg.run_uuid / "server" / str(rounds)
    check((ckpt / "manifest.json").is_file(), f"train: no round checkpoint at {ckpt}")
    if cfg.model.attn_impl == "pallas":
        # the dispatcher steps down to XLA attention in silence off-TPU; the
        # compiled program is the only witness that it did not do so here
        check("tpu_custom_call" in hlo,
              "train: attn_impl=pallas but no Pallas kernel in the compiled step")

    # round 1 compiles (and writes the synthetic shards); round 2 is warm
    round_s = [v for _, v in history.series("server/fit_round_time")]
    tps = history.latest("client/tokens_per_sec")
    run.record(
        "train", t0,
        model=cfg.model.name, rounds=rounds, losses=losses, eval_losses=evals,
        pseudo_grad_norm=pg, pallas_in_step="tpu_custom_call" in hlo,
        compile_s=round(round_s[0] - round_s[-1], 2),
        tokens_per_s=tps, tokens_per_s_label=NOT_A_MEASUREMENT,
    )
    return cfg


def _post_generate(port: int, prompt: list[int]) -> tuple[int, dict]:
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/generate",
        data=json.dumps({"tokens": prompt, "max_new_tokens": MAX_NEW}).encode(),
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=600) as resp:
        return resp.status, json.loads(resp.read())


def _engine_prefill_logits(engine, prompt: list[int]):
    """Next-token logits from the engine's own prefill of ``prompt``: the
    host arrays ``engine.admit`` hands its device step, replayed through
    ``mixed_chunk_step`` (the step itself returns sampled tokens only)."""
    import jax

    from photon_tpu.serve.cache import mixed_chunk_step

    slot = engine.free_slot()
    got = {}
    inner = engine._mixed_call

    def spy(n_ctx, has_chunk, *args, **kw):
        params, state, tokens, positions, q_valid, emit_off = args[:6]
        lengths_after, chunk_slot = args[7], args[8]
        logits, _ = jax.jit(lambda *a: mixed_chunk_step(
            *a, engine.mc, n_ctx=n_ctx, has_chunk=has_chunk,
            impl="ragged" if engine.attn_impl == "ragged" else "gather",
        ))(params, state, tokens, positions, q_valid, emit_off, lengths_after,
           chunk_slot)
        got["logits"] = logits[slot]
        return inner(n_ctx, has_chunk, *args, **kw)

    engine._mixed_call = spy
    try:
        engine.admit(slot, prompt, max_new=1)
    finally:
        del engine._mixed_call  # the instance attribute; the method returns
        engine.evict(slot)
    return got["logits"]


def phase_serve(run: Run, cfg, *, prompt_lens=PROMPT_LENS) -> None:
    """The daemon ``python -m photon_tpu.serve`` builds, in this process, on
    the checkpoint the train phase wrote."""
    import dataclasses

    import jax.numpy as jnp
    import numpy as np

    from photon_tpu.checkpoint import FileStore
    from photon_tpu.models.decode import prefill
    from photon_tpu.serve.engine import PagedEngine
    from photon_tpu.serve.frontend import ServeFrontend
    from photon_tpu.serve.scheduler import ContinuousBatcher

    t0 = time.monotonic()
    sc = cfg.photon.serve
    engine = PagedEngine.from_checkpoint(
        cfg, store=FileStore(cfg.photon.save_path + "/store")
    )
    want = "gather" if sc.attention_impl == "gather" else "ragged"
    check(engine.attn_impl == want,
          f"serve: engine.attn_impl={engine.attn_impl!r}, expected {want!r} "
          f"for photon.serve.attention_impl={sc.attention_impl!r}")

    rng = np.random.default_rng(run.seed)
    prompts = [rng.integers(0, cfg.model.vocab_size, n).tolist() for n in prompt_lens]

    # engine prefill vs the plain full forward, same params, XLA attention
    probe = prompts[1]
    got = np.asarray(_engine_prefill_logits(engine, probe), np.float32)
    ref, _ = prefill(
        engine.params, jnp.asarray([probe], jnp.int32),
        jnp.asarray([len(probe)], jnp.int32),
        dataclasses.replace(cfg.model, attn_impl="xla"),
    )
    ref = np.asarray(ref[0], np.float32)
    check(got.shape == (cfg.model.vocab_size,) and np.isfinite(got).all(),
          f"serve: engine prefill logits shape {got.shape} / not finite")
    err = float(np.max(np.abs(got - ref)))
    # worst element as a share of its allowance |d| <= atol + rtol * |ref|
    share = float(np.max(np.abs(got - ref) / (LOGIT_TOL + LOGIT_TOL * np.abs(ref))))
    check(share <= 1.0,
          f"serve: engine prefill logits off the plain forward by {err:.4g}, "
          f"{share:.2f}x the allowance (rtol = atol = {LOGIT_TOL})")

    batcher = ContinuousBatcher(
        engine,
        max_queue=sc.max_queue,
        prefill_token_budget=sc.prefill_token_budget,
        default_eos_id=sc.eos_id if sc.eos_id >= 0 else None,
        speculative=sc.speculative,
    ).start()
    frontend = ServeFrontend(
        batcher, host=sc.host, port=0, max_new_tokens_cap=sc.max_new_tokens,
    )
    port = frontend.start()
    waves = []
    try:
        # two waves of the same three prompts: the first compiles every
        # bucket it reaches, the second is warm
        with ThreadPoolExecutor(len(prompts)) as pool:
            for _ in range(2):
                t_w = time.monotonic()
                replies = list(pool.map(lambda p: _post_generate(port, p), prompts))
                waves.append(time.monotonic() - t_w)
                for (status, body), p in zip(replies, prompts):
                    check(status == 200 and len(body["tokens"]) == MAX_NEW
                          and all(0 <= t < cfg.model.vocab_size for t in body["tokens"]),
                          f"serve: /generate on a {len(p)}-token prompt gave "
                          f"{status} {str(body)[:200]}")
    finally:
        # the SIGTERM path of serve/__main__.py
        frontend.mark_draining()
        drained = batcher.drain(sc.drain_timeout_s)
        frontend.close(handler_join_s=5.0)
    check(drained, "serve: drain dropped in-flight requests")

    run.record(
        "serve", t0,
        round=engine.loaded_round, attn_impl=engine.attn_impl,
        n_slots=engine.n_slots, n_blocks=engine.n_blocks,
        requests=2 * len(prompts), prompt_lens=list(prompt_lens),
        prefill_logits_max_abs_err=err, prefill_logits_share_of_tolerance=share,
        compile_s=round(waves[0] - waves[1], 2),
        tokens_per_s=len(prompts) * MAX_NEW / waves[1],
        tokens_per_s_label=NOT_A_MEASUREMENT,
    )


# ---------------------------------------------------------------------------
# four chips: the two paths that exist only across chips
# ---------------------------------------------------------------------------


def _three_steps(trainer, batch) -> list[float]:
    losses: list[float] = []
    trainer.fit(iter([batch] * 3), 3, log_every=1,
                callback=lambda _i, m: losses.append(m["loss"]))
    return losses


def phase_sharded_trainer(run: Run, overrides) -> None:
    """``Trainer`` on an fsdp=2 x tensor=2 mesh (the flash kernel under
    ``shard_map``) against the same three steps on device 0 alone."""
    import jax
    import numpy as np

    from photon_tpu.parallel.mesh import single_device_mesh
    from photon_tpu.train.trainer import Trainer

    t0 = time.monotonic()
    devices = run.devices
    cfg = load_config("mpt-125m", (*overrides, "mesh.fsdp=2", "mesh.tensor=2"),
                      run.out / "sharded")
    batch = np.random.default_rng(run.seed).integers(
        0, cfg.model.vocab_size,
        (cfg.train.global_batch_size, cfg.model.max_seq_len), dtype=np.int32,
    )
    trainer = Trainer(cfg)
    check(trainer.mesh.devices.size == len(devices),
          f"sharded: mesh spans {trainer.mesh.devices.size} of {len(devices)} devices")
    if cfg.model.attn_impl == "pallas":
        check("tpu_custom_call" in trainer.lower_train_step().compile().as_text(),
              "sharded: no Pallas kernel in the compiled sharded step")
    sharded = _three_steps(trainer, batch)
    holders = {s.device.id for leaf in jax.tree.leaves(trainer.state.params)
               for s in leaf.addressable_shards}
    in_use = {d.id: (d.memory_stats() or {}).get("bytes_in_use") for d in devices}
    check(holders == {d.id for d in devices},
          f"sharded: parameter shards on devices {sorted(holders)} only")
    check(all(v is None or v > 0 for v in in_use.values()),
          f"sharded: a device holds nothing: bytes_in_use={in_use}")
    del trainer
    gc.collect()

    one_cfg = load_config("mpt-125m", overrides, run.out / "sharded")
    single = _three_steps(
        Trainer(one_cfg, mesh=single_device_mesh(devices[0])), batch
    )
    check(all(math.isfinite(v) for v in sharded + single)
          and np.allclose(sharded, single, rtol=SHARD_RTOL),
          f"sharded: losses {sharded} vs one device {single} "
          f"(rtol {SHARD_RTOL})")
    gc.collect()
    run.record("sharded_trainer", t0, mesh={"fsdp": 2, "tensor": 2},
                 losses_sharded=sharded, losses_one_device=single,
                 param_shard_devices=sorted(holders), bytes_in_use=in_use)


def phase_collective_round(run: Run, overrides) -> None:
    """One ``CollectiveFedRunner`` round, one client per device, against the
    driver-plane round from the same seed."""
    import numpy as np

    from photon_tpu import federated
    from photon_tpu.federation.collective_round import (
        CollectiveFedRunner,
        partition_cids,
    )

    t0 = time.monotonic()
    n = len(run.devices)
    job = (*overrides, f"fl.n_total_clients={n}", f"fl.n_clients_per_round={n}",
           "fl.local_steps=2", "photon.checkpoint=false")
    cfg = load_config("mpt-125m", (*job, "photon.comm_stack.collective=true",
                                   "photon.comm_stack.shm=false"), run.out / "collective")
    shutil.rmtree(cfg.photon.save_path, ignore_errors=True)
    runner = CollectiveFedRunner(cfg, partition_cids(n, 1, 0))
    pg = runner.run(1).latest("server/pseudo_grad_norm")
    got = [np.array(a) for a in runner.strategy.current_parameters]
    fit_device = str(runner.runtime.trainer.mesh.devices.flat[0])
    plane_devices = [str(d) for d in runner.mesh.devices.flat]
    del runner
    gc.collect()

    oracle = load_config("mpt-125m", job, run.out / "driver_plane")
    shutil.rmtree(oracle.photon.save_path, ignore_errors=True)
    app = federated.build_app(oracle, n_nodes=1)
    try:
        app.run(1)
    finally:
        app.driver.shutdown()
    want = app.strategy.current_parameters
    check(len(got) == len(want), "collective: payload length differs between planes")
    worst = max(float(np.max(np.abs(g - w))) for g, w in zip(got, want))
    check(all(np.allclose(g, w, rtol=PLANE_RTOL, atol=PLANE_ATOL)
              for g, w in zip(got, want)),
          f"collective: parameters off the driver plane by {worst:.4g} "
          f"(rtol {PLANE_RTOL}, atol {PLANE_ATOL})")
    check(pg is not None and math.isfinite(pg) and pg > 0,
          f"collective: server/pseudo_grad_norm={pg}")
    run.record("collective_round", t0, clients=n,
                 client_fit_device={cid: fit_device for cid in range(n)},
                 aggregation_mesh_devices=plane_devices,
                 max_abs_diff_vs_driver_plane=worst, pseudo_grad_norm=pg)


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", default="chip_smoke_out", help="output directory")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4 = only the sharded trainer and the collective round")
    args = ap.parse_args(argv)

    t0 = time.monotonic()
    devices = require_tpu(args.chips)
    from photon_tpu.utils.compile_cache import use_compile_cache

    use_compile_cache()
    out = pathlib.Path(args.out).resolve()
    out.mkdir(parents=True, exist_ok=True)
    run = Run(out, devices, args.seed, BackendCompileClock())
    run.record("device", t0, kind=devices[0].device_kind, count=len(devices))

    overrides = (*TRAIN_SETS, f"seed={args.seed}")
    if args.chips == 1:
        cfg = phase_train(run, overrides)
        gc.collect()
        phase_serve(run, cfg)
    else:
        phase_sharded_trainer(run, overrides)
        phase_collective_round(run, overrides)

    emit({"ok": True, "device": {"platform": devices[0].platform,
                                 "kind": devices[0].device_kind,
                                 "count": len(devices)}})
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
