"""Serving CLI — load a round checkpoint, serve it.

Serving is opt-in (``photon.serve.enabled`` defaults to false): a resolved
TRAINING config can't be pointed at this entry by accident — enable it in
the config, or pass ``--enable`` to opt in from the command line.

Examples::

    # serve the latest round of a federated run
    python -m photon_tpu.serve --config /runs/my-run/resolved.yaml \
        --enable --port 8000

    # explicit store/run/round + a text tokenizer
    python -m photon_tpu.serve --preset mpt-125m --store /runs/store \
        --run my-run --round -1 --enable --port 8000 --tokenizer byte-fallback

    curl -s localhost:8000/generate -d '{"tokens": [5, 9, 2], "max_new_tokens": 8}'
    curl -sN localhost:8000/generate -d '{"text": "hi", "stream": true}'
"""

from __future__ import annotations

import argparse
import json
import signal
import threading


def main(argv: list[str] | None = None) -> None:
    ap = argparse.ArgumentParser(
        prog="photon_tpu.serve", description="serve a checkpoint over HTTP"
    )
    ap.add_argument("--config", default=None, help="resolved config YAML")
    ap.add_argument("--preset", default="mpt-125m")
    ap.add_argument("--store", default=None,
                    help="object-store root (default: {photon.save_path}/store)")
    ap.add_argument("--run", default=None, help="run_uuid (default: config's)")
    ap.add_argument("--round", type=int, default=-1,
                    help="server round (negative = latest valid)")
    ap.add_argument("--enable", action="store_true",
                    help="opt in to serving when the config leaves "
                         "photon.serve.enabled=false")
    ap.add_argument("--host", default=None)
    ap.add_argument("--port", type=int, default=None)
    ap.add_argument("--tokenizer", default=None,
                    help="enable 'text' prompts (e.g. byte-fallback, gpt2)")
    # fleet mode (ISSUE 16): a replica daemon dials the router's control
    # plane and reports its bound data port there — N replicas with
    # --port 0 race no ports and need no port bookkeeping at spawn time
    ap.add_argument("--fleet-connect", default=None, metavar="HOST:PORT",
                    help="dial this fleet router control plane and serve "
                         "as one replica of its fleet")
    ap.add_argument("--replica-id", default=None,
                    help="stable replica id for --fleet-connect "
                         "(cohort pins + liveness key on the router)")
    args = ap.parse_args(argv)
    if bool(args.fleet_connect) != bool(args.replica_id):
        ap.error("--fleet-connect and --replica-id go together")

    from photon_tpu import telemetry
    from photon_tpu.checkpoint import FileStore
    from photon_tpu.config import load_preset
    from photon_tpu.config.schema import Config
    from photon_tpu.serve.engine import PagedEngine
    from photon_tpu.serve.frontend import ServeFrontend
    from photon_tpu.serve.scheduler import ContinuousBatcher
    from photon_tpu.utils.compile_cache import use_compile_cache

    use_compile_cache()
    cfg = Config.from_yaml(args.config) if args.config else load_preset(args.preset)
    if args.run:
        cfg.run_uuid = args.run
    sc = cfg.photon.serve
    if args.enable:
        sc.enabled = True
    if not sc.enabled:
        raise SystemExit(
            "serving is off in this config (photon.serve.enabled=false) — "
            "enable it there or pass --enable"
        )
    if args.host:
        sc.host = args.host
    if args.port is not None:
        sc.port = args.port
    cfg.validate()
    if cfg.photon.telemetry.enabled:
        # run-health observatory rides along (ISSUE 10): typed /metrics,
        # /statusz health rollup, POST /debug/profile artifacts landing in
        # the run's telemetry dir beside the training traces
        telemetry.install(
            cfg.photon.telemetry, scope="serve",
            profile_dir=(cfg.photon.telemetry.dir
                         or cfg.photon.save_path + "/telemetry"),
        )

    store = FileStore(args.store) if args.store else FileStore(
        cfg.photon.save_path + "/store"
    )
    engine = PagedEngine.from_checkpoint(cfg, store=store, resume_round=args.round)
    batcher = ContinuousBatcher(
        engine,
        max_queue=sc.max_queue,
        prefill_token_budget=sc.prefill_token_budget,
        default_eos_id=sc.eos_id if sc.eos_id >= 0 else None,
        speculative=sc.speculative,
    ).start()
    tokenizer = None
    if args.tokenizer:
        from photon_tpu.data.tokenizer import load_tokenizer

        tokenizer = load_tokenizer(args.tokenizer)
    frontend = ServeFrontend(
        batcher, host=sc.host, port=sc.port,
        max_new_tokens_cap=sc.max_new_tokens, tokenizer=tokenizer,
    )
    watcher = None
    if sc.hotswap:
        # track the federated run live (ISSUE 11): poll the store, verify
        # candidate rounds through the manifest CRCs, swap at the
        # scheduler swap point — zero dropped requests across a swap
        from photon_tpu.checkpoint.server import ServerCheckpointManager
        from photon_tpu.serve.hotswap import CheckpointWatcher

        watcher = CheckpointWatcher(
            batcher, ServerCheckpointManager(store, cfg.run_uuid), cfg,
            poll_s=sc.hotswap_poll_s, statusz_url=sc.hotswap_statusz_url,
        ).start()
        frontend.watcher = watcher
    port = frontend.start()
    agent = None
    if args.fleet_connect:
        from photon_tpu.serve.fleet import ReplicaAgent

        agent = ReplicaAgent(
            args.fleet_connect, args.replica_id,
            batcher=batcher, frontend=frontend, watcher=watcher,
            drain_timeout_s=sc.drain_timeout_s,
        ).start()
    print(json.dumps({
        "serving": f"http://{sc.host}:{port}",
        # explicit bound port (satellite: --port 0 spawners parse this
        # instead of splitting the URL)
        "port": port,
        "replica_id": args.replica_id,
        "round": engine.loaded_round,
        "model": cfg.model.name,
        "n_slots": engine.n_slots,
        "n_blocks": engine.n_blocks,
        "block_size": engine.block_size,
        "prefix_cache": engine.prefix_cache is not None,
        "hotswap": watcher is not None,
        # per-cohort LoRA plane (ISSUE 13): cohorts this daemon can decode
        "adapters": (engine.adapter_pool.cohorts()
                     if engine.adapter_pool is not None else None),
    }), flush=True)

    # SIGTERM = graceful drain (ISSUE 8 satellite): healthz flips to
    # "draining" (the load balancer pulls us), new /generate gets 503 +
    # Retry-After, in-flight slots finish within serve.drain_timeout_s,
    # then the scheduler hard-stops. SIGINT (operator ^C) stays immediate.
    stop = threading.Event()
    graceful = threading.Event()

    def _sigterm(*_):
        graceful.set()
        stop.set()

    signal.signal(signal.SIGTERM, _sigterm)
    signal.signal(signal.SIGINT, lambda *_: stop.set())
    try:
        stop.wait()
    finally:
        # the watcher stops FIRST either way: a swap staged mid-shutdown
        # would churn params under the drain (its poll path also refuses
        # on its own once the batcher reports draining)
        if watcher is not None:
            watcher.close()
        if agent is not None:
            # leave the fleet first: the router stops routing here before
            # the drain begins, so survivors absorb the traffic
            agent.stop()
        if graceful.is_set():
            frontend.mark_draining()
            batcher.drain(sc.drain_timeout_s)
            # bounded wait for handler threads still flushing responses:
            # the batcher finishing a generation is not the reply being on
            # the wire yet (slow client, chunked stream tail)
            frontend.close(handler_join_s=5.0)
        else:
            frontend.close()
            batcher.close()


if __name__ == "__main__":
    main()
