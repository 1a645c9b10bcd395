"""Federated training entry point — the analog of the reference's launch
pipeline (``scripts/photon_llm_125M.sh``: hydra_resolver → superlink →
server-app → client-app). TPU-first there is no external broker: one command
assembles the server driver, node agents, transport and checkpointing and
runs the round loop.

Examples::

    # 8 synthetic clients, 3 rounds, tiny model, single process
    python -m photon_tpu.federated --preset mpt-125m --rounds 3 \
        --set model.n_layers=2 --set fl.local_steps=8

    # node agents as separate processes over the objstore plane
    python -m photon_tpu.federated --config run.yaml --nodes 2 --multiprocess
"""

from __future__ import annotations

import argparse
import json
import pathlib

from photon_tpu.checkpoint import ClientCheckpointManager, FileStore, ServerCheckpointManager
from photon_tpu.config import load_preset
from photon_tpu.config.schema import Config
from photon_tpu.federation import (
    InProcessDriver,
    MultiprocessDriver,
    NodeAgent,
    ParamTransport,
    ServerApp,
)
from photon_tpu.metrics.history import make_wandb_run


def build_app(
    cfg: Config,
    n_nodes: int = 1,
    multiprocess: bool = False,
    tcp_listen: str | None = None,
) -> ServerApp:
    if cfg.photon.comm_stack.collective:
        # the collective plane is a DIFFERENT topology (multi-controller
        # SPMD, no server process) — fail loudly instead of silently falling
        # back to a pointer plane (the silent-no-op class FitRoundConfig
        # exists to eliminate)
        raise ValueError(
            "photon.comm_stack.collective uses the multi-controller SPMD "
            "topology: launch `python -m photon_tpu.federation.collective_round "
            "--coordinator host:port --num-processes N --process-id i "
            "--config ...` on every slice instead of the driver-based "
            "federated CLI (see photon_tpu/federation/collective_round.py)"
        )
    save = pathlib.Path(cfg.photon.save_path)
    save.mkdir(parents=True, exist_ok=True)

    store = FileStore(save / "store")
    mode = "objstore" if (multiprocess or tcp_listen or cfg.photon.comm_stack.objstore) else (
        "shm" if cfg.photon.comm_stack.shm else "inline"
    )
    if mode == "objstore":
        # normalize BEFORE dumping the config of record: every other process
        # (multiprocess children, TCP node agents) re-loads it and must agree
        # on the bulk-tensor plane (reference: resolved config.yaml is the
        # IPC of record, ``hydra_resolver.py:30-39``)
        cfg.photon.comm_stack.objstore = True
        cfg.photon.comm_stack.shm = False
    cfg.to_yaml(save / "config.yaml")

    if tcp_listen:
        # multi-host: node agents dial in from other machines/processes
        # (reference: superlink + remote DRIVER_API_ADDRESS,
        # ``scripts/fed_125m_example.sh:104-137``); bulk tensors ride the
        # shared objstore, control messages the sockets
        from photon_tpu.federation.tcp import TcpServerDriver

        host, _, port = tcp_listen.rpartition(":")
        driver = TcpServerDriver(host or "0.0.0.0", int(port), expected_nodes=n_nodes)
        print(f"[federated] listening on {host or '0.0.0.0'}:{driver.port}, "
              f"waiting for {n_nodes} node(s)", flush=True)
        # node hosts may take a while to provision; reuse the fit timeout
        # knob rather than hardcoding a second, unconfigurable limit
        driver.wait_for_nodes(timeout=cfg.fl.fit_timeout_s)
    elif multiprocess:
        driver = MultiprocessDriver(cfg, n_nodes=n_nodes)
    else:
        def make_agent(node_id: str) -> NodeAgent:
            return NodeAgent(
                cfg,
                node_id,
                make_transport=lambda: ParamTransport(
                    mode, store=store, compression=cfg.photon.compression,
                    host_threads=cfg.photon.host_threads,
                ),
                make_ckpt_mgr=lambda: ClientCheckpointManager(store, cfg.run_uuid),
            )

        driver = InProcessDriver(cfg, make_agent, n_nodes=n_nodes)

    transport = ParamTransport(mode, store=store, compression=cfg.photon.compression,
                               host_threads=cfg.photon.host_threads)
    ckpt = ServerCheckpointManager(store, cfg.run_uuid) if cfg.photon.checkpoint else None
    from photon_tpu.metrics.history import History

    initial = None
    # warm start only applies to fresh runs: with resume_round set,
    # try_resume would immediately overwrite it (and the source run's
    # checkpoints may have been GC'd since)
    if cfg.photon.init_from_run and cfg.photon.resume_round is None:
        from photon_tpu.federation.server import centralized_warm_start

        initial = centralized_warm_start(store, cfg.photon.init_from_run)
    history = History(make_wandb_run(None, cfg.run_uuid))
    return ServerApp(cfg, driver, transport, ckpt_mgr=ckpt, history=history, initial_params=initial)


def main(argv: list[str] | None = None) -> None:
    ap = argparse.ArgumentParser(description="photon-tpu federated training")
    ap.add_argument("--config", help="resolved config YAML")
    ap.add_argument("--preset", default=None, help="model preset (mpt-125m … mpt-7b)")
    ap.add_argument("--rounds", type=int, default=None)
    ap.add_argument("--nodes", type=int, default=1)
    ap.add_argument("--multiprocess", action="store_true")
    ap.add_argument("--tcp-listen", default=None, metavar="HOST:PORT",
                    help="serve the round loop over TCP; node agents join "
                         "via `python -m photon_tpu.federation.tcp --connect`")
    # action="append": each --set adds one override (nargs="*" would make
    # every repeated --set silently REPLACE the previous list)
    ap.add_argument("--set", action="append", default=[], metavar="KEY=VALUE")
    args = ap.parse_args(argv)

    from photon_tpu.utils.compile_cache import use_compile_cache

    use_compile_cache()
    if args.multiprocess or args.tcp_listen:
        # a chip belongs to one process at a time, and here the node
        # processes own it: this process only aggregates on the host, so its
        # own JAX (model init, strategy state) stays on the CPU
        import jax

        jax.config.update("jax_platforms", "cpu")

    if args.config:
        cfg = Config.from_yaml(args.config)
    elif args.preset:
        cfg = load_preset(args.preset)
    else:
        cfg = Config()
    from photon_tpu.centralized import _apply_override

    for kv in args.set:
        key, _, value = kv.partition("=")
        _apply_override(cfg, key, value)
    cfg.validate()

    app = build_app(
        cfg, n_nodes=args.nodes, multiprocess=args.multiprocess,
        tcp_listen=args.tcp_listen,
    )
    try:
        history = app.run(args.rounds)
    finally:
        app.driver.shutdown()
    final = {k: history.latest(k) for k in ("server/round_time", "server/eval_loss", "server/pseudo_grad_norm", "server/nodes_live")}
    # run-level elasticity summary: total readmissions says whether the
    # fleet churned — 0.0 on a healthy run, so presence is keyed on the
    # series existing, not on the total being nonzero
    if history.series("server/nodes_readmitted"):
        final["server/nodes_readmitted_total"] = history.cumulative("server/nodes_readmitted")
    print(json.dumps({"rounds": args.rounds or cfg.fl.n_rounds, **{k: v for k, v in final.items() if v is not None}}))


if __name__ == "__main__":
    main()
