"""The rate sweep that finds a serving cell's knee: once, by hand, on the
chip. One process and one server; for each rate a window of ``--seconds`` of
the cell's mix, reported as one JSON line: requests answered and failed, the
tails as the client saw them, tokens per second, and the scheduler's queue at
the window's middle and end (a backlog that grows says the rate is past the
knee). The knee goes into the traffic file as a number; a run never searches.

    python3 benchmark/tools/sweep.py --workload <name> --rates 1,2,3 --seconds 20
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True, help="comma-separated requests/s")
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)

    from benchmark import traffic_gen
    from benchmark.harness import percentile, prepare
    from benchmark.spec import Spec

    rates = [float(r) for r in args.rates.split(",")]
    parts, run = prepare(Spec(ROOT), args.workload, args.seed, args.seconds,
                         False, t_process=time.monotonic())
    driver = parts["driver"]
    vocab = run.config["model"]["vocab_size"]
    plans = {r: traffic_gen.open_loop_requests(run.traffic, args.seed + i,
                                               args.seconds, vocab, r)
             for i, r in enumerate(rates)}
    server = driver.Server(run, plans[max(rates)])
    print(json.dumps({"setup_s": time.monotonic() - run.t_process,
                      "compile_s": run.clock.seconds,
                      "programs": run.clock.programs, **run.counters}), flush=True)
    try:
        for rate in rates:
            run.samples.clear()
            programs = run.clock.programs
            served = driver.drive(run, server.batcher, server.port, plans[rate])
            seen = driver.client_metrics(run, plans[rate], served, vocab)
            depth = run.samples["queue_depth"]
            tokens = sum(len(s["tokens"]) for s in served)
            print(json.dumps({
                "rate_per_s": rate, "requests": run.attempted, "failed": run.failed,
                "window_s": run.window[1] - run.window[0],
                "tokens_per_s": tokens / (run.window[1] - run.window[0]),
                "ttft_p50_ms": 1e3 * percentile(seen["ttft_s"], 50),
                "ttft_p95_ms": 1e3 * percentile(seen["ttft_s"], 95),
                "itl_p50_ms": 1e3 * percentile(seen["itl_s"], 50),
                "itl_p95_ms": 1e3 * percentile(seen["itl_s"], 95),
                "late_p95_ms": 1e3 * percentile(seen["late_s"], 95),
                "queue_mid": depth[len(depth) // 2], "queue_max": max(depth),
                "occupancy_mean": sum(run.samples["slot_occupancy"]) / len(depth),
                "programs_in_window": run.clock.programs - programs,
                "rejected": server.batcher.stats()["serve/rejected"]}), flush=True)
    finally:
        server.close()
        run.clock.close()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
