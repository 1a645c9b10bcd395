"""Readings that a limit of the correctness check is set from (PERF.md).

    python3 benchmark/tools/readings.py --workload <name> --seeds 1,2,3

One process, the cell's own size, no measured window (or the short one the
kind needs): for each seed one JSON line with the gaps of the program to the
plain reference and the gaps of the control (the reference computed one
precision below the configuration's) to the same reference. Needs the chip,
like ``run.py``.
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
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, default=0.0)
    args = ap.parse_args(argv)

    from benchmark.harness import prepare
    from benchmark.spec import Spec

    spec = Spec(ROOT)
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.monotonic()
        parts, run = prepare(spec, args.workload, seed, args.seconds, False,
                             t_process=t0)
        try:
            out = parts["driver"].readings(run)
        finally:
            run.clock.close()
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "wall_s": time.monotonic() - t0, **out}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
