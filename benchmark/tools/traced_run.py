"""A ``--trace 1`` run that keeps its trace and prints what is in it: the
planes and lines of the ``.xplane.pb``, and the operations that took most
device time with their scopes. Look at this before writing a reader against
a kernel's name. Needs the chip, like ``run.py``.

    python3 benchmark/tools/traced_run.py --workload <name> --seed 1 --seconds 10
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

T_PROCESS = time.monotonic()
ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--top", type=int, default=40)
    args = ap.parse_args(argv)

    from jax.profiler import ProfileData

    from benchmark.harness import execute
    from benchmark.spec import Spec
    from benchmark.trace.reduce import find_xplane, reduce_trace

    spec = Spec(ROOT)
    result = execute(spec, args.workload, args.seed, args.seconds, True,
                     t_process=T_PROCESS, keep_work=True)
    trace_dir = ROOT / ".bench_work" / args.workload / "trace"
    data = ProfileData.from_file(str(find_xplane(trace_dir)))
    for plane in data.planes:
        lines = [(ln.name, sum(1 for _ in ln.events)) for ln in plane.lines]
        print(json.dumps({"plane": plane.name, "lines": lines[:40]}))
    reduction = reduce_trace(trace_dir)
    for e in reduction["ops"][:args.top]:
        print(json.dumps({"op": e["name"], "seconds": e["seconds"],
                          "count": e["count"], "scope": e["scope"][:300]}))
    print(json.dumps({k: reduction[k] for k in
                      ("window_s", "busy_s", "n_devices", "idle_gaps", "longest_gap_s")}))
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
