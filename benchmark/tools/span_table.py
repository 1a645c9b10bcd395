"""A ``--trace 1`` run that keeps its trace and prints the program's spans in
it: seconds and self seconds by span name over the whole trace, and, for each
unit of work (a ``server/round`` or a ``trainer/fit`` of the harness), the
unit's seconds that no leaf span covers, by the span they lie under. Look at
this before aiming a change at the host side of a cell. Needs the chip, like
``run.py``.

    python3 benchmark/tools/span_table.py --workload <name> --seed 1 --seconds 40
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

UNITS = ("server/round", "trainer/fit")


def uncovered_by_parent(members, unit) -> dict[str, float]:
    """The self seconds of the spans inside ``unit`` that have child spans
    (and of ``unit`` itself), by name: where the unnamed host work sits. A
    span whose thread waits for another line's work (the server's
    ``server/fit_round_time`` for a fit on a pool worker) shows that wait
    here too; ``unattributed_s`` is the union over all lines and does not."""
    out: dict[str, float] = {}
    for s in [unit, *members]:
        if not s.leaf and s.self_s > 0:
            out[s.name] = out.get(s.name, 0.0) + s.self_s
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--out", help="also write everything printed, as one JSON file")
    args = ap.parse_args(argv)

    from benchmark.harness import execute
    from benchmark.spec import Spec
    from benchmark.trace import host_spans as hs

    result = execute(Spec(ROOT), args.workload, args.seed, args.seconds, True,
                     t_process=T_PROCESS, keep_work=True)
    spans = hs.host_spans(ROOT / ".bench_work" / args.workload / "trace")
    report = {"spans": hs.table(spans), "units": [], "result": result}
    for row in report["spans"]:
        print(json.dumps(row))
    for unit in hs.named(spans, *UNITS):
        members = hs.inside(spans, unit)
        report["units"].append({
            "unit": unit.name, "seconds": unit.seconds, "stats": unit.stats,
            "unattributed_s": hs.unattributed_seconds(members, unit),
            "uncovered_by_parent": uncovered_by_parent(members, unit),
            "by_name": {r["span"]: r["self_s"] for r in hs.table(members)},
        })
        print(json.dumps(report["units"][-1]))
    if args.out:
        out = pathlib.Path(args.out)
        out.parent.mkdir(parents=True, exist_ok=True)
        out.write_text(json.dumps(report, indent=1))
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
