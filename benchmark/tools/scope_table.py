"""A ``--trace 1`` run that keeps its trace and prints where a training
step's device time goes, by the one partition every reader shares
(``benchmark/trace/step_parts.py``): every part's milliseconds a step and
share of the device's busy time, then the operations that took most, each
with its part and ``op_name``, and what is under no stage of the step split
into the compiler's own operations (no ``op_name``) and the program's. A part
under its floor at the chip's peak means a fusion took another part's name:
the operations' list says which.
Look at this before aiming a change at a part of a training cell. Needs the
chip, like ``run.py``.

    python3 benchmark/tools/scope_table.py --workload <name> --seed 1 --top 10
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

#: the static attrs ``Trainer.fit`` writes on ``trainer/dsa`` for the index
#: loss's ``pbar``: printed beside ``dsa_index_loss`` (they have no reader)
DSA_ATTRS = ("index_loss_kernel", "index_loss_tiles", "index_loss_tiles_skipped")


def report_of(run, reduction) -> dict | None:
    """Everything this tool knows of a traced run and its reduced trace: the
    parts, and every operation with its part, the largest first."""
    from benchmark.trace import host_spans as hs
    from benchmark.trace import step_parts
    from benchmark.trace.dsa_attrs import DSA_SPAN

    table = step_parts.parts_table(run, reduction)
    if table is None:
        return None
    steps = table["steps"]
    report = {
        "steps": steps,
        "busy_ms_per_step": 1000.0 * reduction["busy_s"] / steps,
        "window_ms_per_step": 1000.0 * reduction["window_s"] / steps,
        "parts_ms_per_step": table["total_ms_per_step"],
        "parts": table["parts"],
        "ops": table["ops"],
    }
    # the guard's two halves: operations the compiler made, which carry no
    # ``op_name`` at all, and the program's own outside every stage of the step
    loose = [o for o in table["ops"] if o["part"] == "step_unscoped"]
    report["step_unscoped_ms_per_step"] = {
        "without_op_name": sum(o["ms_per_step"] for o in loose if not o["op_name"]),
        "named_outside_train_step": sum(o["ms_per_step"] for o in loose if o["op_name"])}
    dsa = hs.named(hs.host_spans(run.trace_dir), DSA_SPAN)
    if dsa:
        report["dsa_index_loss_attrs"] = {
            k: dsa[-1].stats[k] for k in DSA_ATTRS if k in dsa[-1].stats}
    return report


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--top", type=int, default=10,
                    help="how many of the largest operations to list")
    ap.add_argument("--out", help="also write one JSON file: what is printed, and EVERY operation")
    args = ap.parse_args(argv)

    from benchmark.harness import finish, prepare
    from benchmark.spec import Spec
    from benchmark.trace.reduce import reduce_trace

    spec = Spec(ROOT)
    parts, run = prepare(spec, args.workload, args.seed, args.seconds, True,
                         t_process=T_PROCESS)
    try:
        parts["driver"].run(run)
    finally:
        run.clock.close()
    result = finish(spec, parts, run, keep_work=True)
    reduction = reduce_trace(run.trace_dir, [d.id for d in run.devices],
                             window_s=run.trace_window[1] - run.trace_window[0])
    report = report_of(run, reduction) or {}
    # what the driver measured over the whole window with the profiler on for
    # its first part: beside a plain run's, what tracing costs
    report["traced_end_to_end"] = dict(run.end_to_end)
    report["result"] = result
    for row in report.get("parts", ()):
        print(json.dumps(row))
    for row in report.get("ops", ())[:args.top]:
        print(json.dumps(row))
    print(json.dumps({k: v for k, v in report.items()
                      if k not in ("parts", "ops", "result")}))
    if args.out:
        out = pathlib.Path(args.out)
        out.parent.mkdir(parents=True, exist_ok=True)
        out.write_text(json.dumps(report, indent=1))
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
