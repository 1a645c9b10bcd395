"""The benchmark's command: one new process, one cell, once.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Exits non-zero and prints no result without an accelerator, with fewer chips
than the cell asks for, on a chip whose peaks are not in ``peaks.json``, and
in a directory that lacks the program. The last line of standard output is
the result object; every number compared for ``correct`` is printed beside
its limit on the lines before it, as the last lines of standard error, and
under the result's last key, ``checks``.
"""

from __future__ import annotations

import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from benchmark.harness import NoAcceleratorError, execute
    from benchmark.spec import Spec, SpecError

    try:
        spec = Spec(ROOT)
        result = execute(spec, args.workload, args.seed, args.seconds,
                         bool(args.trace), t_process=T_PROCESS)
    except (NoAcceleratorError, SpecError) as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2
    for name, c in result["checks"].items():
        print(f"{name} {c['value']} limit {c['limit']} "
              f"{'ok' if c['ok'] else 'NOT OK'}", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
