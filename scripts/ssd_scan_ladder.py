"""The state-space scan alone, on the chip: ``ops/ssd.py``'s two launches over
a ladder of head blocks, against the ``jax.numpy`` walk (what
``ops/ssd.HEAD_BLOCK`` is held to; PERF.md has the readings).

One line a rung at ``granite4hmicro-train``'s shapes (one row of 8,192
positions, 64 heads of 64, state 128, chunks of 256, bf16): ``forward_ms``,
``both_ms`` (forward + pull-back of ``sum(y * w)``), each one's share of the
floor ``benchmark/costs/ssd_scan.py`` gives the shapes on this chip
(``forward_floor_share``, ``training_floor_share``: the required work at the
chip's peaks over the time, a forward and a whole forward + backward), and
the largest gap of ``y`` and of each gradient from the walk's, over the
walk's largest entry.

    chiprun -- python scripts/ssd_scan_ladder.py --out chiprun_out/ssd_ladder

Needs the chip (``--tiny`` runs a small size under the interpreter on the
CPU, for the control flow).
"""

from __future__ import annotations

import argparse
import json
import pathlib
import statistics
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

#: bf16 FLOP/s and bytes/s of the chips the ladder has run on
PEAKS = {"TPU v5 lite": (197e12, 819e9)}
ARGS = ("x", "dt", "a_log", "b", "c", "d")


def _time(fn, args, calls: int, rounds: int) -> float:
    """Median milliseconds of one call."""
    import jax

    jax.block_until_ready(fn(*args))
    per_call = []
    for _ in range(rounds):
        t0 = time.perf_counter()
        out = None
        for _ in range(calls):
            out = fn(*args)
        jax.block_until_ready(out)
        per_call.append((time.perf_counter() - t0) / calls * 1e3)
    return statistics.median(per_call)


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--shape", default="1x8192x64x64x128x256",
                    help="rows x positions x heads x head width x state x chunk")
    ap.add_argument("--rungs", default="8,16,32", help="heads a block, comma-separated")
    ap.add_argument("--groups", type=int, default=1,
                    help="groups of B and C (a block's heads are of one group: a rung over "
                         "heads / groups runs at heads / groups)")
    ap.add_argument("--calls", type=int, default=10)
    ap.add_argument("--rounds", type=int, default=5)
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.costs import ssd_scan_grouped as costs
    from photon_tpu.ops import ssd

    device = jax.devices()[0]
    if not args.tiny and device.platform == "cpu":
        print("ssd_scan_ladder: no accelerator (use --tiny for the control flow)",
              file=sys.stderr)
        return 1
    if args.tiny:
        args.shape, args.rungs, args.calls, args.rounds = "1x256x4x64x128x128", "2", 1, 1
    bsz, s, h, p, n, chunk = (int(v) for v in args.shape.split("x"))
    g = args.groups
    flops, bw = PEAKS["TPU v5 lite" if args.tiny else device.device_kind]
    floors = {
        "forward": max(costs.forward_flops(s, h, g, p, n, chunk, bsz) / flops,
                       costs.forward_bytes(s, h, g, p, n, bsz) / bw) * 1e3,
        "training": max(costs.training_flops(s, h, g, p, n, chunk, bsz) / flops,
                        costs.training_bytes(s, h, g, p, n, bsz) / bw) * 1e3}
    keys = jax.random.split(jax.random.PRNGKey(s + h), 7)
    dtype = jnp.bfloat16
    # x, y and y's cotangent as the mixer holds them, ``[B, S, H·P]``: the
    # reshapes to and from ``ssd_scan``'s ``[B, S, H, P]`` are inside the jit,
    # where they cost nothing (a ``[.., 64, 64]`` array ON the device is
    # another layout, and turning it would be timed as the scan's)
    inputs = dict(
        x=jax.random.normal(keys[0], (bsz, s, h * p), dtype),
        dt=jax.nn.softplus(jax.random.normal(keys[1], (bsz, s, h)) - 3.0),
        a_log=jnp.log(jax.random.uniform(keys[2], (h,), minval=1.0, maxval=16.0)),
        b=jax.random.normal(keys[3], (bsz, s, g * n), dtype),
        c=jax.random.normal(keys[4], (bsz, s, g * n), dtype),
        d=jnp.ones((h,), jnp.float32))
    w = jax.random.normal(keys[5], (bsz, s, h * p), jnp.float32)
    operands = tuple(inputs[k] for k in ARGS)

    def readings(scan):
        fwd = jax.jit(scan)
        both = jax.jit(lambda *a: (lambda y, pull: (y, *pull(w)))(*jax.vjp(scan, *a)))
        return fwd, both, [np.asarray(t, np.float32) for t in both(*operands)]

    rungs = {"walk": None}
    for rung in args.rungs.split(","):
        rungs[f"launches-{rung}"] = int(rung)
    lines, want = [], None
    for name, rung in rungs.items():
        def scan(*a, rung=rung):
            if rung is not None:
                ssd.HEAD_BLOCK = rung  # read where the launches are traced
            x, *rest = a
            y = ssd.ssd_scan(x.reshape(bsz, s, h, p), *rest, chunk=chunk, compute_dtype=dtype,
                             impl="xla" if rung is None else "pallas", interpret=args.tiny,
                             groups=g)
            return y.reshape(bsz, s, h * p)
        fwd, both, got = readings(scan)
        want = want or got
        forward_ms = _time(fwd, operands, args.calls, args.rounds)
        both_ms = _time(both, operands, args.calls, args.rounds)
        gaps = {f"gap_{k}": float(np.max(np.abs(g - t)) / np.max(np.abs(t)))
                for k, g, t in zip(("y", *ARGS), got, want)}
        lines.append({
            "shape": args.shape, "groups": g, "rung": name, "forward_ms": forward_ms, "both_ms": both_ms,
            "forward_floor_ms": floors["forward"], "training_floor_ms": floors["training"],
            "forward_floor_share": floors["forward"] / forward_ms,
            "training_floor_share": floors["training"] / both_ms,
            **gaps, "device": device.device_kind})
        print(json.dumps(lines[-1]), flush=True)
    if args.out:
        out = pathlib.Path(args.out)
        out.mkdir(parents=True, exist_ok=True)
        (out / "ssd_scan_ladder.jsonl").write_text("".join(json.dumps(x) + "\n" for x in lines))
    return 0


if __name__ == "__main__":
    sys.exit(main())
