"""The dropless expert layer's grouped products alone, on the chip: megablox's
Pallas kernel over a ladder of tile caps against ``jax.lax.ragged_dot``, at
two loads (what ``ops/moe.GMM_TILING`` and ``grouped_matmul``'s choice of the kernel are held to;
PERF.md has the readings).

The shapes are the ``glm-4.7-flash-ep8`` layer's: ``N k`` = 65,536 static
rows of width 2,048, 8 experts held of width 1,536, SwiGLU (gate, up, down).
``--rows`` of those rows belong to the held experts (spread evenly), the rest
to none: a layer whose device time follows the rows routed here takes about
four times as long at ``N k / 2`` as at ``N k / 8``, one that pads to the
static shape takes the same. Each variant is the three products and the
activation, forward alone and forward + backward (the gradients of the rows
and of the three weight stacks), jitted, ``--calls`` queued back to back and
waited for once, the median of ``--rounds``; beside it the time the required
operations take at the bf16 peak (``benchmark/costs/moe_grouped_matmul.py``).

    chiprun -- python scripts/moe_grouped_ladder.py --out chiprun_out/moe_ladder

Needs the chip (``--interpret`` runs one tiny variant of each on the CPU, for
the control flow).
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

CAPS = "512x1024x1024,512x512x512,256x1024x1024,1024x1024x1024,512x2048x768,512x1024x512,1024x512x1024"
V5E = "TPU v5 lite"  # whose peak an --interpret rehearsal prints beside its (CPU) times


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
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--static-rows", type=int, default=65536)
    ap.add_argument("--rows", default="8192,32768", help="rows of the held experts")
    ap.add_argument("--d-model", type=int, default=2048)
    ap.add_argument("--hidden", type=int, default=1536)
    ap.add_argument("--experts", type=int, default=8)
    ap.add_argument("--caps", default=CAPS, help="tile caps, rows x contraction x output")
    ap.add_argument("--ungated", action="store_true",
                    help="two products and relu^2 between (nemotron_h's experts), not SwiGLU's three")
    ap.add_argument("--ragged-tiles", default="",
                    help="rungs in place of ops/moe._ragged_tile's choice for a width no "
                         "multiple of 128 divides, comma-separated (0: the whole dimension as "
                         "one tile); each under every --caps")
    ap.add_argument("--calls", type=int, default=5)
    ap.add_argument("--rounds", type=int, default=5)
    ap.add_argument("--interpret", action="store_true")
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from benchmark.costs import moe_grouped_matmul, moe_grouped_matmul_ungated
    from benchmark.harness import load_peaks
    from photon_tpu.ops import moe

    if not args.interpret and jax.devices()[0].platform == "cpu":
        print("moe_grouped_ladder: no accelerator (use --interpret for the "
              "control flow alone)", file=sys.stderr)
        return 2
    cost = moe_grouped_matmul_ungated if args.ungated else moe_grouped_matmul
    peak = load_peaks(V5E if args.interpret else jax.devices()[0].device_kind)["flops_per_s_bf16"]
    m, d, f, e = args.static_rows, args.d_model, args.hidden, args.experts
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    x = jax.random.normal(keys[0], (m, d), jnp.bfloat16)
    w_gate = jax.random.normal(keys[1], (e, d, f), jnp.bfloat16) * 0.02
    w_up = jax.random.normal(keys[2], (e, d, f), jnp.bfloat16) * 0.02
    w_down = jax.random.normal(keys[3], (e, f, d), jnp.bfloat16) * 0.02

    def experts_fn(impl, caps, ragged=None):
        def forward(x, w_gate, w_up, w_down, sizes):
            if ragged is not None:  # read where the products are traced
                moe._ragged_tile = lambda dim, cap: ragged or dim
            mm = lambda a, b: moe.grouped_matmul(  # noqa: E731
                a, b, sizes, impl=impl, tiling=caps, interpret=args.interpret)
            if args.ungated:
                return mm(jnp.square(jax.nn.relu(mm(x, w_up))), w_down)
            return mm(jax.nn.silu(mm(x, w_gate)) * mm(x, w_up), w_down)

        def loss(x, w_gate, w_up, w_down, sizes):
            return jnp.sum(forward(x, w_gate, w_up, w_down, sizes).astype(jnp.float32))

        return jax.jit(forward), jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3)))

    raggeds = [int(t) for t in args.ragged_tiles.split(",")] if args.ragged_tiles else [None]
    variants = [("xla", (0, 0, 0), None)] + [
        ("pallas", tuple(int(t) for t in caps.split("x")), ragged)
        for caps in args.caps.split(",") for ragged in raggeds]
    lines = []
    for impl, caps, ragged in variants:
        forward, backward = experts_fn(impl, caps, ragged)
        for rows in (int(r) for r in args.rows.split(",")):
            per = rows // e
            sizes = jnp.asarray([per] * e + [m - per * e], jnp.int32)
            operands = (x, w_gate, w_up, w_down, sizes)
            line = {"impl": impl, "caps": "x".join(map(str, caps)) if impl == "pallas" else "",
                    "ragged_tile": ragged, "rows": per * e, "static_rows": m}
            try:
                line["fwd_ms"] = _time(forward, operands, args.calls, args.rounds)
                line["fwd_bwd_ms"] = _time(backward, operands, args.calls, args.rounds)
                line["fwd_at_peak_ms"] = 1e3 * cost.forward_flops(per * e, d, f) / peak
                line["fwd_bwd_at_peak_ms"] = 1e3 * cost.training_flops(per * e, d, f) / peak
            except Exception as err:  # noqa: BLE001 - a tile the compiler refuses is a reading
                line["error"] = f"{type(err).__name__}: {str(err)[:300]}"
            print(json.dumps(line), flush=True)
            lines.append(line)
    if args.out:
        out = pathlib.Path(args.out)
        out.mkdir(parents=True, exist_ok=True)
        (out / "moe_grouped_ladder.jsonl").write_text(
            "".join(json.dumps(line) + "\n" for line in lines))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
