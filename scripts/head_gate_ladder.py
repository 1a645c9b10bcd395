"""The headwise gate alone, on the chip: ``ops/head_gate.py``'s two launches
over a ladder of row blocks, against the two lines left to autodiff and
against the same ``custom_vjp`` with ``jax.numpy`` inside (what
``ops/head_gate.ROW_BLOCK`` is held to; PERF.md has the readings).

One line a shape (``lagunaxs2-train-16k``'s two attention kinds: 16,384
tokens, 64 and 48 heads of 128) and variant: ``forward_ms``, ``both_ms``
(forward + pull-back of ``sum(gated * w)``), the bytes a pass has to move at
the dtype's width over its time (``forward_gbps``, ``backward_gbps``; the
chip's memory gives 819), and for every variant whether ``gated``, ``d_o``
are the two lines' to the bit on this chip and ``d_logits``' largest gap in
units of its dtype's spacing.

    chiprun -- python scripts/head_gate_ladder.py --out chiprun_out/gate_ladder

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


def two_lines(o, logits):
    """``models/mpt.py``'s gate before PR 50, its pull-back autodiff's."""
    import jax
    import jax.numpy as jnp

    attn_out = o.reshape(*o.shape[:-1], logits.shape[-1], -1)
    gate = jax.nn.sigmoid(logits.astype(jnp.float32))
    return (attn_out.astype(jnp.float32) * gate[..., None]).astype(o.dtype).reshape(o.shape)


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", default="16384x64x128,16384x48x128", help="tokens x heads x d")
    ap.add_argument("--blocks", default="64,128,256")
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--rounds", type=int, default=5)
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from photon_tpu.ops import head_gate as hg

    if not args.tiny and jax.devices()[0].platform == "cpu":
        print("head_gate_ladder: no accelerator (use --tiny for the control flow)",
              file=sys.stderr)
        return 1
    if args.tiny:
        args.shapes, args.blocks, args.calls, args.rounds = "256x4x128", "64,128", 1, 1
    dtype = jnp.bfloat16
    lines = []
    for shape in args.shapes.split(","):
        s, heads, d = (int(x) for x in shape.split("x"))
        keys = jax.random.split(jax.random.PRNGKey(s + heads), 3)
        o = jax.random.normal(keys[0], (1, s, heads * d), dtype)
        logits = jax.random.normal(keys[1], (1, s, heads), dtype)
        w = jax.random.normal(keys[2], (1, s, heads * d), dtype)
        wide = o.size * o.dtype.itemsize

        def readings(gate_fn):
            fwd = jax.jit(gate_fn)
            both = jax.jit(lambda o, logits: (
                lambda out, pull: (out, *pull(w)))(*jax.vjp(gate_fn, o, logits)))
            return fwd, both, [np.asarray(a, np.float32) for a in both(o, logits)]

        variants = {"two_lines": two_lines,
                    "jnp": lambda o, logits: hg.head_gate(o, logits, impl="xla")}
        for block in (int(b) for b in args.blocks.split(",")):
            def launches(o, logits, block=block):
                hg.ROW_BLOCK = block  # read where the launch is traced
                return hg.head_gate(o, logits, impl="pallas", interpret=args.tiny)
            variants[f"pallas-{block}"] = launches
        want = None
        for name, gate_fn in variants.items():
            fwd, both, got = readings(gate_fn)
            want = want or got
            forward_ms = _time(fwd, (o, logits), args.calls, args.rounds)
            both_ms = _time(both, (o, logits), args.calls, args.rounds)
            spacing = float(jnp.finfo(dtype).eps) * np.maximum(np.abs(want[2]), 1e-30)
            lines.append({
                "shape": shape, "variant": name, "forward_ms": forward_ms, "both_ms": both_ms,
                "forward_gbps": 2 * wide / forward_ms / 1e6,
                "backward_gbps": 3 * wide / max(both_ms - forward_ms, 1e-9) / 1e6,
                "gated_exact": bool(np.array_equal(got[0], want[0])),
                "d_o_exact": bool(np.array_equal(got[1], want[1])),
                "d_logits_gap_ulp": float(np.max(np.abs(got[2] - want[2]) / spacing)),
                "device": jax.devices()[0].device_kind})
            print(json.dumps(lines[-1]), flush=True)
    if args.out:
        out = pathlib.Path(args.out)
        out.mkdir(parents=True, exist_ok=True)
        (out / "head_gate_ladder.jsonl").write_text("".join(json.dumps(x) + "\n" for x in lines))
    return 0


if __name__ == "__main__":
    sys.exit(main())
