"""The flash launches' static schedules, from the TPU compiler, with no chip.

A Mosaic kernel is straight-line VLIW code: the compiler's last dump of a
launch lists every bundle it will issue, and which unit slots each fills. For
each launch, tile and strip height this compiles the launch for a described
v5e (``jax.experimental.topologies``) with ``--xla_jf_dump_llo_text``, and
prints one JSON line: ``bundles`` (the kernel's scheduled bundles for one
grid step's code, every body it holds counted once) and the slot uses by unit
(MXU, XLU, VALU, EUP, loads, stores and how many of those are spills).

    python scripts/flash_static_schedule.py --launch fwd,dq,dkv --tile 2048 --sub 0,256

``--layout auto`` (the default) compiles each launch over operands in the
layout ``flash_attention`` reads for the shape (``flash_layout``: in place, or
the pair body at d_head 64: what the cells run); ``--layout head_major`` over
``to_bh``'s padded copies. ``--shape`` is ``batch*heads,seq,d_head`` either way.

A count, not a time: on the chip a launch took 1.1 to 1.4 times its bundles
at 1.5 GHz (PERF.md, PR 39), stalls and DMA waits being what the dump cannot
see. It orders variants of one body well, which is what it is for: try a
variant here, and send the ladder (``scripts/flash_tile_ladder.py``) to the
chip when one looks better. One child process a compile: the dump flags are
read when the TPU library loads, and that process aborts on exit once its
files are written.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import pathlib
import re
import subprocess
import sys
import tempfile

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

UNITS = ("MXU", "XLU", "VALU", "EUP", "VLOAD", "VLOAD_FILL", "VSTORE", "VSTORE_SPILL", "SALU")


def compile_one(launch: str, shape: tuple[int, int, int], tile: int, sub: int, alibi: bool,
                layout: str = "auto"):
    """In the child: compile ``launch`` alone at one pinned square tile."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from photon_tpu.ops import flash_attention as fa
    from photon_tpu.parallel.topo import abstract_tpu_devices

    one = SingleDeviceSharding(abstract_tpu_devices("v5e:2x2x1")[0])
    bh, s, d = shape
    took = fa.flash_layout(bh, bh, d, d) if layout == "auto" else layout
    heads = fa._Heads.of(took, bh, bh)
    if heads is None:
        rows = bh
        x = jax.ShapeDtypeStruct((bh, s, fa.lane_padded(d)), jnp.bfloat16, sharding=one)
    else:  # one batch row of bh heads, read in place
        rows = heads.q_cols
        x = jax.ShapeDtypeStruct((1, s, bh * d), jnp.bfloat16, sharding=one)
    row = jax.ShapeDtypeStruct((bh, s), jnp.float32, sharding=one)
    slopes = jax.ShapeDtypeStruct((rows, fa.SUBLANE, fa.LANE), jnp.float32, sharding=one)
    fa.STRIP_ROWS = dict.fromkeys(fa.STRIP_ROWS, sub)
    small = (min(tile, 256),) * 2  # the launch that is not asked for is dropped as dead code

    def fn(q, k, v, o, lse, do, sl):
        sl = sl if alibi else None
        if launch == "fwd":
            return fa._fwd(q, k, v, scale=0.125, causal=True, block_q=tile, block_k=tile,
                           slopes=sl, heads=heads)[0]
        grads = fa._bwd(0.125, True, (tile, tile) if launch == "dq" else small,
                        (tile, tile) if launch == "dkv" else small,
                        (q, k, v, o, lse), do, slopes=sl, heads=heads)
        return grads[0] if launch == "dq" else grads[1:]

    jax.jit(fn).lower(x, x, x, x, row, x, slopes).compile()


def read_dump(dump: str, launch: str) -> dict:
    """Bundles and slot uses of the one kernel the child compiled: of the
    programs under the kernel's scope (XLA's fusions around the call are
    there too) the one with the most bundles."""
    kernel = {"fwd": "flash_fwd", "dq": "flash_dq", "dkv": "flash_dkv"}[launch]
    found = []
    for path in glob.glob(f"{dump}/*schedule-analysis_final_bundles.txt"):
        text = pathlib.Path(path).read_text()
        if f"{kernel}/multihead_attention" in text:
            found.append((int(re.search(r"total scheduled bundles:\s+(\d+)", text).group(1)), path))
    if not found:
        raise RuntimeError(f"no {kernel} schedule under {dump}")
    bundles, path = max(found)
    stem = re.sub(r"-\d+-schedule-analysis_final_bundles.txt$", "", path)
    table, = glob.glob(glob.escape(stem) + "-*-final_hlo-static-per-bundle-utilization.txt")
    rows = pathlib.Path(table).read_text().split("== UTILIZATION:")[1].split()
    uses = [sum(int(v) for v in rows[i::len(UNITS)]) for i in range(len(UNITS))]
    return {"bundles": bundles, "slot_uses": dict(zip(UNITS, uses))}


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--launch", default="fwd,dq,dkv")
    ap.add_argument("--shape", default="48,2048,64", help="batch*heads,seq,d_head")
    ap.add_argument("--tile", default="2048", help="square tiles, comma-separated")
    ap.add_argument("--sub", default=None, help="strip heights (0: the whole-tile body); "
                                                "default: the module's STRIP_ROWS")
    ap.add_argument("--no-alibi", action="store_true")
    ap.add_argument("--layout", default="auto", choices=["auto", "head_major"],
                    help="auto: the layout flash_attention reads for the shape; "
                         "head_major: to_bh's padded copies")
    ap.add_argument("--child", default=None, help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    shape = tuple(int(x) for x in args.shape.split(","))
    if args.child:
        launch, tile, sub = args.child.split(",")
        compile_one(launch, shape, int(tile), int(sub), not args.no_alibi, args.layout)
        return 0

    from photon_tpu.ops.flash_attention import STRIP_ROWS

    for launch in args.launch.split(","):
        subs = [int(x) for x in args.sub.split(",")] if args.sub else [STRIP_ROWS[launch]]
        for tile in (int(x) for x in args.tile.split(",")):
            for sub in subs:
                with tempfile.TemporaryDirectory() as dump:
                    env = dict(os.environ, JAX_PLATFORMS="cpu", TPU_LOG_DIR="disabled",
                               LIBTPU_INIT_ARGS=f"--xla_jf_dump_to={dump} --xla_jf_dump_llo_text=true")
                    cmd = [sys.executable, __file__, "--shape", args.shape,
                           "--layout", args.layout, "--child", f"{launch},{tile},{sub}"]
                    if args.no_alibi:
                        cmd.append("--no-alibi")
                    child = subprocess.run(cmd, env=env, capture_output=True, text=True)
                    try:
                        row = read_dump(dump, launch)
                    except (RuntimeError, ValueError):
                        print(child.stderr[-2000:], file=sys.stderr)
                        raise
                print(json.dumps({"launch": launch, "shape": list(shape), "layout": args.layout,
                                  "tile": tile, "sub": sub, **row}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
