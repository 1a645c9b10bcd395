"""The flash kernel alone, on the chip, over a ladder of tiles: what
``ops/flash_attention.pick_tiles`` is held to (PERF.md has the readings).

Each of the three launches (forward, dq, dk/dv) is timed by itself at each
tile, bf16, causal, over operands in the layout ``flash_attention`` reads for
the shape (``--layout auto``: ``[batch, seq, heads*d]`` in place or as head
pairs where ``flash_layout`` says so, which is what the cells run) or in
``to_bh``'s ``[batch*heads, seq, d_pad]`` copies (``--layout head_major``):
``--calls`` launches are queued back to back and waited for once, ``--rounds``
times, and the median round is kept. ``--sub 0,128,256,512`` times every
square tile once per strip height (``STRIP_ROWS`` for all three launches; 0 is
the whole-tile masked body, the only one a tile that is not square has), and
``--window N`` times the windowed launches, which walk the band (``--kv-heads``
for grouped heads: ``--shapes 1,64,16384,128 --kv-heads 8 --window 512`` is a
sliding layer of ``lagunaxs2-train-16k``); each row carries ``executed_share``, the pairs its bodies multiply over the
visible ones. With ``--no-clamp`` the dead-tile index clamps are replaced by
the identity maps (every grid step fetches its own block, as before PR 28),
which is how the clamp's share is read. With ``--parent FILE`` (a copy of an
older ``flash_attention.py``) outputs and gradients at fixed explicit tiles
are compared with that file's: the strip bodies sum a row's pairs in another
order than a whole tile does, so they are not bit-identical to a parent
without them, and the comparison is at the interpret tests' tolerance
(``PARENT_TOLERANCE``), with the count of arrays that differ at all beside it.

    chiprun -- python scripts/flash_tile_ladder.py --out chiprun_out/ladder

Needs the chip (``--interpret`` runs the ``--parent`` part alone on the CPU).
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import pathlib
import statistics
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

SHAPES = "4,12,2048,64;4,16,2048,128"
TILES = ("256x256,512x512,1024x1024,512x1024,1024x512,2048x1024,1024x2048,"
         "2048x512,512x2048,2048x2048")
LAUNCHES = ("fwd", "dq", "dkv")
# relative error (norm of the difference over the norm) a result may have
# against the parent's: tests/test_flash_kernel_interpret.py's, for float32
# under the interpreter and for bfloat16 on the chip
PARENT_TOLERANCE = {"float32": 2e-4, "bfloat16": 2e-2}


def _time(fn, args, calls: int, rounds: int) -> float:
    """Median milliseconds of one launch."""
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


def ladder(fa, shapes, tiles, subs, calls, rounds, alibi, layout="auto", window=None,
           kv_heads=0):
    import jax
    import jax.numpy as jnp

    rows = []
    # the strip heights the launches under test read: the band's own with a window
    strips = "STRIP_ROWS" if window is None else "BAND_STRIP_ROWS"
    shipped = dict(getattr(fa, strips))
    for b, h, s, d in shapes:
        setattr(fa, strips, dict(shipped))
        d_pad = fa.lane_padded(d)
        bh = b * h
        h_kv = kv_heads or h  # grouped heads: k and v hold ``--kv-heads`` of them
        took = fa.flash_layout(h, h_kv, d, d) if layout == "auto" else layout
        pair = took == fa.HEAD_PAIRS
        keys = jax.random.split(jax.random.PRNGKey(0), 4)
        heads = fa._Heads.of(took, h, h_kv)
        if heads is None:
            q, k, v, do = (jnp.pad(jax.random.normal(kk, (b * n, s, d), jnp.bfloat16),
                                   ((0, 0), (0, 0), (0, d_pad - d)))
                           for kk, n in zip(keys, (h, h_kv, h_kv, h)))
        else:  # the projections' own arrays, a head (or a pair) a column block
            q, k, v, do = (jax.random.normal(kk, (b, s, n * d), jnp.bfloat16)
                           for kk, n in zip(keys, (h, h_kv, h_kv, h)))
        h_q = h if heads is None and h_kv != h else 0
        slopes = None
        if alibi:
            from photon_tpu.ops.attention import alibi_slopes

            slopes = fa._bh_slopes(alibi_slopes(h), bh, pair)
        scale = 1.0 / d**0.5
        o, lse = jax.jit(lambda q, k, v: fa._fwd(
            q, k, v, scale=scale, causal=True, block_q=256, block_k=256,
            slopes=slopes, heads=heads, h_q=h_q, window=window))(q, k, v)
        plan = fa.pick_tiles(s, s, d_pad, 2, h // h_kv, layout=took, window=window)
        picked_sub = {name: fa.strip_rows(name, t.block_q, t.block_k, causal=True, offset=0,
                                          window=window)
                      for name, t in zip(LAUNCHES, plan)}

        # what every dq / dkv reading holds besides its kernel: _bwd's delta
        # and the sublane-replicated lse / delta, the same at every tile
        def prologue_fn(o, lse, do):
            if took == fa.IN_PLACE:  # the dq launch makes delta itself
                return fa._stats_to_blocks(lse, pair)
            delta = do.astype(jnp.float32) * o.astype(jnp.float32)
            if heads is None:
                delta = jnp.sum(delta, axis=-1)
            else:
                delta = jnp.transpose(jnp.sum(delta.reshape(b, s, h, d), axis=-1),
                                      (0, 2, 1)).reshape(bh, s)
            return fa._stats_to_blocks(delta, pair), fa._stats_to_blocks(lse, pair)

        prologue = _time(jax.jit(prologue_fn), (o, lse, do), calls, rounds)
        head = {"shape": [b, h, s, d], "kv_heads": h_kv, "window": window, "layout": took,
                "bwd_prologue_ms": prologue}
        print(json.dumps(head), flush=True)
        rows.append(head)
        for (bq, bk), sub in ((t, sub) for t in tiles for sub in subs):
            if s % bq or s % bk:
                continue
            # a strip height is a reading only where it changes the body
            if sub and (bq != bk or bq % sub):
                continue
            setattr(fa, strips, dict.fromkeys(LAUNCHES, sub))
            row = {"shape": [b, h, s, d], "layout": took, "tile": [bq, bk], "sub": sub,
                   "executed_share": {
                       name: round(fa.executed_pairs(name, s, s, bq, bk, window=window)
                                   / fa.visible_pairs(s, s, window=window), 4)
                       for name in LAUNCHES},
                   "picked": [
                       name for name, t in zip(LAUNCHES, plan)
                       if (t.block_q, t.block_k) == (bq, bk) and sub == picked_sub[name]]}
            launches = {
                "fwd": (lambda q, k, v, o, lse, do: fa._fwd(
                    q, k, v, scale=scale, causal=True, block_q=bq, block_k=bk,
                    slopes=slopes, heads=heads, h_q=h_q, window=window)[0]),
                "dq": (lambda q, k, v, o, lse, do: fa._bwd(
                    scale, True, (bq, bk), (bq, bk), (q, k, v, o, lse), do,
                    slopes=slopes, heads=heads, h_q=h_q, window=window)[0]),
                "dkv": (lambda q, k, v, o, lse, do: fa._bwd(
                    scale, True, (bq, bk), (bq, bk), (q, k, v, o, lse), do,
                    slopes=slopes, heads=heads, h_q=h_q, window=window)[1:]),
            }
            for name, fn in launches.items():
                try:
                    row[name + "_ms"] = _time(jax.jit(fn), (q, k, v, o, lse, do),
                                              calls, rounds)
                except Exception as e:  # the compiler refusing a tile is a reading
                    row[name + "_ms"] = None
                    row[name + "_error"] = str(e).strip().splitlines()[-1][:200]
            print(json.dumps(row), flush=True)
            rows.append(row)
    setattr(fa, strips, shipped)
    return rows


def against_parent(fa, parent_file, interpret):
    """Outputs and all three gradients at fixed explicit tiles, this tree's
    kernel against ``parent_file``'s: the number of arrays that differ at all,
    and the largest relative error, held to ``PARENT_TOLERANCE``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    spec = importlib.util.spec_from_file_location("parent_flash_attention", parent_file)
    parent = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(parent)
    s = 512 if interpret else 2048
    cases = [  # (h, h_kv, d, s_q, alibi, tile)
        (4, 4, 64, s, False, 128 if interpret else 256),
        (4, 4, 64, s, True, 512),  # two strips and more
        (4, 2, 128, s, False, 128 if interpret else 512),
        (4, 4, 64, s // 2, False, 128 if interpret else 256),  # s_q != s_k
    ]
    rows = []
    for h, h_kv, d, s_q, alibi, tile in cases:
        dtype = jnp.float32 if interpret else jnp.bfloat16
        keys = jax.random.split(jax.random.PRNGKey(d + s_q + h_kv), 4)
        q = jax.random.normal(keys[0], (2, s_q, h, d), dtype)
        k = jax.random.normal(keys[1], (2, s, h_kv, d), dtype)
        v = jax.random.normal(keys[2], (2, s, h_kv, d), dtype)
        w = jax.random.normal(keys[3], (2, s_q, h, d), jnp.float32)

        def both(mod):
            def f(q, k, v):
                return mod.flash_attention(q, k, v, causal=True, alibi=alibi,
                                           block_q=tile, block_k=tile,
                                           interpret=interpret)

            o = jax.jit(f)(q, k, v)
            g = jax.jit(jax.grad(lambda q, k, v: (f(q, k, v).astype(jnp.float32) * w).sum(),
                                 argnums=(0, 1, 2)))(q, k, v)
            return [np.asarray(x.astype(jnp.float32)) for x in (o, *g)]

        ours, theirs = both(fa), both(parent)
        differ = sum(not np.array_equal(a, b) for a, b in zip(ours, theirs))
        rel = max(float(np.linalg.norm(a - b) / (np.linalg.norm(b) + 1e-12))
                  for a, b in zip(ours, theirs))
        row = {"against_parent": [h, h_kv, d, s_q, s, alibi, tile], "arrays_differ": differ,
               "max_rel_error": rel, "within": rel < PARENT_TOLERANCE[jnp.dtype(dtype).name]}
        print(json.dumps(row), flush=True)
        rows.append(row)
    return rows


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shapes", default=SHAPES, help="b,h,s,d;...")
    ap.add_argument("--tiles", default=TILES, help="QxK,...")
    ap.add_argument("--sub", default=None,
                    help="strip heights to time each square tile at, e.g. 0,128,256,512 "
                         "(0: the whole-tile body); default: the module's STRIP_ROWS")
    ap.add_argument("--calls", type=int, default=40)
    ap.add_argument("--rounds", type=int, default=5)
    ap.add_argument("--alibi", action="store_true")
    ap.add_argument("--layout", default="auto", choices=["auto", "head_major"],
                    help="auto: the layout flash_attention reads for each shape "
                         "(what the cells run); head_major: to_bh's copies")
    ap.add_argument("--window", type=int, default=None,
                    help="time the windowed (banded) launches: a query's last N keys")
    ap.add_argument("--kv-heads", type=int, default=0,
                    help="grouped heads: k and v hold this many (0: as many as q)")
    ap.add_argument("--no-clamp", action="store_true")
    ap.add_argument("--parent", default=None)
    ap.add_argument("--interpret", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    import jax

    from photon_tpu.ops import flash_attention as fa

    if not args.interpret and jax.default_backend() != "tpu":
        print("flash_tile_ladder: no TPU here; a CPU time is not a reading", file=sys.stderr)
        return 2
    if args.no_clamp:
        fa._kv_block = lambda i, j, **kw: j
        fa._q_block = lambda i, j, **kw: i
    result = {"device": jax.devices()[0].device_kind, "jax": jax.__version__,
              "clamp": not args.no_clamp, "alibi": args.alibi, "layout": args.layout}
    if args.parent:
        result["against_parent"] = against_parent(fa, args.parent, args.interpret)
    if not args.interpret:
        shapes = [tuple(int(x) for x in sh.split(",")) for sh in args.shapes.split(";")]
        tiles = [tuple(int(x) for x in t.split("x")) for t in args.tiles.split(",")]
        subs = ([int(x) for x in args.sub.split(",")] if args.sub
                else sorted({0, *fa.STRIP_ROWS.values()}))
        result["ladder"] = ladder(fa, shapes, tiles, subs, args.calls, args.rounds, args.alibi,
                                  args.layout, args.window, args.kv_heads)
    if args.out:
        out = pathlib.Path(args.out)
        out.mkdir(parents=True, exist_ok=True)
        name = "ladder" + ("_noclamp" if args.no_clamp else "") + ".json"
        (out / name).write_text(json.dumps(result, indent=1))
    return 0 if all(r["within"] for r in result.get("against_parent", [])) else 1


if __name__ == "__main__":
    raise SystemExit(main())
