"""The dropless expert layer's row movements alone, on the chip: the
un-permute (``ops/moe.rows_of_live_prefix``) against today's whole gather over
a ladder of chunks and of held shares, the combine's pull-back against the
parent's, and one whole layer against an older ``ops/moe.py`` (what
``ops/moe.DISPATCH_CHUNK_BYTES`` is held to; PERF.md has the readings).

A layer routes ``N`` tokens to ``k`` of ``E`` experts and holds the first
``share x E`` of them, so of its ``N k`` assignments about ``share`` have an
expert here; the sort puts those first. Four lines a shape:

- ``permute``: ``x[order // k]``, the gather whose operand is the ``N`` tokens;
- ``unpermute``: ``rows[inv]`` from all ``N k`` expert-ordered rows (today),
  and ``rows_of_live_prefix`` from the first ``--chunks`` rows, alone and
  under its consumer in the permute's pull-back (the float32 sum over a
  token's ``k`` slots);
- ``pullback``: the combine's pull-back, the parent's (the einsum's own
  transposes over the un-permuted rows, the rows' cotangent gathered from
  ``[N k, D]`` by ``order``) and this tree's (``ops/moe._combine``: in expert
  order, from the ``[N, D]`` cotangent gathered by token), and whether both
  cotangents are the parent's to the bit on this chip;
- ``layer``: ``dropless_moe_mlp`` under ``jax.checkpoint``, forward +
  backward, this tree's and, with ``--parent FILE``, an older file's, and
  whether every gradient is that file's to the bit.

Every variant is jitted, checked once against the whole gather's masked
result (``exact``), then ``--calls`` queued back to back and waited for once,
the median of ``--rounds``.

    chiprun -- python scripts/moe_dispatch_ladder.py --out chiprun_out/dispatch_ladder

Needs the chip (``--tiny`` runs a small size on the CPU, for the control flow).
"""

from __future__ import annotations

import argparse
import functools
import importlib.util
import json
import pathlib
import statistics
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

#: tokens, top-k, routed experts, held, expert width: the three cells' layers
CELLS = {"lfm2moe-train-8k": (16384, 4, 32, 8, 1792),
         "glm47flash-train": (16384, 4, 64, 8, 1536),
         "keyevl2-train-16k": (16384, 8, 128, 16, 768)}


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


def _routing(n: int, k: int, experts: int, held: int, seed: int):
    """``order``, ``inv`` and ``rows_held`` of ``n`` tokens' top-``k`` of
    ``experts`` (distinct, uniform) where the first ``held`` are held: the
    layer's own sort."""
    import jax
    import jax.numpy as jnp

    _, idx = jax.lax.top_k(jax.random.uniform(jax.random.PRNGKey(seed), (n, experts)), k)
    key = jnp.where(idx < held, idx, held).reshape(n * k)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    return order, jnp.argsort(order).astype(jnp.int32), jnp.sum(key < held).astype(jnp.int32)


def _parent_rows_by_token(rows, order, inv):
    """The parent's un-permute: a whole gather, its transpose the gather by ``order``."""
    import jax

    @jax.custom_vjp
    def by_token(rows):
        return rows[inv]

    by_token.defvjp(lambda rows: (rows[inv], None), lambda _, g: (g[order],))
    return by_token(rows)


def _load(path: str):
    spec = importlib.util.spec_from_file_location("parent_moe", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _layer(module, n, d, hidden, experts, held, k):
    """One layer's forward + backward under ``jax.checkpoint``, jitted."""
    import jax
    import jax.numpy as jnp

    def loss(h32, router_w, bias, w_gate, w_up, w_down):
        out, _ = jax.checkpoint(functools.partial(
            module.dropless_moe_mlp, top_k=k, first_expert=0))(
                h32, router_w, bias, w_gate, w_up, w_down)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    keys = jax.random.split(jax.random.PRNGKey(1), 5)
    args = (jax.random.normal(keys[0], (n, d), jnp.float32),
            jax.random.normal(keys[1], (d, experts), jnp.float32) * 0.02,
            jnp.zeros((experts,), jnp.float32),
            jax.random.normal(keys[2], (held, d, hidden), jnp.float32) * 0.02,
            jax.random.normal(keys[3], (held, d, hidden), jnp.float32) * 0.02,
            jax.random.normal(keys[4], (held, hidden, d), jnp.float32) * 0.02)
    return jax.jit(jax.grad(loss, argnums=(0, 1, 3, 4, 5))), args


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cells", default="lfm2moe-train-8k,keyevl2-train-16k")
    ap.add_argument("--d-model", type=int, default=2048)
    ap.add_argument("--shares", default="0,0.125,0.25,0.5", help="held experts over all")
    ap.add_argument("--chunks", default="12288,16384,20480,24576,28672",
                    help="rows of the prefix the un-permute gathers from")
    ap.add_argument("--no-layer", action="store_true", help="the movements alone")
    ap.add_argument("--parent", default="", help="an older ops/moe.py, for the layer")
    ap.add_argument("--calls", type=int, default=10)
    ap.add_argument("--rounds", type=int, default=5)
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from photon_tpu.ops import moe

    if not args.tiny and jax.devices()[0].platform == "cpu":
        print("moe_dispatch_ladder: no accelerator (use --tiny for the control "
              "flow alone)", file=sys.stderr)
        return 2
    cells = {name: CELLS[name] for name in args.cells.split(",")}
    d = args.d_model
    if args.tiny:
        cells, d, args.chunks = {"tiny": (64, 4, 32, 8, 128)}, 128, "32,96"
        args.calls = args.rounds = 1
    shares = [float(s) for s in args.shares.split(",")]
    lines = []

    def emit(line):
        print(json.dumps(line), flush=True)
        lines.append(line)

    def sum_slots(by_token, k):
        return jnp.sum(by_token.reshape(-1, k, d).astype(jnp.float32), axis=1).astype(
            by_token.dtype)

    for cell, (n, k, e, held_here, hidden) in cells.items():
        m = n * k
        x = jax.random.normal(jax.random.PRNGKey(2), (n, d), jnp.bfloat16)
        rows = jax.random.normal(jax.random.PRNGKey(3), (m, d), jnp.bfloat16)
        gates = jax.random.uniform(jax.random.PRNGKey(4), (n, k), jnp.float32)
        order, inv, _ = _routing(n, k, e, held_here, seed=7)
        base = {"cell": cell, "rows_static": m}
        whole = jax.jit(lambda src, inv: src[inv])
        emit({**base, "move": "permute", "variant": "today", "ms": _time(
            jax.jit(lambda src, order: src[order // k]), (x, order), args.calls, args.rounds)})
        emit({**base, "move": "unpermute", "variant": "today",
              "ms": _time(whole, (rows, inv), args.calls, args.rounds),
              "summed_ms": _time(jax.jit(lambda src, inv: sum_slots(src[inv], k)),
                                 (rows, inv), args.calls, args.rounds)})
        for chunk in (int(c) for c in args.chunks.split(",")):
            follow = jax.jit(lambda src, inv, live, chunk=chunk:
                             moe.rows_of_live_prefix(src, inv, live, chunk))
            summed = jax.jit(lambda src, inv, live, chunk=chunk: sum_slots(
                moe.rows_of_live_prefix(src, inv, live, chunk), k))
            for share in shares:
                _, inv_s, live = _routing(n, k, e, round(share * e), seed=7)
                # as the grouped products leave them: zeros past the live rows
                live_rows = jnp.where((jnp.arange(m) < live)[:, None], rows, 0)
                line = {**base, "move": "unpermute", "variant": "follow", "chunk": chunk,
                        "share": share, "rows_held": int(live)}
                try:
                    line["exact"] = bool(np.array_equal(
                        np.asarray(follow(live_rows, inv_s, live), np.float32),
                        np.asarray(whole(live_rows, inv_s), np.float32)))
                    line["ms"] = _time(follow, (live_rows, inv_s, live), args.calls, args.rounds)
                    line["summed_ms"] = _time(summed, (live_rows, inv_s, live),
                                              args.calls, args.rounds)
                except Exception as err:  # noqa: BLE001 - a refusal is a reading
                    line["error"] = f"{type(err).__name__}: {str(err)[:300]}"
                emit(line)

        order, inv, live = _routing(n, k, e, held_here, seed=7)
        live_rows = jnp.where((jnp.arange(m) < live)[:, None], rows, 0)
        gates = jnp.where((inv < live).reshape(n, k), gates, 0.0)

        def parent_combine(rows, gates):
            per_slot = _parent_rows_by_token(rows, order, inv).reshape(n, k, d)
            return jnp.einsum("nk,nkd->nd", gates, per_slot,
                              preferred_element_type=jnp.float32).astype(rows.dtype)

        pulls = {"parent": jax.jit(lambda r, w, g: jax.vjp(parent_combine, r, w)[1](g)),
                 "tree": jax.jit(lambda r, w, g: jax.vjp(
                     lambda r, w: moe._combine(r, w, order, inv, live), r, w)[1](g))}
        want = pulls["parent"](live_rows, gates, x)
        for name, pull in pulls.items():
            got = pull(live_rows, gates, x)
            emit({**base, "move": "pullback", "variant": name,
                  "rows_exact": bool(np.array_equal(np.asarray(got[0], np.float32),
                                                    np.asarray(want[0], np.float32))),
                  "gates_exact": bool(np.array_equal(np.asarray(got[1]), np.asarray(want[1]))),
                  "ms": _time(pull, (live_rows, gates, x), args.calls, args.rounds)})
        if args.no_layer:
            continue
        modules = {**({"parent": _load(args.parent)} if args.parent else {}), "tree": moe}
        for share in shares:
            held = round(share * e)
            if not held:
                continue
            want = None
            for name, module in modules.items():
                step, operands = _layer(module, n, d, hidden, e, held, k)
                got = [np.asarray(g) for g in step(*operands)]
                want = want or got
                emit({**base, "move": "layer", "variant": name, "share": share,
                      "grads_exact": [bool(np.array_equal(a, b)) for a, b in zip(got, want)],
                      "fwd_bwd_ms": _time(step, operands, args.calls, args.rounds)})
    if args.out:
        out = pathlib.Path(args.out)
        out.mkdir(parents=True, exist_ok=True)
        (out / "moe_dispatch_ladder.jsonl").write_text(
            "".join(json.dumps(line) + "\n" for line in lines))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
