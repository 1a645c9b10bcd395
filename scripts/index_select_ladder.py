"""The selection's threshold search alone, on the chip: ``ops/index_select.py``'s
launch over a ladder of row blocks and bits a pass, against ``ops/dsa.
kth_largest``'s eight ``jax.numpy`` passes (what ``index_select.ROW_BLOCK`` and
``BITS`` are held to; PERF.md has the readings).

One line a band of ``keyevl2-train-16k`` (its 8 chunks of 512 queries against
4,096 / 8,192 / 12,288 / 16,384 keys, 2,048 picked, walked by one ``lax.map``
inside one program as ``dsa._row_select``'s chunk loop walks them, so that the
host's dispatch of a launch is not in the reading) and variant: ``ms`` a
band and pass, ``exact`` (the thresholds are ``kth_largest``'s on this chip,
bit for bit); then a line a variant with ``ms_a_step``: the four bands' times
by 4 layers and 2 passes under ``remat``.

``--masks N`` instead holds the whole selection to its ``jax.numpy`` twin at
the cell's sizes: ``N`` seeds of the cell's own weights and rows
(``benchmark/drivers/train_steps.py``: ``make_weights``, ``make_rows``), one
forward pass each through the model, every layer's ``select_keys`` made on
both paths from the same indexer outputs; one line a seed with the entries
that differ (0) and the pairs picked.

    chiprun -- python scripts/index_select_ladder.py --out chiprun_out/select_ladder
    chiprun -- python scripts/index_select_ladder.py --masks 3

Needs the chip (``--tiny`` runs a small size under the interpreter on the
CPU, for the control flow).
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib
import statistics
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

PRESET = "keye-vl-2.0-30b-a3b-ep8"


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


def band_scores(chunks: int, chunk: int, n_keys: int, seed: int):
    """A band's chunks of index scores ``[chunks, chunk, n_keys]`` as
    ``dsa._row_select`` hands them over: sums of weighted ``relu``s (exact
    zeros and ties among them), ``-inf`` past the diagonal."""
    import jax
    import jax.numpy as jnp

    ks = jax.random.split(jax.random.PRNGKey(seed), 2)
    dots = jax.random.normal(ks[0], (chunks * chunk, 4, n_keys), jnp.float32)
    w = jax.random.normal(ks[1], (chunks * chunk, 4, 1), jnp.float32)
    scores = jnp.sum(jax.nn.relu(dots) * w, axis=1) + 0.0
    t = n_keys - chunks * chunk + jnp.arange(chunks * chunk)
    causal = jnp.arange(n_keys)[None, :] <= t[:, None]
    return jnp.where(causal, scores, -jnp.inf).reshape(chunks, chunk, n_keys)


def ladder(args) -> list[dict]:
    import jax
    import numpy as np

    from photon_tpu.ops import dsa, index_select

    chunk, topk = args.chunk, args.topk
    lines, step_ms = [], {}
    for n_keys in (int(n) for n in args.keys.split(",")):
        x = band_scores(args.chunks_a_band, chunk, n_keys, n_keys)
        variants = {"jnp": lambda x: dsa.kth_largest(x, topk)}
        for rows in (int(r) for r in args.rows.split(",")):
            for bits in (int(b) for b in args.bits.split(",")):
                variants[f"pallas-r{rows}-b{bits}"] = (
                    lambda x, rows=rows, bits=bits: index_select.kth_largest(
                        x, topk, interpret=args.tiny, rows=rows, bits=bits))
        want = None
        for name, kth in variants.items():
            fn = jax.jit(lambda x, kth=kth: jax.lax.map(kth, x))
            got = np.asarray(fn(x)).view(np.int32)
            want = got if want is None else want
            ms = _time(fn, (x,), args.calls, args.rounds)
            step_ms[name] = step_ms.get(name, 0.0) + ms * args.passes
            lines.append({"chunks": args.chunks_a_band, "chunk": chunk, "n_keys": n_keys,
                          "topk": topk, "variant": name, "ms": ms,
                          "exact": bool(np.array_equal(got, want)),
                          "device": jax.devices()[0].device_kind})
            print(json.dumps(lines[-1]), flush=True)
    for name, ms in step_ms.items():
        lines.append({"variant": name, "ms_a_step": ms})
        print(json.dumps(lines[-1]), flush=True)
    return lines


def masks(args) -> list[dict]:
    import jax
    import jax.numpy as jnp

    from benchmark.drivers.train_steps import make_rows, make_weights
    from benchmark.reference import keye_sparse_moe as ref
    from photon_tpu.config import load_preset
    from photon_tpu.models import MPTModel
    from photon_tpu.ops import dsa

    cfg = load_preset(PRESET)
    model = cfg.model
    if args.tiny:
        for key, value in dict(
                d_model=64, n_layers=2, n_heads=4, n_kv_heads=2, head_dim=32, max_seq_len=512,
                vocab_size=96, dsa_topk=32, dsa_index_heads=4, dsa_index_head_dim=16,
                dsa_chunk=128, mlp_hidden_size=32, moe_num_experts=8, moe_top_k=2,
                moe_experts_held=2, attn_impl="pallas", attn_interpret=True).items():
            setattr(model, key, value)
    cfg.validate()
    dims = ref.dims_of(dataclasses.asdict(model))
    s, chunk, topk = model.max_seq_len, model.dsa_chunk, model.dsa_topk
    if not dsa.selects_in_vmem(model.attn_impl, model.attn_interpret, s, chunk, topk):
        print("index_select_ladder: the launch does not run here", file=sys.stderr)
        return []
    seen, select_keys = [], dsa.select_keys

    def both(q_idx, k_idx, w, **kwargs):
        mask = select_keys(q_idx, k_idx, w, **kwargs)
        twin = select_keys(q_idx, k_idx, w, topk=kwargs["topk"], chunk=kwargs["chunk"])
        jax.debug.callback(lambda d, n: seen.append((int(d), int(n))),
                           jnp.sum(mask != twin), jnp.sum(mask, dtype=jnp.int32))
        return mask

    dsa.select_keys = both  # the block looks it up at the call
    forward = jax.jit(lambda p, t: jnp.sum(
        MPTModel(model).apply({"params": p}, t).astype(jnp.float32)))
    lines = []
    for seed in range(args.seed, args.seed + args.masks):
        rows = make_rows(1, s, model.vocab_size, 1.01, seed)
        del seen[:]
        jax.block_until_ready(forward(make_weights(ref, dims, seed), rows))
        jax.effects_barrier()
        lines.append({"seed": seed, "positions": s, "layers": len(seen),
                      "entries_that_differ": sum(d for d, _ in seen),
                      "picked_pairs": sum(n for _, n in seen),
                      "device": jax.devices()[0].device_kind})
        print(json.dumps(lines[-1]), flush=True)
    return lines


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--chunk", type=int, default=512)
    ap.add_argument("--topk", type=int, default=2048)
    ap.add_argument("--keys", default="4096,8192,12288,16384", help="a band's keys")
    ap.add_argument("--chunks-a-band", type=int, default=8)
    ap.add_argument("--passes", type=int, default=8, help="layers x passes under remat")
    ap.add_argument("--rows", default="32,64,128")
    ap.add_argument("--bits", default="1,2")
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--rounds", type=int, default=5)
    ap.add_argument("--masks", type=int, default=0, help="seeds of the whole selection")
    ap.add_argument("--seed", type=int, default=3000055001)
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)

    import jax

    if not args.tiny and jax.devices()[0].platform == "cpu":
        print("index_select_ladder: no accelerator (use --tiny for the control flow)",
              file=sys.stderr)
        return 1
    if args.tiny:
        args.chunk, args.topk, args.keys, args.rows = 32, 16, "256,512", "8,16"
        args.calls = args.rounds = 1
    lines = masks(args) if args.masks else ladder(args)
    if args.out:
        out = pathlib.Path(args.out)
        out.mkdir(parents=True, exist_ok=True)
        name = "index_select_masks.jsonl" if args.masks else "index_select_ladder.jsonl"
        (out / name).write_text("".join(json.dumps(x) + "\n" for x in lines))
    return 0 if lines and all(x.get("exact", True) and not x.get("entries_that_differ", 0)
                              for x in lines) else 1


if __name__ == "__main__":
    sys.exit(main())
