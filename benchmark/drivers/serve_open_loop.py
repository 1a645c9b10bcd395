"""Traffic kind ``serve_open_loop``: streamed requests at a fixed rate.

The process that holds the chip builds what ``python -m photon_tpu.serve``
builds (``PagedEngine`` on seeded weights, ``ContinuousBatcher``,
``ServeFrontend``); a child process that never imports JAX sends the mix's
requests over HTTP when each is due, whatever the server is doing, and takes
the time of every streamed token as it arrives. Latencies count from the
instant a request was DUE. The window opens when the schedule starts and
closes when the last request due in it has been answered.

Set-up warms every step shape the mix can reach (chunk width x live context
width, both powers of two of the block) through the engine's own
``begin`` / ``mixed_step`` / ``evict``, then one request through HTTP.
"""

from __future__ import annotations

import gc
import json
import pathlib
import subprocess
import sys
import time
import urllib.request

import numpy as np

from benchmark import traffic_gen
from benchmark.drivers.train_steps import make_weights, reference_family
from benchmark.harness import percentile
from benchmark.program import build_config

LOADGEN = pathlib.Path(__file__).resolve().parents[1] / "loadgen.py"


def pow2_at_least(n: int) -> int:
    return 1 << max(0, (n - 1).bit_length())


def build_server(run, cfg, params):
    from photon_tpu.serve.engine import PagedEngine
    from photon_tpu.serve.frontend import ServeFrontend
    from photon_tpu.serve.scheduler import ContinuousBatcher

    sc = cfg.photon.serve
    with run.span("setup/engine"):
        engine = PagedEngine(cfg, params)
    batcher = ContinuousBatcher(
        engine, max_queue=sc.max_queue,
        prefill_token_budget=sc.prefill_token_budget,
        default_eos_id=sc.eos_id if sc.eos_id >= 0 else None,
        speculative=sc.speculative)
    frontend = ServeFrontend(batcher, host=sc.host, port=0,
                             max_new_tokens_cap=sc.max_new_tokens)
    return engine, batcher, frontend


def warm_shapes(run, engine, requests: list[dict], budget: int) -> int:
    """Run every (chunk width, context width) step the requests can reach,
    once, through the engine's own calls. Context width is a high-water mark
    over the requests alive together, so a chunk of width w can meet any
    width from its own reservation's up to the mix's largest."""
    bs = engine.block_size
    if any(len(r["prompt"]) > budget for r in requests):
        raise ValueError("a prompt longer than the prefill budget chunks in "
                         "more widths than this warm-up covers")
    chunk_blocks = sorted({pow2_at_least(-(-len(r["prompt"]) // bs)) for r in requests})
    need = [-(-(len(r["prompt"]) + r["max_new"]) // bs) for r in requests]
    lo, hi = pow2_at_least(min(need)), pow2_at_least(max(need))
    steps = 0
    for slot in range(2, engine.n_slots):  # admission's own programs, per slot
        engine.begin(slot, [1], 1)
        engine.evict(slot)
    ctx = lo
    while ctx <= hi:
        with run.span("setup/warm_shapes"):
            # the anchor fills ``ctx`` blocks, which raises the context width
            # to ``ctx``; its own chunk is the one that is ``ctx`` blocks wide
            engine.begin(0, [1] * (ctx * bs - 2), 2)
            while engine.pending_tokens(0):
                engine.mixed_step((0, budget))
                steps += 1
            for cb in chunk_blocks:
                n_prompt = (cb // 2) * bs + 1 if cb > 1 else 1
                if cb >= ctx or not engine.can_admit(n_prompt, 1):
                    continue
                engine.begin(1, [1] * n_prompt, 1)
                engine.mixed_step((1, budget), include_decode=False)
                engine.evict(1)
                steps += 1
            engine.mixed_step()  # decode alone at this width
            steps += 1
            engine.evict(0)  # idle: the high-water mark falls back
        ctx *= 2
    return steps


def http_warm(port: int, prompt: list[int]) -> None:
    body = json.dumps({"tokens": prompt, "max_new_tokens": 2, "eos_id": -1,
                       "stream": True}).encode()
    req = urllib.request.Request(f"http://127.0.0.1:{port}/generate", data=body,
                                 headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=300) as resp:
        resp.read()


def drive(run, batcher, port: int, requests: list[dict]) -> list[dict]:
    """Open the timed window, let the child send the schedule, sample the
    scheduler's counters meanwhile, close the window when the child is done."""
    t = run.traffic
    plan_path, out_path = run.work_dir / "plan.json", run.work_dir / "served.json"
    t_start = time.monotonic() + t["lead_s"]
    plan_path.write_text(json.dumps(
        {"port": port, "t_start": t_start, "requests": requests}))
    child = subprocess.Popen([sys.executable, str(LOADGEN), str(plan_path), str(out_path)])
    try:
        time.sleep(max(0.0, t_start - time.monotonic()))
        deadline = t_start + run.seconds + t["drain_s"]
        with run.timed_window():
            while child.poll() is None:
                stats = batcher.stats()
                run.sample("slot_occupancy", stats["serve/slot_occupancy"])
                run.sample("queue_depth", stats["serve/queue_depth"])
                run.stop_trace_if_due()
                if time.monotonic() > deadline:
                    raise TimeoutError("the load generator did not finish")
                time.sleep(t["stats_every_s"])
    finally:
        if child.poll() is None:
            child.kill()
        child.wait()
    if child.returncode != 0:
        raise RuntimeError(f"load generator exited with {child.returncode}")
    return json.loads(out_path.read_text())


def client_metrics(run, requests: list[dict], served: list[dict], vocab: int) -> dict:
    """What the client saw, each request from the instant it was due."""
    by_id = {r["id"]: r for r in requests}
    ttft, itl, late, bad = [], [], [], 0
    for s in served:
        want = by_id[s["id"]]["max_new"]
        ok = (s["status"] == 200 and not s["error"] and len(s["tokens"]) == want
              and all(0 <= tok < vocab for tok in s["tokens"]))
        if not ok:
            bad += 1
            continue
        times = s["token_times"]
        ttft.append(times[0] - s["due"])
        itl.extend(b - a for a, b in zip(times, times[1:]))
        late.append(s["sent"] - s["due"])
    run.attempted, run.failed = len(requests), bad + len(requests) - len(served)
    return {"ttft_s": ttft, "itl_s": itl, "late_s": late}


def served_gaps(ref, dims, seed: int, sample: list[tuple[list[int], list[int]]],
                matmul: str | None = None) -> np.ndarray:
    """For every served token of the sampled requests, the gap by which its
    reference logit lies below the reference's best at that position (0 where
    the served token is the reference's own first choice). With ``matmul``
    set it is the control's: the token that precision puts first instead of
    the served one."""
    import jax
    import jax.numpy as jnp

    width = dims["max_seq_len"]
    params = make_weights(ref, dims, seed)

    # the weights go in as an argument: closed over, they would be copied
    # into the program as constants (5 GB of them, on the host)
    @jax.jit
    def gaps(params, tokens, chosen):
        logits = ref.forward(params, tokens[None], dims, "float32")[0]
        picked = jnp.take_along_axis(logits, chosen[:, None], axis=-1)[:, 0]
        return jnp.max(logits, axis=-1) - picked

    lower = None if matmul is None else jax.jit(lambda params, tokens: jnp.argmax(
        ref.forward(params, tokens[None], dims, matmul)[0], axis=-1))
    out = []
    for prompt, answer in sample:
        seq = np.zeros(width, np.int32)
        seq[:len(prompt) + len(answer)] = prompt + answer
        lo = len(prompt) - 1  # position lo predicts the first served token
        chosen = np.zeros(width, np.int32)
        chosen[lo:lo + len(answer)] = answer
        if lower is not None:
            chosen = lower(params, jnp.asarray(seq))
        g = np.asarray(gaps(params, jnp.asarray(seq), jnp.asarray(chosen)))
        out.append(g[lo:lo + len(answer)])
    return np.concatenate(out) if out else np.array([float("nan")])


def gap_numbers(gaps: np.ndarray) -> dict[str, float]:
    """The widest gap, and two steadier readings of the same tokens."""
    return {"served_logit_gap": float(np.max(gaps)),
            "served_gap_mean": float(np.mean(gaps)),
            "served_not_first_share": float(np.mean(gaps > 0))}


def pick_sample(run, requests, served) -> list[tuple[list[int], list[int]]]:
    """A sample of the finished requests drawn from the seed, the longest
    among them."""
    by_id = {r["id"]: r for r in requests}
    done = [s for s in served if s["status"] == 200 and not s["error"] and s["tokens"]]
    if not done:
        return []
    done.sort(key=lambda s: -(len(by_id[s["id"]]["prompt"]) + len(s["tokens"])))
    rng = np.random.default_rng([run.seed, 11])
    k = min(run.traffic["check_requests"], len(done))
    rest = rng.choice(np.arange(1, len(done)), size=k - 1, replace=False) if k > 1 else []
    return [(by_id[done[i]["id"]]["prompt"], done[i]["tokens"]) for i in [0, *rest]]


class Server:
    """The daemon's three parts on seeded weights, warmed for ``requests``."""

    def __init__(self, run, requests: list[dict]) -> None:
        self.ref = reference_family(run.config)
        self.dims = self.ref.dims_of(run.config["model"])
        self.cfg = build_config(run.config, run.traffic, run.work_dir / "save", run.seed)
        with run.span("setup/weights"):
            params = make_weights(self.ref, self.dims, run.seed)
        self.engine, self.batcher, self.frontend = build_server(run, self.cfg, params)
        del params
        run.counters["attention_impl"] = self.engine.attn_impl
        run.counters["n_slots"] = self.engine.n_slots
        run.counters["warm_steps"] = warm_shapes(
            run, self.engine, requests, self.cfg.photon.serve.prefill_token_budget)
        self.batcher.start()
        self.port = self.frontend.start()
        self.submitted = []
        submit = self.batcher.submit

        def recording_submit(*args, **kw):  # the call into the scheduler layer
            req = submit(*args, **kw)
            self.submitted.append(req)
            return req

        self.batcher.submit = recording_submit
        with run.span("setup/http_warm"):
            http_warm(self.port, requests[0]["prompt"][:8])
        self.submitted.clear()

    def close(self) -> None:
        self.frontend.mark_draining()
        self.batcher.drain(self.cfg.photon.serve.drain_timeout_s)
        self.frontend.close(handler_join_s=5.0)
        self.batcher.close()


def serve(run, rate: float | None = None):
    """Set-up, window and tear-down; returns what the check needs."""
    vocab = run.config["model"]["vocab_size"]
    requests = traffic_gen.open_loop_requests(run.traffic, run.seed, run.seconds,
                                              vocab, rate)
    server = Server(run, requests)
    try:
        served = drive(run, server.batcher, server.port, requests)
        run.counters["rejected"] = server.batcher.stats()["serve/rejected"]
    finally:
        server.close()
    run.samples["queue_s"] = [r.t_admit - r.t_submit for r in server.submitted if r.t_admit]
    seen = client_metrics(run, requests, served, vocab)
    run.served = {"requests": requests, "served": served}  # for the readers
    ref, dims = server.ref, server.dims
    del server
    gc.collect()
    return ref, dims, requests, served, seen


def run(run) -> None:
    ref, dims, requests, served, seen = serve(run)
    print(json.dumps({"generator_lateness_p95_ms":
                      1000.0 * (percentile(seen["late_s"], 95) or 0.0),
                      "requests": len(requests), "answered": len(seen["ttft_s"])}),
          flush=True)
    run.samples.update(seen)
    run.end_to_end["ttft_p95_ms"] = 1000.0 * (percentile(seen["ttft_s"], 95) or 0.0)
    run.end_to_end["itl_p95_ms"] = 1000.0 * (percentile(seen["itl_s"], 95) or 0.0)

    expect = run.config.get("expect_attention_impl")
    if expect is not None:
        run.check("attention_impl_as_expected",
                  float(run.counters["attention_impl"] == expect), 1.0, at_least=True)
    run.check("failed_requests", run.failed, 0)
    sample = pick_sample(run, requests, served)
    numbers = gap_numbers(served_gaps(ref, dims, run.seed, sample))
    run.counters["checked_tokens"] = sum(len(a) for _, a in sample)
    for name, limit in run.traffic["limits"].items():
        run.check(name, numbers[name], limit)


def readings(run) -> dict:
    """For setting the limit: a short window at the cell's own load, then the
    program's widest gap and the control's over the same sample."""
    ref, dims, requests, served, _ = serve(run)
    sample = pick_sample(run, requests, served)
    program = served_gaps(ref, dims, run.seed, sample)
    control = served_gaps(ref, dims, run.seed, sample, run.traffic["control_matmul"])
    return {"program": gap_numbers(program), "control": gap_numbers(control),
            "checked_tokens": sum(len(a) for _, a in sample), "failed": run.failed}
