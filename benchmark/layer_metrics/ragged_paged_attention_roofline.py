"""Layer: kernels (``ops/ragged_paged_attention.py``). The ragged paged
kernel's share of its roofline over the traced window: the least time the
chip could take for the attention of every token the clients received in it
(``costs/ragged_paged_attention.py``: the larger of the summed operations
over the bf16 peak and the summed live-KV bytes over the HBM peak; a lower
bound on the sum of each launch's own least time, so the share reads low,
never high) over the kernel's summed device time in the trace. At decode it
is the bytes that bind. Moves ``itl_p95_ms``."""

from benchmark.costs import ragged_paged_attention as cost
from benchmark.trace.reduce import op_seconds

# the mixed step's only Mosaic custom calls are this kernel's launches
KERNEL = r'custom_call_target="tpu_custom_call"'


def read(run, reduction):
    seconds, _ = op_seconds(reduction, KERNEL)
    served = getattr(run, "served", None)
    if not seconds or not served or run.trace_window is None:
        return None
    lo, hi = run.trace_window
    m = run.config["model"]
    d, layers = m["d_model"], m["n_layers"]
    prompts = {r["id"]: len(r["prompt"]) for r in served["requests"]}
    flops = nbytes = 0.0
    for s in served["served"]:
        p = prompts[s["id"]]
        for i, t in enumerate(s["token_times"]):
            if not lo <= t <= hi:
                continue
            if i == 0:  # the first token closes the prompt's chunk
                flops += cost.prefill_flops(p, d, layers)
                nbytes += cost.prefill_bytes(p, d, layers)
            else:
                flops += cost.decode_flops(p + i, d, layers)
                nbytes += cost.decode_bytes(p + i, d, layers)
    least = max(flops / run.peaks["flops_per_s_bf16"],
                nbytes / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds
