"""Layer: kernels (``ops/flash_attention.py``). The flash kernel's share of
its roofline, forward and backward together: the least time the chip could
take for the required work (``costs/flash_attention.py``: the larger of
operations over the bf16 peak and bytes over the HBM peak) over the kernel's
summed device time in the trace. Moves ``train_tokens_per_s``.

One layer of one microbatch is three kernel launches (forward, dq, dk/dv), so
the launches counted in the trace, over three, say how much work was done.
"""

from benchmark.costs import flash_attention as cost
from benchmark.trace.reduce import op_seconds

# today the kernel has no name of its own: its launches are the Mosaic custom
# calls under the model's ``multihead_attention`` (PERF.md, for the tracing issue)
KERNEL = r'^%?multihead_attention[.\d]* = .*custom_call_target="tpu_custom_call"'
LAUNCHES_PER_UNIT = 3


def read(run, reduction):
    seconds, launches = op_seconds(reduction, KERNEL)
    micro = run.counters.get("device_microbatch_size")
    if not seconds or not micro:
        return None
    m = run.config["model"]
    shape = dict(batch=micro, heads=m["n_heads"], seq=m["max_seq_len"],
                 d_head=m["d_head"])
    units = launches / LAUNCHES_PER_UNIT
    least = max(cost.training_flops(**shape) / run.peaks["flops_per_s_bf16"],
                cost.training_bytes(**shape) / run.peaks["hbm_bytes_per_s"])
    return 100.0 * units * least / seconds
