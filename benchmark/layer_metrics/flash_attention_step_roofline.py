"""Layer: kernels (``ops/flash_attention.py``). The flash kernel's share of
its roofline over a whole optimizer step, for a configuration that trains
under ``remat``: the least time the chip could take for the work a step
requires (``costs/flash_attention.py``, forward and backward of every layer
and microbatch once: the larger of operations over the bf16 peak and bytes
over the HBM peak) over the device time a step spends in the kernel's
launches, found by their own names (``flash_fwd``, ``flash_dq``,
``flash_dkv``) in the launch's ``op_name``.

``flash_attention_roofline`` counts launches and takes three for one layer of
one microbatch; under ``remat`` the forward kernel runs again in the backward
pass, four launches a layer, and that reader would credit a third more work
than was required. Here the recomputed forward is in the time and not in the
work, so the share reads lower for it and cannot pass what the kernel really
reaches. Moves ``train_tokens_per_s``."""

from benchmark.costs import flash_attention as cost
from benchmark.trace.op_scopes import device_ms_per_step

# the launches themselves: a kernel's scope with the pallas_call inside it
KERNEL = r"\bflash_(fwd|dq|dkv)/multihead_attention\b.*pallas_call"


def read(run, reduction):
    ms = device_ms_per_step(run, reduction, KERNEL)
    micro = run.counters.get("device_microbatch_size")
    if not ms or not micro:
        return None
    m = run.config["model"]
    shape = dict(batch=micro, heads=m["n_heads"], seq=m["max_seq_len"],
                 d_head=m["d_head"])
    rows = run.counters["tokens_per_step"] // m["max_seq_len"]
    units = m["n_layers"] * rows / micro  # layers x microbatches a step
    least = max(cost.training_flops(**shape) / run.peaks["flops_per_s_bf16"],
                cost.training_bytes(**shape) / run.peaks["hbm_bytes_per_s"])
    return 100.0 * units * least / (ms / 1000.0)
