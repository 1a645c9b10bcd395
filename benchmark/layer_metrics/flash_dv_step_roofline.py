"""Layer: kernels (``ops/flash_attention.py`` where v has a head width of its
own: latent attention's q / k 192, v 128). The flash kernel's share of its
roofline over a whole optimizer step, as ``flash_attention_step_roofline``
reads it for one width: the least time the chip could take for the work a
step requires (``costs/flash_attention_dv.py``, forward and backward of every
layer and microbatch once, the score products at ``d_head`` and the value
products at ``v_head_dim``: the larger of operations over the bf16 peak and
bytes over the HBM peak) over the device time a step spends in the kernel's
launches, found by their own names (``flash_fwd``, ``flash_dq``,
``flash_dkv``) in the launch's ``op_name``. Under ``remat`` the recomputed
forward is in the time and not in the work. Read only where the configuration
states a ``v_head_dim``. Moves ``train_tokens_per_s``."""

from benchmark.costs import flash_attention_dv as cost
from benchmark.trace.op_scopes import device_ms_per_step

# the launches themselves: a kernel's scope with the pallas_call inside it
KERNEL = r"\bflash_(fwd|dq|dkv)/multihead_attention\b.*pallas_call"


def read(run, reduction):
    m = run.config["model"]
    ms = device_ms_per_step(run, reduction, KERNEL)
    micro = run.counters.get("device_microbatch_size")
    if not ms or not micro or not m.get("v_head_dim"):
        return None
    shape = dict(batch=micro, heads=m["n_heads"], seq=m["max_seq_len"],
                 d_qk=m["d_head"], d_v=m["v_head_dim"])
    rows = run.counters["tokens_per_step"] // m["max_seq_len"]
    units = m["n_layers"] * rows / micro  # layers x microbatches a step
    least = max(cost.training_flops(**shape) / run.peaks["flops_per_s_bf16"],
                cost.training_bytes(**shape) / run.peaks["hbm_bytes_per_s"])
    return 100.0 * units * least / (ms / 1000.0)
