"""Layer: kernels (``ops/ssd.py``: the chunked state-space scan of the
Mamba-2 layers). Its share of its roofline: the least time the chip could
take for the required work (``costs/ssd_scan.py``, forward and backward of
every Mamba-2 layer and row of a step once: the larger of operations over the
bf16 peak and bytes over the HBM peak) over the device time of a step under
the scope ``mamba/scan``. What ``remat`` and the scan's own checkpoint run
again is in the time and not in the work. The number of layers is the
program's own word (``mamba_layers`` on its ``trainer/steps`` span). Moves
``train_tokens_per_s``."""

from benchmark.costs import ssd_scan as cost
from benchmark.trace.mamba_attrs import mamba_layers
from benchmark.trace.op_scopes import device_ms_per_step


def read(run, reduction):
    ms = device_ms_per_step(run, reduction, r"\bmamba/scan\b")
    layers = mamba_layers(run)
    if not ms or not layers:
        return None
    m = run.config["model"]
    shape = dict(seq=m["max_seq_len"], heads=m["mamba_n_heads"],
                 d_head=m["mamba_d_head"], d_state=m["mamba_d_state"])
    rows = run.counters["tokens_per_step"] // m["max_seq_len"]
    least = max(cost.training_flops(chunk=m["mamba_chunk_size"], **shape)
                / run.peaks["flops_per_s_bf16"],
                cost.training_bytes(**shape) / run.peaks["hbm_bytes_per_s"])
    return 100.0 * layers * rows * least / (ms / 1000.0)
