"""Layer: kernels (``ops/ssd.py``: the chunked state-space scan of Mamba-2
layers whose B and C come in groups). Its share of its roofline: the least
time the chip could take for the required work (``costs/ssd_scan_grouped.py``,
forward and backward of every Mamba-2 layer and row of a step once, ``C B^T``
and the bytes of B and C once a group: the larger of operations over the bf16
peak and bytes over the HBM peak) over the device time of a step under the
scope ``mamba/scan``. What ``remat`` runs again is in the time and not in the
work. The numbers of layers and of groups are the program's own word
(``mamba_layers`` and ``mamba_groups`` on its ``trainer/steps`` span). Moves
``train_tokens_per_s``."""

from benchmark.costs import ssd_scan_grouped as cost
from benchmark.trace.nemotron_attrs import static_count
from benchmark.trace.op_scopes import device_ms_per_step


def read(run, reduction):
    ms = device_ms_per_step(run, reduction, r"\bmamba/scan\b")
    layers, groups = static_count(run, "mamba_layers"), static_count(run, "mamba_groups")
    if not ms or not layers or not groups:
        return None
    m = run.config["model"]
    shape = dict(seq=m["max_seq_len"], heads=m["mamba_n_heads"], groups=groups,
                 d_head=m["mamba_d_head"], d_state=m["mamba_d_state"])
    rows = run.counters["tokens_per_step"] // m["max_seq_len"]
    least = max(cost.training_flops(chunk=m["mamba_chunk_size"], **shape)
                / run.peaks["flops_per_s_bf16"],
                cost.training_bytes(**shape) / run.peaks["hbm_bytes_per_s"])
    return 100.0 * layers * rows * least / (ms / 1000.0)
