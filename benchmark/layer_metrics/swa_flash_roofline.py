"""Layer: kernels (``ops/flash_attention.py``, the windowed launches). The
banded flash kernel's share of its roofline over a whole optimizer step: the
least time the chip could take for the work a step's sliding-window layers
require (``costs/flash_attention_window.py``, forward and backward of every
such layer and row once, the band's pairs and no other: the larger of
operations over the bf16 peak and bytes over the HBM peak) over the device
time a step spends in the launches ``swa_flash_ms_train`` reads. Under
``remat`` the recomputed forward is in the time and not in the work; pairs a
tile multiplies outside the band are in the time and not in the work. The
number of sliding layers and the window are the program's own word
(``swa_layers``, ``sliding_window`` on its ``trainer/steps`` span), not
``n_layers``. Moves ``train_tokens_per_s``."""

from benchmark.costs import flash_attention_window as cost
from benchmark.layer_metrics.swa_flash_ms_train import KERNEL
from benchmark.trace.op_scopes import device_ms_per_step
from benchmark.trace.swa_attrs import sliding_window, swa_layers


def read(run, reduction):
    ms = device_ms_per_step(run, reduction, KERNEL)
    layers, window = swa_layers(run), sliding_window(run)
    if not ms or not layers or not window:
        return None
    m = run.config["model"]
    heads, seq = m.get("swa_n_heads") or m["n_heads"], m["max_seq_len"]
    rows = run.counters["tokens_per_step"] // seq
    flops = cost.training_flops(batch=1, heads=heads, seq=seq, d_head=m["d_head"],
                                window=window)
    moved = cost.training_bytes(batch=1, heads=heads, kv_heads=m["n_kv_heads"] or heads,
                                seq=seq, d_head=m["d_head"])
    least = max(flops / run.peaks["flops_per_s_bf16"], moved / run.peaks["hbm_bytes_per_s"])
    return 100.0 * layers * rows * least / (ms / 1000.0)
