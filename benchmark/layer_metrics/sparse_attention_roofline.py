"""Layer: kernels (``ops/masked_flash_attention.py``: flash attention under
the mask the indexer's selection made). Its share of its roofline: the least
time the chip could take for the required work (``costs/sparse_attention.py``
at the (query, key) pairs the program's counter says were picked, forward and
backward of every layer once: the larger of operations over the bf16 peak and
bytes over the HBM peak) over the device time of a step in ALL the kernel's
launches, found by their own names (``flash_fwd``, ``flash_dq``,
``flash_dkv``). The kernel visits every tile that holds a picked pair and
computes the whole tile, and ``remat`` runs the forward twice: both are in the
time and not in the work. Moves ``train_tokens_per_s``."""

from benchmark.costs import sparse_attention as cost
from benchmark.trace.dsa_attrs import dsa_layers, picked_pairs
from benchmark.trace.op_scopes import device_ms_per_step

KERNELS = r"\bflash_(fwd|dq|dkv)/multihead_attention\b.*pallas_call"


def read(run, reduction):
    ms = device_ms_per_step(run, reduction, KERNELS)
    layers, pairs = dsa_layers(run), picked_pairs(run)
    if not ms or not layers or not pairs:
        return None
    m = run.config["model"]
    rows = run.counters["tokens_per_step"] // m["max_seq_len"]
    least = max(
        cost.training_flops(pairs, m["n_heads"], m["head_dim"])
        / run.peaks["flops_per_s_bf16"],
        layers * cost.training_bytes(rows, m["n_heads"], m["n_kv_heads"],
                                     m["max_seq_len"], m["head_dim"])
        / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / (ms / 1000.0)
