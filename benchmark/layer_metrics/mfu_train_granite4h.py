"""Layer: client trainer. Model FLOP/s utilisation of the Granite-4.0-H stage,
as ``mfu_train`` reads it for the dense family: tokens per second of the
median optimizer step (the window's ``trainer/fit`` spans) times the
operations one token's forward and backward pass require
(``costs/granite_hybrid_train.py``; recomputed operations do not count) over
the chip's published bf16 peak. Read only where the program says its step
holds Mamba-2 layers (``mamba_layers`` on its ``trainer/steps`` span). Moves
``train_tokens_per_s``."""

from benchmark.costs import granite_hybrid_train
from benchmark.harness import median
from benchmark.trace.mamba_attrs import mamba_layers


def read(run, reduction):
    spans = run.span_seconds("trainer/fit")
    if not spans or not mamba_layers(run):
        return None
    step_s = median(spans) / run.traffic["steps_per_fit"]
    tokens = run.counters["tokens_per_step"]
    flops = granite_hybrid_train.flops_per_token(run.config["model"])
    peak = run.peaks["flops_per_s_bf16"] * len(run.devices)
    return 100.0 * tokens / step_s * flops / peak
