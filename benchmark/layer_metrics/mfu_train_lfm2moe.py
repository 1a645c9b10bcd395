"""Layer: client trainer. Model FLOP/s utilisation of the LFM2-8B-A1B share,
as ``mfu_train`` reads it for the dense family: tokens per second of the
median optimizer step (the window's ``trainer/fit`` spans) times the
operations one token's forward and backward pass require
(``costs/lfm2_moe_train.py``, whose routed term takes the rows the program's
counter ``moe/rows_held`` says were routed to the experts held here, both
expert stacks together; recomputed operations do not count) over the chip's
published bf16 peak. Read only where the program says its step holds conv
layers (``conv_layers`` on its ``trainer/steps`` span). Moves
``train_tokens_per_s``."""

from benchmark.costs import lfm2_moe_train
from benchmark.harness import median
from benchmark.trace.conv_attrs import conv_layers
from benchmark.trace.span_attrs import MOE_LOAD_SPAN, mean_attr


def read(run, reduction):
    spans = run.span_seconds("trainer/fit")
    if not spans or not conv_layers(run):
        return None
    rows = mean_attr(run, MOE_LOAD_SPAN, "rows_held")
    if not rows:
        return None
    step_s = median(spans) / run.traffic["steps_per_fit"]
    tokens = run.counters["tokens_per_step"]
    flops = lfm2_moe_train.flops_per_token(run.config["model"], rows / tokens)
    peak = run.peaks["flops_per_s_bf16"] * len(run.devices)
    return 100.0 * tokens / step_s * flops / peak
