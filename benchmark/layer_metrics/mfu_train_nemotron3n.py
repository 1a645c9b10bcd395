"""Layer: client trainer. Model FLOP/s utilisation of the Nemotron-3-Nano
share, as ``mfu_train`` reads it for the dense family: tokens per second of the
median optimizer step (the window's ``trainer/fit`` spans) times the
operations one token's forward and backward pass require
(``costs/nemotron_h_moe_train.py``: a layer by its one branch; its routed term
takes the rows the program's counter ``moe/rows_held`` says were routed to the
experts held here, all expert layers together; recomputed operations do not
count) over the chip's published bf16 peak. Read only where the program says
its step's layers are one branch each (``moe_layers`` beside ``mamba_groups``
on its ``trainer/steps`` span). The share of the whole step that bounds any
later claim in the cell. Moves ``train_tokens_per_s``."""

from benchmark.costs import nemotron_h_moe_train
from benchmark.harness import median
from benchmark.trace.nemotron_attrs import static_count
from benchmark.trace.span_attrs import MOE_LOAD_SPAN, mean_attr


def read(run, reduction):
    spans = run.span_seconds("trainer/fit")
    if not spans or not static_count(run, "moe_layers") or not static_count(run, "mamba_groups"):
        return None
    rows = mean_attr(run, MOE_LOAD_SPAN, "rows_held")
    if not rows:
        return None
    step_s = median(spans) / run.traffic["steps_per_fit"]
    tokens = run.counters["tokens_per_step"]
    flops = nemotron_h_moe_train.flops_per_token(run.config["model"], rows / tokens)
    peak = run.peaks["flops_per_s_bf16"] * len(run.devices)
    return 100.0 * tokens / step_s * flops / peak
