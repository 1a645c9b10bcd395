"""Layer: client trainer. Model FLOP/s utilisation: tokens per second of the
median optimizer step (the window's ``trainer/fit`` spans, as
``step_ms_train`` reads them) times the operations one token's forward and
backward pass require (the benchmark's own causal count,
``costs/mpt_train.py``; recomputed operations do not count) over the chip's
published bf16 peak. Moves ``train_tokens_per_s``."""

from benchmark.costs import mpt_train
from benchmark.harness import median


def read(run, reduction):
    spans = run.span_seconds("trainer/fit")
    if not spans:
        return None
    step_s = median(spans) / run.traffic["steps_per_fit"]
    flops = mpt_train.flops_per_token(run.config["model"])
    peak = run.peaks["flops_per_s_bf16"] * len(run.devices)
    return 100.0 * run.counters["tokens_per_step"] / step_s * flops / peak
