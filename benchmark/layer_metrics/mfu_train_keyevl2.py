"""Layer: client trainer. Model FLOP/s utilisation of the Keye-VL-2.0 share,
as ``mfu_train`` reads it for the dense family: tokens per second of the
median optimizer step (the window's ``trainer/fit`` spans) times the
operations one token's forward and backward pass require
(``costs/keye_sparse_moe_train.py``, whose attention term takes the pairs the
program's counter ``dsa/picked_pairs`` says the indexers picked and whose
routed term the rows ``moe/rows_held`` says were routed to the experts held
here; recomputed operations and the tiles' unpicked pairs do not count) over
the chip's published bf16 peak. Moves ``train_tokens_per_s``."""

from benchmark.costs import keye_sparse_moe_train
from benchmark.harness import median
from benchmark.trace.dsa_attrs import picked_pairs
from benchmark.trace.span_attrs import MOE_LOAD_SPAN, mean_attr


def read(run, reduction):
    spans = run.span_seconds("trainer/fit")
    rows = mean_attr(run, MOE_LOAD_SPAN, "rows_held")
    pairs = picked_pairs(run)
    if not spans or not rows or not pairs:
        return None
    step_s = median(spans) / run.traffic["steps_per_fit"]
    tokens = run.counters["tokens_per_step"]
    flops = keye_sparse_moe_train.flops_per_token(
        run.config["model"], rows / tokens, pairs / tokens)
    peak = run.peaks["flops_per_s_bf16"] * len(run.devices)
    return 100.0 * tokens / step_s * flops / peak
