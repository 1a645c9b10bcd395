"""Layer: kernels (``ops/moe.py``: the grouped products of a dropless expert
layer whose experts are ungated, megablox's Pallas kernel). Their share of
their roofline: the least time the chip could take for the required work
(``costs/moe_grouped_matmul_ungated.py``, two products a row, at the rows the
program's counter ``moe/rows_held`` says were routed to the experts held here,
forward and backward: the larger of operations over the bf16 peak and bytes
over the HBM peak) over the device time of a step under the scope
``moe/experts``. The forward products that ``remat`` runs again are in the
time and not in the work. The number of expert layers is the program's own
word (``moe_layers`` on its ``trainer/steps`` span). Moves
``train_tokens_per_s``."""

from benchmark.costs import moe_grouped_matmul_ungated as cost
from benchmark.trace.nemotron_attrs import static_count
from benchmark.trace.op_scopes import device_ms_per_step
from benchmark.trace.span_attrs import MOE_LOAD_SPAN, mean_attr


def read(run, reduction):
    ms = device_ms_per_step(run, reduction, r"\bmoe/experts\b")
    rows = mean_attr(run, MOE_LOAD_SPAN, "rows_held")
    layers = static_count(run, "moe_layers")
    if not ms or not rows or not layers:
        return None
    m = run.config["model"]
    shape = dict(d_model=m["d_model"], hidden=m["mlp_hidden_size"])
    experts = layers * (m["moe_experts_held"] or m["moe_num_experts"])
    least = max(cost.training_flops(rows, **shape) / run.peaks["flops_per_s_bf16"],
                cost.training_bytes(rows, experts=experts, **shape)
                / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / (ms / 1000.0)
