"""Layer: kernels (``models/mpt.py`` over ``ops/ssd.causal_conv1d``: the gated
short convolution between a conv layer's two projections, ``jax.numpy``). Its
share of its roofline: the least time the chip could take for the required
work (``costs/short_conv.py``, forward and backward of every conv layer and
row of a step once: the larger of operations over the bf16 peak and bytes over
the HBM peak; the bytes bind) over the device time of a step under the scope
``shortconv/mix``. What ``remat`` runs again is in the time and not in the
work. The number of layers is the program's own word (``conv_layers`` on its
``trainer/steps`` span). Moves ``train_tokens_per_s``."""

from benchmark.costs import short_conv as cost
from benchmark.trace.conv_attrs import conv_layers
from benchmark.trace.op_scopes import device_ms_per_step


def read(run, reduction):
    ms = device_ms_per_step(run, reduction, r"\bshortconv/mix\b")
    layers = conv_layers(run)
    if not ms or not layers:
        return None
    m = run.config["model"]
    shape = dict(seq=m["max_seq_len"], d_model=m["d_model"], taps=m["conv_kernel_size"])
    rows = run.counters["tokens_per_step"] // m["max_seq_len"]
    least = max(cost.training_flops(**shape) / run.peaks["flops_per_s_bf16"],
                cost.training_bytes(**shape) / run.peaks["hbm_bytes_per_s"])
    return 100.0 * layers * rows * least / (ms / 1000.0)
