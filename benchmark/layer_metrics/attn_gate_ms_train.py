"""Layer: client trainer (``models/mpt.py``). Device milliseconds of a step
under the scope ``attn/gate``: the headwise gate on attention's output (its
``[D, H]`` product, the sigmoid and the multiply by head), forward, backward
and recomputation. The self time of the operations whose ``op_name`` carries
the scope, over the trace's steps. Moves ``train_tokens_per_s``."""

from benchmark.trace.op_scopes import device_ms_per_step


def read(run, reduction):
    return device_ms_per_step(run, reduction, r"\battn/gate\b")
