"""Layer: client trainer (``ops/moe.py``, the dropless expert layer). Device
milliseconds of a step under the scopes ``moe/router`` (sigmoid scores, the
selection, the gates) and ``moe/dispatch`` (the sort by expert, the permute,
the un-permute and the weighted combine), forward, backward and
recomputation: what routing costs beside the experts' own products. Static
shapes for the worst case, so it does not follow the rows routed here. Moves
``train_tokens_per_s``."""

from benchmark.trace.op_scopes import device_ms_per_step


def read(run, reduction):
    return device_ms_per_step(run, reduction, r"\bmoe/(router|dispatch)\b")
