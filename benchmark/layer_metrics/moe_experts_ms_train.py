"""Layer: client trainer (``ops/moe.py``, the dropless expert layer). Device
milliseconds of a step under the scopes ``moe/experts`` (the grouped products
of the experts held here and the activation between them) and
``moe/shared_expert`` (the SwiGLU every token passes), forward, backward and
recomputation. Moves ``train_tokens_per_s``."""

from benchmark.trace.op_scopes import device_ms_per_step


def read(run, reduction):
    return device_ms_per_step(run, reduction, r"\bmoe/(experts|shared_expert)\b")
