"""Layer: client trainer (``models/mpt.py``, ``MPTBlock._short_conv_mixer``).
Device milliseconds of a step under the scope ``shortconv/proj``: a conv
layer's in-projection to ``B | C | u`` and its out-projection, forward,
backward and recomputation. The self time of the operations whose ``op_name``
carries the scope, over the trace's steps. Moves ``train_tokens_per_s``."""

from benchmark.trace.op_scopes import device_ms_per_step


def read(run, reduction):
    return device_ms_per_step(run, reduction, r"\bshortconv/proj\b")
