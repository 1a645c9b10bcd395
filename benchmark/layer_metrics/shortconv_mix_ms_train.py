"""Layer: client trainer (``models/mpt.py``, ``ops/ssd.causal_conv1d``).
Device milliseconds of a step under the scope ``shortconv/mix``: a conv
layer's split into ``B | C | u``, the gate ``B * u``, the causal taps and the
gate ``C *``, forward, backward and recomputation. The self time of the
operations whose ``op_name`` carries the scope, over the trace's steps. Moves
``train_tokens_per_s``."""

from benchmark.trace.op_scopes import device_ms_per_step


def read(run, reduction):
    return device_ms_per_step(run, reduction, r"\bshortconv/mix\b")
