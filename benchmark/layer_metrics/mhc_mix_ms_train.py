"""Layer: client trainer (``models/mpt.py``: ``_hc_read_in``,
``_write_back``). Device milliseconds of a step under the scopes
``mhc/read_in`` and ``mhc/write_back``: the sublayers' inputs read out of the
residual streams, the streams mixed and the branches written back into them
(and the model's exit sum), forward, backward and recomputation. The self time
of the operations whose ``op_name`` carries either scope, over the trace's
steps. Moves ``train_tokens_per_s``."""

from benchmark.trace.op_scopes import device_ms_per_step

MIX = r"\bmhc/(read_in|write_back)\b"


def read(run, reduction):
    return device_ms_per_step(run, reduction, MIX)
