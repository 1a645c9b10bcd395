"""Layer: client trainer (``ops/dsa.py``, learned sparse attention). Device
milliseconds of a step under the scope ``dsa/select``: the index scores of
every query chunk against all keys, each query's exact threshold (a search
over the float's bits), the mask by (query, key) and its tile counts. The
selection takes no gradient; ``remat`` makes it twice a layer and step. Moves
``train_tokens_per_s``."""

from benchmark.trace.op_scopes import device_ms_per_step


def read(run, reduction):
    return device_ms_per_step(run, reduction, r"\bdsa/select\b")
