"""Layer: client trainer (``ops/dsa.py``, learned sparse attention). Device
milliseconds of a step under the scope ``dsa/indexer``: the indexer's three
projections of the block's detached input, its key's LayerNorm and the
rotation, forward, the weights' gradients and what ``remat`` runs again. Moves
``train_tokens_per_s``."""

from benchmark.trace.op_scopes import device_ms_per_step


def read(run, reduction):
    return device_ms_per_step(run, reduction, r"\bdsa/indexer\b")
