"""Layer: client trainer (``ops/dsa.py``, learned sparse attention). Device
milliseconds of a step under the scope ``dsa/index_loss``: the second pass
over q.k for the heads' mean probabilities over the picked keys, the index
scores again, the alignment loss and its gradient into the indexer (formed
in the forward's chunk loop), and what ``remat`` runs again. Moves
``train_tokens_per_s``."""

from benchmark.trace.op_scopes import device_ms_per_step


def read(run, reduction):
    return device_ms_per_step(run, reduction, r"\bdsa/index_loss\b")
