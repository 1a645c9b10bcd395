"""Layer: client trainer (``models/mpt.py``). Device milliseconds of a step
under the scope ``mla/proj``: latent attention's two low-rank projection
pairs, the norms between them, RoPE, the assembly of the per-head keys and
the output projection, forward, backward and recomputation (the score and
value products between them are the flash kernel's, ``flash_*_ms_train``).
The self time of the operations whose ``op_name`` carries the scope, over
the trace's steps. Moves ``train_tokens_per_s``."""

from benchmark.trace.op_scopes import device_ms_per_step


def read(run, reduction):
    return device_ms_per_step(run, reduction, r"\bmla/proj\b")
