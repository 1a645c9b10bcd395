"""Layer: client trainer (``models/mpt.py``, ``ops/ssd.ssd_scan``). Device
milliseconds of a step under the scope ``mamba/scan``: ``dt``'s softplus, the
chunked state-space scan and its skip term, forward, backward and every
recomputation (the block's under ``remat`` and the scan's own, chunk by
chunk). The self time of the operations whose ``op_name`` carries the scope,
over the trace's steps. Moves ``train_tokens_per_s``."""

from benchmark.trace.op_scopes import device_ms_per_step


def read(run, reduction):
    return device_ms_per_step(run, reduction, r"\bmamba/scan\b")
