"""Layer: client trainer (``models/mpt.py``: ``_hc_maps``). Device
milliseconds of a step under the scope ``mhc/maps``: every hyper-connected
sublayer's flattened norm, the projection of the streams to the maps' logits,
the squashes and the mixing matrix's Sinkhorn iterations, forward, backward
and recomputation. The self time of the operations whose ``op_name`` carries
the scope, over the trace's steps. Moves ``train_tokens_per_s``."""

from benchmark.trace.op_scopes import device_ms_per_step


def read(run, reduction):
    return device_ms_per_step(run, reduction, r"\bmhc/maps\b")
