"""Layer: client trainer (``train/train_step.py``). Device milliseconds of a
step under the scope ``train_step/optimizer`` (``tx.update`` and
``apply_updates``): the self time of the operations whose ``op_name`` carries
the scope, over the trace's steps. Moves ``train_tokens_per_s``."""

from benchmark.trace.op_scopes import device_ms_per_step


def read(run, reduction):
    return device_ms_per_step(run, reduction, r"train_step/optimizer")
