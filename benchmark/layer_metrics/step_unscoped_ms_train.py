"""Layer: client trainer (``train/train_step.py``). Device milliseconds of a
step under no ``train_step/`` scope and in no finer part of
``benchmark/trace/step_parts.py``'s partition: operations without an
``op_name``, other programs inside the traced window, and whatever a later
change adds to the step outside its stages. It should read near 0; it is the
guard that nothing fell out of the partition. Moves ``train_tokens_per_s``."""

from benchmark.trace.step_parts import part_ms_per_step


def read(run, reduction):
    return part_ms_per_step(run, reduction, "step_unscoped")
