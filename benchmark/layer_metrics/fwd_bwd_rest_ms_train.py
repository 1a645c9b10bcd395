"""Layer: client trainer (``train/train_step.py``). Device milliseconds of a
step under ``train_step/forward_backward`` and in no finer part of
``benchmark/trace/step_parts.py``'s partition (no kernel's launch, no scope
of a block or of a family, not the loss head): the layer scan's stacking
copies and loop carries, the embedding and its gradient, the accumulation
scan's adds, what surrounds a kernel's launch. A program without the finer
scopes (a parent commit) has all of its blocks here. Moves
``train_tokens_per_s``."""

from benchmark.trace.step_parts import part_ms_per_step


def read(run, reduction):
    return part_ms_per_step(run, reduction, "fwd_bwd_rest")
