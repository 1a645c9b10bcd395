"""Layer: client trainer (``train/train_step.py``). Device milliseconds of a
step under the scope ``train_step/grad_norm``: the gradient's global norm and
the logged norm of the new weights, one reduction a leaf each. The clip
inside the optimizer takes the same gradient norm under
``train_step/optimizer``; XLA keeps one of the two (PERF.md section 5 says
under which name). One part of ``benchmark/trace/step_parts.py``'s partition.
Moves ``train_tokens_per_s``."""

from benchmark.trace.step_parts import part_ms_per_step


def read(run, reduction):
    return part_ms_per_step(run, reduction, "grad_norm")
