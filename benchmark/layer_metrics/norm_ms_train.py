"""Layer: client trainer (``models/mpt.py``). Device milliseconds of a step
under the scopes ``block/norm`` (every block's ``ln_1`` and ``ln_2`` and the
model's final norm), ``attn/qk_norm`` (the per-head norms of q and k) and
``mamba/gate_norm`` (the gate and the norm over the mixer's inner channels),
forward, backward and what ``remat`` runs again. XLA fuses a norm into the
product that reads it where it can, and that time then counts with the
product. One part of ``benchmark/trace/step_parts.py``'s partition. Moves
``train_tokens_per_s``."""

from benchmark.trace.step_parts import part_ms_per_step


def read(run, reduction):
    return part_ms_per_step(run, reduction, "norm")
