"""Layer: client trainer (``models/mpt.py``). Device milliseconds of a step
under the scope ``attn/proj``: attention's projections on the non-latent
branch (``wqkv``, or ``q_proj`` / ``k_proj`` / ``v_proj``), the reshapes to
heads, the rotation, ``out_proj`` and its residual add, forward, backward and
what ``remat`` runs again; the score and value products between them are the
kernel's (``flash_*_ms_train``), the per-head norms ``norm_ms_train``'s, the
latent branch ``mla_proj_ms_train``'s. One part of
``benchmark/trace/step_parts.py``'s partition. Moves ``train_tokens_per_s``."""

from benchmark.trace.step_parts import part_ms_per_step


def read(run, reduction):
    return part_ms_per_step(run, reduction, "attn_proj")
