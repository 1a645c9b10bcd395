"""Layer: kernels (``ops/flash_attention.py``). Device milliseconds of a step
in the flash kernel's three WINDOWED launches (the sliding-window layers'
forward, recomputed forward, dq and dk/dv, which walk the band), found by
their own names (``flash_swa_fwd``, ``flash_swa_dq``, ``flash_swa_dkv``) in
the launch's ``op_name``, over the trace's steps. ``flash_fwd_ms_train`` /
``flash_bwd_ms_train`` keep reading the full layers' launches. Moves
``train_tokens_per_s``."""

from benchmark.trace.op_scopes import device_ms_per_step

# the launches themselves: a kernel's scope with the pallas_call inside it
KERNEL = r"\bflash_swa_(fwd|dq|dkv)/multihead_attention\b.*pallas_call"


def read(run, reduction):
    return device_ms_per_step(run, reduction, KERNEL)
