"""Layer: kernels (``ops/flash_attention.py``). Device milliseconds of a step
in the flash kernel's forward launches, found by the kernel's own name
(``flash_fwd``) in the launch's ``op_name``, over the trace's steps.
``flash_attention_roofline`` times forward and backward together by the
enclosing ``multihead_attention``; this and ``flash_bwd_ms_train`` split it.
Moves ``train_tokens_per_s``."""

from benchmark.trace.op_scopes import device_ms_per_step


# the launch itself: the kernel's scope with the pallas_call inside it
KERNEL = r"\bflash_fwd/multihead_attention\b.*pallas_call"


def read(run, reduction):
    return device_ms_per_step(run, reduction, KERNEL)
