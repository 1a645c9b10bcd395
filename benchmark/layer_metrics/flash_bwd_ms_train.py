"""Layer: kernels (``ops/flash_attention.py``). Device milliseconds of a step
in the flash kernel's two backward launches, found by their own names
(``flash_dq`` and ``flash_dkv``) in the launch's ``op_name``, over the
trace's steps. Moves ``train_tokens_per_s``."""

from benchmark.trace.op_scopes import device_ms_per_step


# the launches themselves: either kernel's scope with the pallas_call inside it
KERNEL = r"\bflash_d(q|kv)/multihead_attention\b.*pallas_call"


def read(run, reduction):
    return device_ms_per_step(run, reduction, KERNEL)
