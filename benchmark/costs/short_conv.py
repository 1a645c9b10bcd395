"""Operations and bytes the gated short convolution of a ``conv`` layer needs
between its two projections (``models/mpt.py``: the scope ``shortconv/mix``),
from its shapes.

Required work only. A token and channel cost the gate ``B * u``, a multiply
and an add for each tap, and the gate ``C *``; the backward pass is twice
that. The forward reads the in-projection's ``B | C | u`` (three times
``d_model`` wide) and writes the gated output (``d_model``); the backward
reads ``B | C | u`` and the output's gradient and writes the gradient of
``B | C | u``. The taps' own weights and gradient (``taps x d_model``) are
counted once a layer and row. What ``remat`` runs again is not required work.
On a v5e the bytes bind: 22 bytes a token and channel against ~24 operations.
"""


def forward_flops(seq: int, d_model: int, taps: int, batch: int = 1) -> float:
    return batch * seq * d_model * (2 * taps + 2)


def training_flops(seq: int, d_model: int, taps: int, batch: int = 1) -> float:
    return 3.0 * forward_flops(seq, d_model, taps, batch)


def forward_bytes(seq: int, d_model: int, taps: int, batch: int = 1,
                  itemsize: int = 2) -> float:
    """Read B | C | u once, write the gated output once; the taps in float32."""
    return batch * (seq * itemsize * 4 * d_model + 4 * taps * d_model)


def training_bytes(seq: int, d_model: int, taps: int, batch: int = 1,
                   itemsize: int = 2) -> float:
    """Forward, plus: read B | C | u and the output's gradient, write the
    gradient of B | C | u and of the taps."""
    backward = batch * (seq * itemsize * 7 * d_model + 4 * taps * d_model)
    return forward_bytes(seq, d_model, taps, batch, itemsize) + backward
