"""Bytes (and operations) the mixing of hyper-connected residual streams needs
around one sublayer (``models/mpt.py``: the scopes ``mhc/read_in`` and
``mhc/write_back``), from its shapes.

Required passes only, in units of one stream's ``[tokens, d_model]`` array.
Forward: the read-in reads the ``n`` streams and writes the sublayer's input
(``n + 1``); the write-back reads the ``n`` streams and the branch's output and
writes the ``n`` new streams (``2n + 1``). Backward: one pass reads the new
streams' gradient, the streams (for the mixing matrix's and the read-in
weights' gradients) and the branch's output (for the write-back weights'),
and writes the branch's gradient and the streams' (``3n + 2``); the read-in's
backward reads the gradient of the sublayer's input (1). ``6n + 5`` in all, 29
at four streams. ISSUE 44's first count, ``6n + 3``, left out the write of the
sublayer's input and the read of the branch's output, which the sublayer
between them forces. The three maps themselves (``n^2 + 2n`` float32 numbers a
token) are 96 bytes a token beside 208 KB and are left out, as are the model's
entry and exit (the embedding into every stream, their sum out). What
``remat`` runs again is not required work. On a v5e the bytes bind: 2 bytes
against ~2 operations a unit and channel.

The operations feed ``costs/xing_mhc_moe_train.py``. The bytes have no reader:
XLA runs the write-back as the epilogue of the branch's last projection and
writes the new streams inside the next sublayer's maps' reductions, so no set
of scopes holds the time of just these passes (the two mix scopes read 177 %
of this count, all three ``mhc/`` scopes 43 % with Sinkhorn's time in the
denominator; PERF.md sections 6 and 7, PR 44). A share of this roofline waits
for the mix as a kernel of its own, or a reader by fusion.
"""


def forward_units(streams: int) -> int:
    return (streams + 1) + (2 * streams + 1)


def backward_units(streams: int) -> int:
    return (3 * streams + 2) + 1


def training_bytes(tokens: int, d_model: int, streams: int, itemsize: int = 2) -> float:
    """Forward and backward of one sublayer's read-in and write-back."""
    return float(tokens * d_model * itemsize
                 * (forward_units(streams) + backward_units(streams)))


def forward_flops(tokens: int, d_model: int, streams: int) -> float:
    """A multiply and an add for each weight: ``n`` in the read-in, ``n^2 + n``
    in the write-back, per channel."""
    return float(tokens * d_model * 2 * (streams * streams + 2 * streams))


def training_flops(tokens: int, d_model: int, streams: int) -> float:
    return 3.0 * forward_flops(tokens, d_model, streams)
