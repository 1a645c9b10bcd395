"""Operations and bytes causal flash attention needs where v has a head width
of its own (latent attention: q and k 192 wide, v 128), from its shapes.

Required work only, as ``costs/flash_attention.py`` counts it for one width:
a causal query attends ``(seq + 1) / 2`` keys on average; the score product
``Q K^T`` costs ``2 d_qk`` a pair and the value product ``P V`` ``2 d_v``;
each width counts its own size, not the lanes it is padded to. The backward
pass is four products, two of each width (``dV = P^T dO`` and ``dP = dO V^T``
at ``d_v``, ``dQ = dS K`` and ``dK = dS^T Q`` at ``d_qk``); the recomputed
``Q K^T`` of a flash backward is not required work.
"""


def forward_flops(batch: int, heads: int, seq: int, d_qk: int, d_v: int) -> float:
    pairs = seq * (seq + 1) / 2  # causal (query, key) pairs
    return batch * heads * pairs * (2 * d_qk + 2 * d_v)  # QK^T and PV


def backward_flops(batch: int, heads: int, seq: int, d_qk: int, d_v: int) -> float:
    return 2.0 * forward_flops(batch, heads, seq, d_qk, d_v)


def training_flops(batch: int, heads: int, seq: int, d_qk: int, d_v: int) -> float:
    return 3.0 * forward_flops(batch, heads, seq, d_qk, d_v)


def forward_bytes(batch: int, heads: int, seq: int, d_qk: int, d_v: int,
                  itemsize: int = 2) -> float:
    """Read q, k, v once and write o once (plus the fp32 log-sum-exp)."""
    return batch * heads * seq * ((2 * d_qk + 2 * d_v) * itemsize + 4)


def backward_bytes(batch: int, heads: int, seq: int, d_qk: int, d_v: int,
                   itemsize: int = 2) -> float:
    """Read q, k, v, o, dO and the log-sum-exp, write dq, dk, dv."""
    return batch * heads * seq * ((4 * d_qk + 4 * d_v) * itemsize + 4)


def training_bytes(batch: int, heads: int, seq: int, d_qk: int, d_v: int,
                   itemsize: int = 2) -> float:
    return (forward_bytes(batch, heads, seq, d_qk, d_v, itemsize)
            + backward_bytes(batch, heads, seq, d_qk, d_v, itemsize))
