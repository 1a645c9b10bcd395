"""Operations and bytes causal flash attention needs, from its shapes.

Required work only: a causal query attends ``(seq + 1) / 2`` keys on
average, so the score and value products count half the square (plus the
diagonal); ``d_head`` 64 counts 64, not the 128 lanes it is padded to.
"""


def forward_flops(batch: int, heads: int, seq: int, d_head: int) -> float:
    pairs = seq * (seq + 1) / 2  # causal (query, key) pairs
    return batch * heads * pairs * (2 * d_head + 2 * d_head)  # QK^T and PV


def backward_flops(batch: int, heads: int, seq: int, d_head: int) -> float:
    # dV = P^T dO, dP = dO V^T, dQ = dS K, dK = dS^T Q: four products; the
    # recomputed QK^T of a flash backward is not required work
    return 2.0 * forward_flops(batch, heads, seq, d_head)


def training_flops(batch: int, heads: int, seq: int, d_head: int) -> float:
    return forward_flops(batch, heads, seq, d_head) + backward_flops(
        batch, heads, seq, d_head)


def forward_bytes(batch: int, heads: int, seq: int, d_head: int,
                  itemsize: int = 2) -> float:
    """Read q, k, v once and write o once (plus the fp32 log-sum-exp)."""
    return batch * heads * seq * (4 * d_head * itemsize + 4)


def backward_bytes(batch: int, heads: int, seq: int, d_head: int,
                   itemsize: int = 2) -> float:
    """Read q, k, v, o, dO and the log-sum-exp, write dq, dk, dv."""
    return batch * heads * seq * (8 * d_head * itemsize + 4)


def training_bytes(batch: int, heads: int, seq: int, d_head: int,
                   itemsize: int = 2) -> float:
    return forward_bytes(batch, heads, seq, d_head, itemsize) + backward_bytes(
        batch, heads, seq, d_head, itemsize)
