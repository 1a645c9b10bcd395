"""Operations and bytes windowed (banded) causal flash attention needs, from
its shapes: query ``t`` attends keys ``t - window < j <= t``, its own among
them, so ``min(t + 1, window)`` keys.

Required work only: the score and value products over the band's pairs and no
other (a tile that straddles the band's edge multiplies pairs no query sees:
not required); ``d_head`` counts its own size. Grouped heads: q, o, dO and dq
are a query head's, k, v, dk and dv a key-value head's, read and written
once. The backward pass is four products for the forward's two; the
recomputed ``Q K^T`` of a flash backward is not required work.
"""


def band_pairs(seq: int, window: int) -> float:
    """(query, key) pairs of one head: ``sum_t min(t + 1, window)``."""
    w = min(window, seq)
    return w * (w + 1) / 2 + (seq - w) * w


def forward_flops(batch: int, heads: int, seq: int, d_head: int, window: int) -> float:
    return batch * heads * band_pairs(seq, window) * (2 * d_head + 2 * d_head)  # QK^T, PV


def backward_flops(batch: int, heads: int, seq: int, d_head: int, window: int) -> float:
    # dV = P^T dO, dP = dO V^T, dQ = dS K, dK = dS^T Q
    return 2.0 * forward_flops(batch, heads, seq, d_head, window)


def training_flops(batch: int, heads: int, seq: int, d_head: int, window: int) -> float:
    return 3.0 * forward_flops(batch, heads, seq, d_head, window)


def forward_bytes(batch: int, heads: int, kv_heads: int, seq: int, d_head: int,
                  itemsize: int = 2) -> float:
    """Read q, k, v once and write o once (plus the fp32 log-sum-exp)."""
    return batch * seq * ((2 * heads + 2 * kv_heads) * d_head * itemsize + 4 * heads)


def backward_bytes(batch: int, heads: int, kv_heads: int, seq: int, d_head: int,
                   itemsize: int = 2) -> float:
    """Read q, k, v, o, dO and the log-sum-exp, write dq, dk, dv."""
    return batch * seq * ((4 * heads + 4 * kv_heads) * d_head * itemsize + 4 * heads)


def training_bytes(batch: int, heads: int, kv_heads: int, seq: int, d_head: int,
                   itemsize: int = 2) -> float:
    return (forward_bytes(batch, heads, kv_heads, seq, d_head, itemsize)
            + backward_bytes(batch, heads, kv_heads, seq, d_head, itemsize))
