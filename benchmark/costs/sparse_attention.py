"""Operations and bytes attention over picked (query, key) pairs needs, from
the pairs the selection picked and the shapes.

Required work only: a picked pair costs one score and one value product a
head (``2 d_head`` each), a pair that was not picked costs nothing, whatever
tile it sat in; the backward pass is two products for each forward one (the
recomputed scores of a flash backward are not required work). Bytes: q, k, v,
o and their gradients once each, the float32 log-sum-exp, and the selection
once at one bit a (query, key) position, shared by the heads.
"""


def forward_flops(pairs: float, heads: int, d_head: int) -> float:
    return pairs * heads * (2 * d_head + 2 * d_head)  # QK^T and PV


def backward_flops(pairs: float, heads: int, d_head: int) -> float:
    return 2.0 * forward_flops(pairs, heads, d_head)


def training_flops(pairs: float, heads: int, d_head: int) -> float:
    return forward_flops(pairs, heads, d_head) + backward_flops(pairs, heads, d_head)


def selection_bytes(batch: int, seq: int) -> float:
    return batch * seq * seq / 8.0


def forward_bytes(batch: int, heads: int, kv_heads: int, seq: int, d_head: int,
                  itemsize: int = 2) -> float:
    """Read q, k, v and the selection once, write o and the log-sum-exp."""
    rows = batch * seq
    return (rows * (2 * heads + 2 * kv_heads) * d_head * itemsize + 4 * rows * heads
            + selection_bytes(batch, seq))


def backward_bytes(batch: int, heads: int, kv_heads: int, seq: int, d_head: int,
                   itemsize: int = 2) -> float:
    """Read q, k, v, o, dO, the log-sum-exp and the selection, write dq, dk, dv."""
    rows = batch * seq
    return (rows * (4 * heads + 4 * kv_heads) * d_head * itemsize + 4 * rows * heads
            + selection_bytes(batch, seq))


def training_bytes(batch: int, heads: int, kv_heads: int, seq: int, d_head: int,
                   itemsize: int = 2) -> float:
    return (forward_bytes(batch, heads, kv_heads, seq, d_head, itemsize)
            + backward_bytes(batch, heads, kv_heads, seq, d_head, itemsize))
