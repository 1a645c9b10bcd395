"""Operations and bytes the index scores of learned sparse attention need:
``I[t, s] = sum_j w[t, j] * relu(qI[t, j] . kI[s])`` for every causal pair,
``J`` indexer heads of ``Di`` against one key head.

Required work only, products only. The selection needs every causal pair's
score once (``2 Di`` operations a head). The alignment loss's gradient is
non-zero on the picked pairs alone: two products a picked pair and head
(``dqI`` and ``dkI``). The ``relu``, the head-weighted sum and the threshold
search are elementwise or comparisons and not counted; the scores the loss
computes a second time are recomputation, not required work.
"""


def forward_flops(causal_pairs: float, heads: int, dim: int) -> float:
    return causal_pairs * heads * 2 * dim


def backward_flops(picked_pairs: float, heads: int, dim: int) -> float:
    return 2.0 * picked_pairs * heads * 2 * dim


def training_flops(causal_pairs: float, picked_pairs: float, heads: int, dim: int) -> float:
    return forward_flops(causal_pairs, heads, dim) + backward_flops(picked_pairs, heads, dim)


def training_bytes(batch: int, seq: int, heads: int, dim: int, itemsize: int = 2) -> float:
    """Read qI, kI and w, write the selection at one bit a position; read
    them again with the selection and write their gradients."""
    rows = batch * seq
    operands = rows * (heads * dim + dim) * itemsize + 4 * rows * heads
    return 3.0 * operands + 2.0 * batch * seq * seq / 8.0
