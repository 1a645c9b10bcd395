"""Operations and bytes the chunked state-space scan of a Mamba-2 layer with
GROUPS of B and C needs, from its shapes (``ops/ssd.py``: ``groups`` groups,
each for its run of ``heads / groups`` heads).

As ``costs/ssd_scan.py`` counts one group: required work only, products only.
A chunk of ``Q`` positions has ``Q (Q + 1) / 2`` causal (position, earlier
position) pairs: each costs one ``C B^T`` entry A GROUP (``2 N`` operations,
shared by the group's heads) and one entry of every head's masked product with
``dt x`` (``2 P`` a head). Every position adds to its chunk's end state (``2 P
N`` a head) and reads the start state out (``2 P N`` a head). The decays,
their exponentials and the carry of the state from chunk to chunk are
elementwise and not counted; the backward pass is two products for each
forward one; what ``remat`` runs again is not required work. B and C are read
(and their gradients written) once a group.
"""


def forward_flops(seq: int, heads: int, groups: int, d_head: int, d_state: int, chunk: int,
                  batch: int = 1) -> float:
    pairs = chunk * (chunk + 1) / 2
    per_chunk = (groups * pairs * 2 * d_state  # C B^T, once a group
                 + heads * pairs * 2 * d_head  # the masked square times dt x
                 + 2 * heads * chunk * 2 * d_head * d_state)  # state in, state out
    return batch * (seq / chunk) * per_chunk


def training_flops(seq: int, heads: int, groups: int, d_head: int, d_state: int, chunk: int,
                   batch: int = 1) -> float:
    return 3.0 * forward_flops(seq, heads, groups, d_head, d_state, chunk, batch)


def forward_bytes(seq: int, heads: int, groups: int, d_head: int, d_state: int,
                  batch: int = 1, itemsize: int = 2) -> float:
    """Read x, every group's B and C once and dt in float32, write y once."""
    return batch * seq * (itemsize * (2 * heads * d_head + 2 * groups * d_state) + 4 * heads)


def training_bytes(seq: int, heads: int, groups: int, d_head: int, d_state: int,
                   batch: int = 1, itemsize: int = 2) -> float:
    """Forward, plus: read x, B, C, dt and y's gradient, write the gradients
    of x, B, C and dt."""
    backward = batch * seq * (
        itemsize * (3 * heads * d_head + 4 * groups * d_state) + 8 * heads)
    return forward_bytes(seq, heads, groups, d_head, d_state, batch, itemsize) + backward
