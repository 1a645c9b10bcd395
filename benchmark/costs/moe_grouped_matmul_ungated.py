"""Operations and bytes the grouped products of a dropless expert layer with
UNGATED experts need (``W_down act(W_up h)``: two matrices an expert), from the
rows routed to the experts held here.

As ``costs/moe_grouped_matmul.py`` counts three products: required work only.
A row of an expert costs its two products (up, down) whatever tile it was
padded into, rows routed to absent experts cost nothing, and the backward pass
is two products for each forward one (the input's gradient and the weight's);
the forward products that ``remat`` recomputes are not required work.
"""


def forward_flops(rows: float, d_model: int, hidden: int) -> float:
    return rows * 2 * 2 * d_model * hidden  # up, down


def training_flops(rows: float, d_model: int, hidden: int) -> float:
    return 3.0 * forward_flops(rows, d_model, hidden)


def forward_bytes(rows: float, d_model: int, hidden: int, experts: int,
                  itemsize: int = 2) -> float:
    """Read the rows and every held expert's two matrices once, write the
    rows' results once (a fused layer keeps the hidden activations on chip)."""
    return itemsize * (2 * rows * d_model + experts * 2 * d_model * hidden)


def training_bytes(rows: float, d_model: int, hidden: int, experts: int,
                   itemsize: int = 2) -> float:
    """Forward, plus: read the rows, their output's gradient and the matrices
    again, write the rows' gradient and the matrices' gradients."""
    backward = itemsize * (3 * rows * d_model + 2 * experts * 2 * d_model * hidden)
    return forward_bytes(rows, d_model, hidden, experts, itemsize) + backward
