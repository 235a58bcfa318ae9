"""Required work of the paged flash-decode kernel
(``kernels/flash_decode.py``) for one sequence, one layer and one step
that attends to ``length`` cached rows, in bf16: the rows in use of K and
V read once, q read and o written; scores and the weighted sum of values.
Counted by the length served, not by the pages or grid steps visited."""
from __future__ import annotations

BYTES = 2


def step(d, length: int):
    flops = 4 * length * d.heads * d.head_dim
    rows = 2 * length * d.kv_heads * d.head_dim
    return flops, BYTES * (rows + 2 * d.heads * d.head_dim)
