"""Required work of the Mamba2 SSD scan (``kernels/mamba2_scan.py``) for
one layer over ``batch`` sequences of ``seq`` positions, at the
configuration's chunk of ``d.chunk`` positions: products only, in bf16
bytes, each operand read once and each result written once.

Per chunk of L positions (T = L(L+1)/2 pairs of the causal triangle):
C·Bᵀ over the lower triangle once per group (one group), the masked
product with x·dt per head, and per head the chunk-state terms: the
state's update from the chunk (P x N from L positions) and the output
from the carried state (L x P from N).  The backward does each product's
two transposed products, without recompute.  A later kernel at another
chunk or precision is read against the same work."""
from __future__ import annotations

BYTES = 2


def _chunks(d, seq: int):
    chunk = min(d.chunk, seq)
    return -(-seq // chunk), chunk


def _elements(d, seq: int):
    """Elements of x, dt, B and C together, and of y, for one sequence."""
    hp = d.ssm_heads * d.ssm_head_dim
    return seq * (hp + d.ssm_heads + 2 * d.ssm_state), seq * hp


def forward(d, batch: int, seq: int):
    nc, chunk = _chunks(d, seq)
    tri = chunk * (chunk + 1) // 2
    h, p, n = d.ssm_heads, d.ssm_head_dim, d.ssm_state
    per_chunk = 2 * tri * n + h * (2 * tri * p + 2 * 2 * chunk * p * n)
    inputs, y = _elements(d, seq)
    return batch * nc * per_chunk, BYTES * batch * (inputs + y)


def backward(d, batch: int, seq: int):
    flops, _ = forward(d, batch, seq)
    inputs, y = _elements(d, seq)
    # x, dt, B, C and dy in; dx, ddt, dB, dC out
    return 2 * flops, BYTES * batch * (2 * inputs + y)
