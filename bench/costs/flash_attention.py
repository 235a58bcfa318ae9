"""Required work of the flash-attention kernels (``kernels/flash_attention.py``)
for one layer over ``batch`` causal sequences of ``seq`` positions, in
bf16: the forward's two products over the lower triangle, and the
backward's four (dV, dP, dQ, dK), without the recomputed scores.  Bytes:
each operand read once and each result written once, keys and values at
their own (KV) heads."""
from __future__ import annotations

from .dense import attention_pairs

BYTES = 2


def forward(d, batch: int, seq: int):
    flops = 4 * batch * attention_pairs(0, seq) * d.heads * d.head_dim
    q = batch * seq * d.heads * d.head_dim
    kv = batch * seq * d.kv_heads * d.head_dim
    return flops, BYTES * (2 * q + 2 * kv)          # q, k, v in; o out


def backward(d, batch: int, seq: int):
    flops = 8 * batch * attention_pairs(0, seq) * d.heads * d.head_dim
    q = batch * seq * d.heads * d.head_dim
    kv = batch * seq * d.kv_heads * d.head_dim
    # q, o, dO, k, v in; dq, dk, dv out
    return flops, BYTES * (4 * q + 4 * kv)
