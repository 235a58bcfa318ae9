"""Work the dense decoder requires, from its shapes: multiply-adds count
two operations, only the matrix products are counted, causal attention
counts the lower triangle, and nothing is recomputed.  ``d`` is a
``bench.reference.Dims``."""
from __future__ import annotations


def layer_params(d) -> int:
    """Weights of one layer's matrix products."""
    attn = d.d * d.heads * d.head_dim * 2 + d.d * d.kv_heads * d.head_dim * 2
    return attn + 3 * d.d * d.ffn


def attention_pairs(start: int, end: int) -> int:
    """(query, key) pairs of causal attention for queries at positions
    ``[start, end)``: each attends to every position up to its own."""
    return (end * (end + 1) - start * (start + 1)) // 2


def forward_flops(d, batch: int, seq: int) -> float:
    """One forward pass over ``batch`` sequences of ``seq`` positions,
    with the head at every position (training)."""
    tokens = batch * seq
    mm = 2 * tokens * layer_params(d) * d.layers
    attn = 4 * batch * attention_pairs(0, seq) * d.heads * d.head_dim \
        * d.layers
    return mm + attn + 2 * tokens * d.d * d.vocab


def train_step_flops(d, batch: int, seq: int) -> float:
    """Forward and backward (the backward does twice the forward's
    products)."""
    return 3 * forward_flops(d, batch, seq)


def prefill_flops(d, start: int, end: int) -> float:
    """Rows ``[start, end)`` of one prompt computed over a cache that
    holds ``[0, start)``; the head at the last row only."""
    mm = 2 * (end - start) * layer_params(d) * d.layers
    attn = 4 * attention_pairs(start, end) * d.heads * d.head_dim * d.layers
    return mm + attn + 2 * d.d * d.vocab


def decode_flops(d, length: int) -> float:
    """One decode step of one sequence attending to ``length`` rows."""
    return (2 * layer_params(d) * d.layers
            + 4 * length * d.heads * d.head_dim * d.layers
            + 2 * d.d * d.vocab)
