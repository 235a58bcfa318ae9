"""Work the hybrid of Mamba2 and attention layers requires, from its
shapes, as ``bench/costs/dense.py`` counts the dense decoder's: matrix
products only, multiply-adds as two operations, causal attention over
the lower triangle, nothing recomputed.  ``d`` is a
``bench.reference_hybrid.Dims``; the SSD's products are
``bench/costs/ssd.py``'s."""
from __future__ import annotations

from . import ssd
from .dense import attention_pairs


def layer_params(d, kind: str) -> int:
    """Weights of one layer's matrix products: its mixer's projections
    and its MLP."""
    mlp = 3 * d.d * d.ffn
    if kind == "attention":
        return mlp + 2 * d.d * d.head_dim * (d.heads + d.kv_heads)
    return mlp + d.d * (2 * d.d_inner + 2 * d.ssm_state + d.ssm_heads) \
        + d.d_inner * d.d


def forward_flops(d, batch: int, seq: int) -> float:
    """One forward pass over ``batch`` sequences of ``seq`` positions,
    with the head at every position."""
    tokens = batch * seq
    mm = 2 * tokens * sum(layer_params(d, k) for k in d.kinds)
    n_attn = d.kinds.count("attention")
    attn = 4 * batch * attention_pairs(0, seq) * d.heads * d.head_dim \
        * n_attn
    scan = ssd.forward(d, batch, seq)[0] * (d.layers - n_attn)
    return mm + attn + scan + 2 * tokens * d.d * d.vocab


def train_step_flops(d, batch: int, seq: int) -> float:
    """Forward and backward (the backward does twice the forward's
    products)."""
    return 3 * forward_flops(d, batch, seq)
