"""Exact in-kernel relayouts of per-row vectors.

A TPU block's last two dims must be (8, 128) multiples or the full array
dims, so per-position statistics travel lane-dense as ``(1, N)`` rows,
while the tile math broadcasts them as ``(N, 1)`` columns.  These
helpers convert between the two with a masked sum over the identity:
every output element is one input element plus zeros, so the result is
exact, and the (N, N) select costs less than one (N, N) score tile.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _eye(n: int):
    return (jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
            == jax.lax.broadcasted_iota(jnp.int32, (n, n), 1))


def col_to_row(col):
    """(N, 1) -> (1, N)."""
    return jnp.sum(jnp.where(_eye(col.shape[0]), col, 0.0), axis=0,
                   keepdims=True)


def row_to_col(row):
    """(1, N) -> (N, 1)."""
    return jnp.sum(jnp.where(_eye(row.shape[1]), row, 0.0), axis=1,
                   keepdims=True)
