"""Gradient accumulation — the paper's enabling mechanism (Section IV-A.4).

``accumulate_gradients`` splits the per-step batch into micro-batches
along the batch axis and scans over them, summing gradients. From the
optimizer's perspective this is *exactly* one step at the full batch size
(Eq. 1 is linear in the per-sample gradients), which is the paper's "no
accuracy change" claim; ``tests/test_grad_accum.py`` proves the
equivalence numerically.

Non-divisor splits are supported with the same semantics the simulator
prices (``candidate_sub_batches`` / ``PerfParams.t_iter_sub``): the
micro-batch size is ``b = ceil(B / accum_steps)``, the scan runs
``s = ceil(B / b)`` steps, and the final micro-batch absorbs the
remainder — padded to ``b`` rows and masked via a per-sample
``sample_mask`` entry so padded rows contribute nothing to the DATA loss
or its gradients. Each micro-batch's mean is re-weighted by its
valid-sample count, so the result is still the exact full-batch mean of
the CE term. Caveat (same family as DESIGN.md §8): the MoE load-balance
aux loss is a batch statistic — it is not linear in the batch split even
for divisible batches, and padded rows additionally pass through the
router — so exactness claims are about the data loss (aux_loss_weight=0
for strict MoE equivalence, as ``tests/test_grad_accum.py`` pins).

The accumulation buffer dtype is configurable: bf16 accumulation halves
the working set for the >=100B configs (DESIGN.md §7).
"""
from __future__ import annotations

import math
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp


def _leading_dim(batch) -> int:
    dims = {leaf.shape[0] for leaf in jax.tree.leaves(batch)}
    assert len(dims) == 1, f"inconsistent batch leading dims: {dims}"
    return dims.pop()


def accumulate_gradients(
    loss_and_grad: Callable,           # (params, micro_batch) -> (loss, grads)
    params,
    batch,
    accum_steps: int,
    *,
    accum_dtype=jnp.float32,
) -> Tuple[jnp.ndarray, Any]:
    """Returns (mean loss, mean grads) over the micro-batches of ``batch``.

    ``batch`` is a pytree whose leaves have a common leading dim B;
    micro-batches are ``leaf[i*b:(i+1)*b]`` with ``b = ceil(B /
    accum_steps)``. When ``b`` does not divide B the final micro-batch is
    padded and a ``sample_mask`` key is added (``batch`` must then be a
    dict and ``loss_and_grad`` mask-aware, as ``loss_fn`` is).
    """
    if accum_steps <= 1:
        return loss_and_grad(params, batch)

    # ``sample_mask`` is reserved for the ragged-path injection below: a
    # caller-supplied mask would be clobbered on the ragged path and
    # mis-weighted by the uniform 1/steps average on the divisible one.
    assert not (isinstance(batch, dict) and "sample_mask" in batch), (
        "sample_mask is injected by accumulate_gradients; pre-masked "
        "batches are only supported with accum_steps=1")

    big = _leading_dim(batch)
    sub = math.ceil(big / accum_steps)
    steps = math.ceil(big / sub)

    if big % sub == 0:
        # uniform micro-batches: the historical exact path
        def micro(leaf):
            return leaf.reshape(steps, sub, *leaf.shape[1:])

        micro_batches = jax.tree.map(micro, batch)

        def step(carry, mb):
            loss_acc, grads_acc = carry
            loss, grads = loss_and_grad(params, mb)
            with jax.named_scope("accumulate"):
                grads_acc = jax.tree.map(
                    lambda a, g: a + g.astype(accum_dtype), grads_acc, grads)
            return (loss_acc + loss.astype(jnp.float32), grads_acc), None

        zeros = jax.tree.map(
            lambda p: jnp.zeros(p.shape, accum_dtype), params)
        (loss_sum, grads_sum), _ = jax.lax.scan(
            step, (jnp.zeros((), jnp.float32), zeros), micro_batches)
        inv = 1.0 / steps
        return loss_sum * inv, jax.tree.map(lambda g: g * inv, grads_sum)

    # ragged final micro-batch: pad + mask, weight each micro by its
    # valid-sample share so the sum is the exact full-batch mean
    assert isinstance(batch, dict), (
        "non-divisor grad accumulation needs a dict batch (a sample_mask "
        f"entry is injected); got {type(batch).__name__}")
    last = big - (steps - 1) * sub
    padded = steps * sub

    def micro(leaf):
        pad = [(0, padded - big)] + [(0, 0)] * (leaf.ndim - 1)
        return jnp.pad(leaf, pad).reshape(steps, sub, *leaf.shape[1:])

    micro_batches = jax.tree.map(micro, batch)
    micro_batches["sample_mask"] = (
        jnp.arange(padded, dtype=jnp.float32).reshape(steps, sub) < big
    ).astype(jnp.float32)
    counts = jnp.full((steps,), float(sub), jnp.float32).at[-1].set(last)
    weights = counts / big                       # sums to 1

    def step(carry, inp):
        loss_acc, grads_acc = carry
        mb, wgt = inp
        loss, grads = loss_and_grad(params, mb)
        # weight in f32, then cast: keeps the n_i/B factor exact and the
        # scan carry dtype stable when accum_dtype is bf16
        with jax.named_scope("accumulate"):
            grads_acc = jax.tree.map(
                lambda a, g: a + (wgt * g).astype(accum_dtype),
                grads_acc, grads)
        return (loss_acc + wgt * loss.astype(jnp.float32), grads_acc), None

    zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, accum_dtype), params)
    (loss_sum, grads_sum), _ = jax.lax.scan(
        step, (jnp.zeros((), jnp.float32), zeros), (micro_batches, weights))
    return loss_sum, grads_sum
