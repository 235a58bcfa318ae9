"""Loss + the jit-able train step used by smoke tests, the quickstart
example, the co-schedule testbed and the dry-run."""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import forward

from .grad_accum import accumulate_gradients
from .optimizer import OptState, adamw_update


@dataclass(frozen=True)
class TrainConfig:
    accum_steps: int = 1            # s — gradient-accumulation sub-steps
    lr: float = 3e-4
    weight_decay: float = 0.1
    aux_loss_weight: float = 0.01   # MoE load-balance
    remat: bool = True
    use_kernels: bool = False
    accum_dtype: str = "float32"
    schedule: Optional[Callable] = None   # overrides lr when set
    # §Perf A2: re-shard gradients to the parameter sharding before the
    # optimizer (forces reduce-scatter instead of a full-size all-reduce)
    # and optionally reduce them in bf16.
    reshard_grads: bool = False
    grad_reduce_dtype: Optional[str] = None


def loss_fn(cfg: ArchConfig, params, batch: Dict[str, jnp.ndarray], *,
            aux_loss_weight: float = 0.01, remat: bool = True,
            use_kernels: bool = False) -> Tuple[jnp.ndarray, Dict]:
    """Mean next-token CE. An optional ``sample_mask`` (B,) entry marks
    padded rows of a ragged final micro-batch (grad_accum.py): masked
    samples contribute nothing to the CE term and the mean runs over
    valid samples. The MoE aux loss is a batch statistic (DESIGN.md §8)
    and is NOT masked — padded rows do pass through the router, so MoE
    accumulation equivalence holds only with aux_loss_weight=0."""
    logits, aux = forward(cfg, params, batch, remat=remat,
                          use_kernels=use_kernels)
    labels = batch["labels"]
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    ll = jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    mask = batch.get("sample_mask")
    if mask is None:
        ce = -jnp.mean(ll)
    else:
        ce = -jnp.sum(ll * mask[:, None]) / (
            jnp.maximum(jnp.sum(mask), 1.0) * ll.shape[1])
    loss = ce + aux_loss_weight * aux
    return loss, {"ce": ce, "aux": aux}


def make_train_step(cfg: ArchConfig, tc: TrainConfig = TrainConfig()):
    """Returns train_step(params, opt_state, batch) -> (params, opt_state,
    metrics). Gradient accumulation is a ``lax.scan`` over micro-batches
    (the paper's mechanism; memory scales with batch/accum_steps)."""

    def scoped_loss(params, micro_batch):
        # the backward pass inherits the scope, under transpose(jvp(...))
        with jax.named_scope("forward"):
            return loss_fn(cfg, params, micro_batch,
                           aux_loss_weight=tc.aux_loss_weight,
                           remat=tc.remat, use_kernels=tc.use_kernels)

    def lg(params, micro_batch):
        (loss_value, _), grads = jax.value_and_grad(
            scoped_loss, has_aux=True)(params, micro_batch)
        return loss_value, grads

    def train_step(params, opt_state: OptState, batch):
        loss, grads = accumulate_gradients(
            lg, params, batch, tc.accum_steps,
            accum_dtype=jnp.dtype(tc.accum_dtype))
        if tc.grad_reduce_dtype is not None:
            gdt = jnp.dtype(tc.grad_reduce_dtype)
            grads = jax.tree.map(lambda g: g.astype(gdt), grads)
        if tc.reshard_grads:
            from repro.sharding.hooks import constrain_params_tree
            grads = constrain_params_tree(grads)
        lr = tc.schedule if tc.schedule is not None else tc.lr
        with jax.named_scope("optimizer"):
            params, opt_state = adamw_update(
                grads, opt_state, params, lr=lr,
                weight_decay=tc.weight_decay)
            gnorm = jnp.sqrt(sum(
                jnp.sum(jnp.square(g.astype(jnp.float32)))
                for g in jax.tree.leaves(grads)))
        return params, opt_state, {"loss": loss, "grad_norm": gnorm}

    return train_step


def make_jit_train_step(cfg: ArchConfig, tc: TrainConfig = TrainConfig(), *,
                        donate: bool = True):
    """``make_train_step`` jitted with params/opt-state DONATED: the
    gradient-accumulation buffers and the AdamW moment update reuse the
    input HBM in place instead of allocating a second copy — halving the
    peak optimizer-state footprint on TPU. Callers must re-bind
    (params, opt_state) from the outputs every step (the donated inputs
    are invalidated); `repro.core.coschedule` and `repro.launch.train`
    thread state that way."""
    step = make_train_step(cfg, tc)
    return jax.jit(step, donate_argnums=(0, 1) if donate else ())
