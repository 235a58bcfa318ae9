"""Jit-able wrappers choosing kernel vs interpret mode by backend.

On TPU the Pallas kernels compile natively; on CPU they execute in
``interpret=True`` mode — the kernel body runs as traced jnp, matching
the TPU algorithm for validation.  Any other backend is an error: a
kernel never silently interprets on an accelerator.

Inside a :func:`recording` block every call records the implementation
it resolved to (``"pallas"``, ``"interpret"`` or ``"ref"``), so a device
run can prove that no reference path or interpreter stood in for a
kernel.

Both wrappers are TRAINABLE: the underlying entries carry a
``jax.custom_vjp`` whose backward passes are themselves Pallas kernels
(recompute-based flash backward, reverse-chunk SSD backward — DESIGN.md
§11), so ``jax.grad`` through ``use_kernels=True`` works on both
backends. Sequence lengths that are not a multiple of the block/chunk
size are zero-padded and masked inside the kernels, so every ``configs/``
shape can take the kernel path.

Autotuned routing (DESIGN.md §15): when a call site leaves the block /
chunk arguments at ``None`` (the default — all production call sites do),
the wrapper consults the autotune table for this shape class.  A tuned
entry supplies block sizes; an entry recording ``backend: "ref"`` (the
sweep found XLA faster at this shape) routes to the reference path —
*bitwise identical* to the corresponding model jnp path, so token/loss
identity is preserved through the reroute.  With no artifact present the
hard-coded defaults apply unchanged.  Explicit block arguments always
win (tests pin them).
"""
from __future__ import annotations

import contextlib
from typing import Dict, Iterator, List, Set

import jax
import jax.numpy as jnp

from . import autotune
from . import flash_attention as _flash
from . import flash_decode as _decode
from . import mamba2_scan as _ssd
from . import ref as _ref


_recorders: List[Dict[str, Set[str]]] = []


@contextlib.contextmanager
def recording() -> Iterator[Dict[str, Set[str]]]:
    """Collect ``{kernel: {implementations}}`` for every kernel call
    traced inside the block (a call is recorded when it is traced, so a
    program compiled before the block is not seen)."""
    routes: Dict[str, Set[str]] = {}
    _recorders.append(routes)
    try:
        yield routes
    finally:
        _recorders.remove(routes)


def _route(kind: str, use_ref: bool = False) -> bool:
    """Record how ``kind`` runs; returns the ``interpret`` flag."""
    if use_ref:
        impl = "ref"
    else:
        backend = jax.default_backend()
        if backend not in ("tpu", "cpu"):
            raise RuntimeError(
                f"Pallas kernels compile for tpu and interpret on cpu; the "
                f"{backend!r} backend has neither path")
        impl = "interpret" if backend == "cpu" else "pallas"
    for routes in _recorders:
        routes.setdefault(kind, set()).add(impl)
    return impl == "interpret"


def _resolve(kind: str, s: int, d: int, dtype, overrides: dict):
    """Merge explicit call-site arguments over the tuned entry (or the
    hard-coded defaults).  Returns (cfg, use_ref): ``use_ref`` only when
    the tuned winner is the reference AND the caller pinned nothing."""
    explicit = {k: v for k, v in overrides.items() if v is not None}
    if len(explicit) == len(overrides):
        return explicit, False
    entry = autotune.lookup(kind, s, d, dtype)
    if entry is not None and entry.get("backend") == "ref":
        if not explicit:
            return dict(autotune.DEFAULTS[kind]), True
        entry = None                       # caller pinned a block: honor it
    base = dict(autotune.DEFAULTS[kind])
    if entry is not None:
        base.update({k: entry[k] for k in base if k in entry})
    base.update(explicit)
    return base, False


def flash_attention(q, k, v, *, causal=True, window=0,
                    block_q=None, block_k=None):
    """q/k/v: (B, S, H, D) (model layout) -> (B, S, H, D). Differentiable
    in q, k, v; any sequence length."""
    cfg, use_ref = _resolve(
        "flash_attention", q.shape[1], q.shape[3], q.dtype,
        {"block_q": block_q, "block_k": block_k})
    interpret = _route("flash_attention", use_ref)
    if use_ref:
        # lazy: models.attention imports this module inside functions only
        from repro.models.attention import full_attention
        return full_attention(q, k, v, causal=causal, window=window)
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    out = _flash.flash_attention(qt, kt, vt, causal=causal, window=window,
                                 block_q=cfg["block_q"],
                                 block_k=cfg["block_k"],
                                 interpret=interpret)
    return out.transpose(0, 2, 1, 3)


def flash_attention_extend(q, k, v, *, q_offset, block_q=None,
                           block_k=None):
    """Suffix prefill attention. q: (B, Sq, H, D) at absolute positions
    ``[q_offset, q_offset + Sq)``, k/v: (B, q_offset + Sq, H, D) (model
    layout) -> (B, Sq, H, D); causal, forward only.  Routed like
    ``flash_attention`` over the whole key extent, so its rows match that
    call's rows."""
    cfg, use_ref = _resolve(
        "flash_attention", k.shape[1], q.shape[3], q.dtype,
        {"block_q": block_q, "block_k": block_k})
    interpret = _route("flash_attention", use_ref)
    if use_ref:
        from repro.models.attention import full_attention
        return full_attention(q, k, v, causal=True, q_offset=q_offset)
    out = _flash.flash_attention_extend(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), q_offset=q_offset,
        block_q=cfg["block_q"], block_k=cfg["block_k"], interpret=interpret)
    return out.transpose(0, 2, 1, 3)


def flash_decode(q, k, v, lengths, *, block_k=None):
    """Single-query decode attention against a linear KV cache.
    q: (B, 1, H, D) (model layout), k/v: (B, S_cache, H_kv, D) with
    ``H % H_kv == 0`` (GQA is resolved inside the kernel; kv heads need
    not be repeated), lengths: (B,) valid-prefix rows.  Not
    differentiable (serving only)."""
    cfg, use_ref = _resolve("flash_decode", k.shape[1], q.shape[3],
                            q.dtype, {"block_k": block_k})
    interpret = _route("flash_decode", use_ref)
    if use_ref:
        groups = q.shape[2] // k.shape[2]
        if groups > 1:
            k = jnp.repeat(k, groups, axis=2)
            v = jnp.repeat(v, groups, axis=2)
        return _ref.flash_decode_ref(q, k, v, lengths)
    return _decode.flash_decode(q, k, v, lengths, block_k=cfg["block_k"],
                                interpret=interpret)


def flash_decode_paged(q, k_pool, v_pool, pages, lengths, layer=0):
    """Paged decode attention. q: (B, 1, H, D) (model layout);
    k_pool/v_pool: (N_pages, page_size, H_kv*D) shared pools, or
    (L, N_pages, page_size, H_kv*D) stacked over layers and read at
    ``layer``; pages: (B, P) per-slot page table (-1 = unassigned);
    lengths: (B,) valid rows.  GQA is resolved inside the kernel — kv
    heads are never repeated.  Not differentiable (serving only).

    The kernel has no block knobs, so tuned routing is consulted
    directly (``_resolve`` would early-return on the empty override
    set): an entry recording ``backend: "ref"`` for this
    (page_size, head_dim, dtype) class routes to the gather oracle,
    bitwise identical to the engine's jnp paged path."""
    entry = autotune.lookup("flash_decode_paged", k_pool.shape[-2],
                            q.shape[3], q.dtype)
    use_ref = entry is not None and entry.get("backend") == "ref"
    interpret = _route("flash_decode_paged", use_ref)
    if use_ref:
        return _ref.flash_decode_paged_ref(q, k_pool, v_pool, pages,
                                           lengths, layer)
    return _decode.flash_decode_paged(q, k_pool, v_pool, pages, lengths,
                                      layer, interpret=interpret)


def ssd(x, dt, A, Bm, Cm, *, chunk=None):
    """Mamba2 SSD: x (B,S,H,P), dt (B,S,H), A (H,), Bm/Cm (B,S,N).
    Differentiable in all five operands; any sequence length."""
    cfg, use_ref = _resolve("ssd", x.shape[1], x.shape[3], x.dtype,
                            {"chunk": chunk})
    interpret = _route("ssd", use_ref)
    if use_ref:
        from repro.models.ssm import ssd_chunked
        return ssd_chunked(x, dt, A, Bm, Cm)
    return _ssd.ssd(x, dt, A, Bm, Cm, chunk=cfg["chunk"],
                    interpret=interpret)
