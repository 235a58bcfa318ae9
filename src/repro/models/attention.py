"""GQA attention: full, chunked (flash-style, jnp — the lowering-friendly
path used for long sequences; the Pallas TPU kernel in ``repro.kernels``
implements the same algorithm and is TRAINABLE — its ``custom_vjp``
backward is a recompute-based Pallas kernel, so ``use_kernel=True`` works
under ``jax.grad`` at any sequence length), and single-token decode
against a KV cache.

Sliding-window masking supports the sub-quadratic dense variants used by
``long_500k`` (DESIGN.md §5).
"""
from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.sharding.hooks import constrain

from .layers import apply_rope, linear, linear_init

NEG_INF = -1e30

# sequences at or above this length take the chunked (flash-style) path
CHUNKED_THRESHOLD = 8192
Q_CHUNK = 1024
KV_CHUNK = 1024


# ---------------------------------------------------------------------- #
# params
# ---------------------------------------------------------------------- #
def attention_init(key, d_model, n_heads, n_kv_heads, head_dim,
                   qkv_bias=False, dtype=jnp.float32):
    kq, kk, kv, ko = jax.random.split(key, 4)
    return {
        "wq": linear_init(kq, d_model, n_heads * head_dim, bias=qkv_bias,
                          dtype=dtype),
        "wk": linear_init(kk, d_model, n_kv_heads * head_dim, bias=qkv_bias,
                          dtype=dtype),
        "wv": linear_init(kv, d_model, n_kv_heads * head_dim, bias=qkv_bias,
                          dtype=dtype),
        "wo": linear_init(ko, n_heads * head_dim, d_model, dtype=dtype),
    }


def _repeat_kv(k: jnp.ndarray, groups: int) -> jnp.ndarray:
    """(B, S, Hkv, D) -> (B, S, Hkv*groups, D)."""
    if groups == 1:
        return k
    b, s, h, d = k.shape
    return jnp.repeat(k, groups, axis=2)


# ---------------------------------------------------------------------- #
# full (quadratic) attention — short sequences
# ---------------------------------------------------------------------- #
def full_attention(q, k, v, *, causal=True, window=0,
                   q_offset: int = 0) -> jnp.ndarray:
    """q: (B, Sq, H, D), k/v: (B, Sk, H, D). ``q_offset`` is the absolute
    position of q[0] (decode: Sk-1).

    Mixed precision (§Perf iteration A1): for bf16 inputs the QK/PV
    matmuls run in bf16 with f32 accumulation (preferred_element_type)
    and the probabilities are cast to bf16 before PV — no f32 copies of
    q/k/v/probs ever hit HBM. f32 inputs (tests) keep the exact path."""
    b, sq, h, d = q.shape
    sk = k.shape[1]
    scale = d ** -0.5
    low = q.dtype == jnp.bfloat16
    if low:
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                            preferred_element_type=jnp.float32) * scale
    else:
        scores = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                            k.astype(jnp.float32)) * scale
    qpos = jnp.arange(sq)[:, None] + q_offset
    kpos = jnp.arange(sk)[None, :]
    mask = jnp.ones((sq, sk), dtype=bool)
    if causal:
        mask &= qpos >= kpos
    if window > 0:
        mask &= kpos > qpos - window
    scores = jnp.where(mask[None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    if low:
        out = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(jnp.bfloat16), v,
                         preferred_element_type=jnp.float32)
    else:
        out = jnp.einsum("bhqk,bkhd->bqhd", probs, v.astype(jnp.float32))
    return out.astype(q.dtype)


# ---------------------------------------------------------------------- #
# chunked (flash-style) attention — long sequences, O(S * chunk) memory
# ---------------------------------------------------------------------- #
def chunked_attention(q, k, v, *, causal=True, window=0,
                      q_chunk=Q_CHUNK, kv_chunk=KV_CHUNK) -> jnp.ndarray:
    """Two-level scan with running (max, sum, acc) — the flash-attention
    recurrence in pure jnp. Same math as ``full_attention``."""
    b, sq, h, d = q.shape
    sk = k.shape[1]
    assert sq % q_chunk == 0 and sk % kv_chunk == 0, (sq, sk)
    nq, nk = sq // q_chunk, sk // kv_chunk
    scale = d ** -0.5

    qc = q.reshape(b, nq, q_chunk, h, d).transpose(1, 0, 3, 2, 4)  # (nq,B,H,qc,D)
    kc = k.reshape(b, nk, kv_chunk, h, d).transpose(1, 0, 3, 2, 4)
    vc = v.reshape(b, nk, kv_chunk, h, d).transpose(1, 0, 3, 2, 4)

    kpos = (jnp.arange(nk)[:, None] * kv_chunk + jnp.arange(kv_chunk))

    low = q.dtype == jnp.bfloat16   # §Perf A1: bf16 matmuls, f32 accum

    def q_step(_, qi_and_idx):
        qi, iq = qi_and_idx
        qpos = iq * q_chunk + jnp.arange(q_chunk)
        qif = qi if low else qi.astype(jnp.float32) * scale

        def kv_step(carry, kv_and_idx):
            m, l, acc = carry
            ki, vi, kp = kv_and_idx
            if low:
                s = jnp.einsum("bhqd,bhkd->bhqk", qif, ki,
                               preferred_element_type=jnp.float32) * scale
            else:
                s = jnp.einsum("bhqd,bhkd->bhqk", qif,
                               ki.astype(jnp.float32))
            mask = jnp.ones((q_chunk, kv_chunk), dtype=bool)
            if causal:
                mask &= qpos[:, None] >= kp[None, :]
            if window > 0:
                mask &= kp[None, :] > qpos[:, None] - window
            s = jnp.where(mask[None, None], s, NEG_INF)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(axis=-1)
            if low:
                pv = jnp.einsum("bhqk,bhkd->bhqd",
                                p.astype(jnp.bfloat16), vi,
                                preferred_element_type=jnp.float32)
            else:
                pv = jnp.einsum("bhqk,bhkd->bhqd", p,
                                vi.astype(jnp.float32))
            acc_new = acc * corr[..., None] + pv
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((b, h, q_chunk), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, h, q_chunk), jnp.float32)
        a0 = jnp.zeros((b, h, q_chunk, d), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0), (kc, vc, kpos))
        out = acc / jnp.maximum(l[..., None], 1e-30)
        return None, out.astype(q.dtype)

    _, outs = jax.lax.scan(q_step, None, (qc, jnp.arange(nq)))
    # outs: (nq, B, H, qc, D) -> (B, S, H, D)
    return outs.transpose(1, 0, 3, 2, 4).reshape(b, sq, h, d)


# ---------------------------------------------------------------------- #
# module-level forward
# ---------------------------------------------------------------------- #
def attention(p, x, cos, sin, *, n_heads, n_kv_heads, head_dim,
              causal=True, window=0, use_kernel: bool = False,
              q_scale: float = 1.0) -> jnp.ndarray:
    """Training / prefill attention over the whole sequence.

    cos/sin: RoPE tables (may be None for NoPE/xLSTM-style blocks).
    ``q_scale`` multiplies the queries before the head_dim**-0.5 of the
    softmax, so a model's own softmax scale ``a`` is ``q_scale =
    a * sqrt(head_dim)`` (1.0 leaves the program as it was)."""
    b, s, _ = x.shape
    q = linear(p["wq"], x).reshape(b, s, n_heads, head_dim)
    k = linear(p["wk"], x).reshape(b, s, n_kv_heads, head_dim)
    v = linear(p["wv"], x).reshape(b, s, n_kv_heads, head_dim)
    if cos is not None:
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
    if q_scale != 1.0:
        q = q * jnp.asarray(q_scale, q.dtype)
    q = constrain(q, "act_heads")
    groups = n_heads // n_kv_heads
    k = _repeat_kv(k, groups)
    v = _repeat_kv(v, groups)
    if use_kernel:
        # Pallas flash kernel (fwd + custom_vjp bwd); pads internally, so
        # every configs/ sequence length is eligible.
        from repro.kernels import flash_attention_ops
        out = flash_attention_ops.flash_attention(
            q, k, v, causal=causal, window=window)
    elif s >= CHUNKED_THRESHOLD:
        out = chunked_attention(q, k, v, causal=causal, window=window)
    else:
        out = full_attention(q, k, v, causal=causal, window=window)
    out = constrain(out, "act_heads")
    return linear(p["wo"], out.reshape(b, s, n_heads * head_dim))


def attention_prefill(p, x, cos, sin, cache, *, n_heads, n_kv_heads,
                      head_dim, window=0, use_kernel: bool = False,
                      q_scale: float = 1.0) -> Tuple[jnp.ndarray, dict]:
    """Single-shot prefill: attend over the whole prompt (same math as
    ``attention``) AND write the per-position K/V rows into a FRESH decode
    cache.  x: (B, S, D); cache: {"k","v"} (B, S_cache, Hkv, D) — linear
    layout (slot t == position t) when ``window == 0``, ring-buffered
    (slot t == t % S_cache) when ``window > 0``.  The cache must start at
    index 0; callers continue decoding at absolute position S."""
    b, s, _ = x.shape
    q = linear(p["wq"], x).reshape(b, s, n_heads, head_dim)
    k = linear(p["wk"], x).reshape(b, s, n_kv_heads, head_dim)
    v = linear(p["wv"], x).reshape(b, s, n_kv_heads, head_dim)
    if cos is not None:
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
    if q_scale != 1.0:
        q = q * jnp.asarray(q_scale, q.dtype)
    q = constrain(q, "act_heads")
    s_cache = cache["k"].shape[1]
    if window > 0:
        # ring buffer: only the last min(S, S_cache) positions survive;
        # their slots (t % S_cache) are distinct, so one scatter suffices.
        keep = min(s, s_cache)
        slots = (jnp.arange(s - keep, s) % s_cache).astype(jnp.int32)
        ck = cache["k"].at[:, slots].set(
            k[:, s - keep:].astype(cache["k"].dtype))
        cv = cache["v"].at[:, slots].set(
            v[:, s - keep:].astype(cache["v"].dtype))
    else:
        assert s <= s_cache, (s, s_cache)
        ck = cache["k"].at[:, :s].set(k.astype(cache["k"].dtype))
        cv = cache["v"].at[:, :s].set(v.astype(cache["v"].dtype))
    ck = constrain(ck, "kv_cache")
    cv = constrain(cv, "kv_cache")

    groups = n_heads // n_kv_heads
    kk = _repeat_kv(k, groups)
    vv = _repeat_kv(v, groups)
    if use_kernel:
        from repro.kernels import flash_attention_ops
        out = flash_attention_ops.flash_attention(
            q, kk, vv, causal=True, window=window)
    elif s >= CHUNKED_THRESHOLD:
        out = chunked_attention(q, kk, vv, causal=True, window=window)
    else:
        out = full_attention(q, kk, vv, causal=True, window=window)
    out = constrain(out, "act_heads")
    return (linear(p["wo"], out.reshape(b, s, n_heads * head_dim)),
            {"k": ck, "v": cv})


def attention_prefill_extend(p, x, cos, sin, cache, *, start, n_heads,
                             n_kv_heads, head_dim, use_kernel: bool = False
                             ) -> Tuple[jnp.ndarray, dict]:
    """Suffix prefill (DESIGN.md §18): rows ``[0, start)`` of the linear
    cache are already populated (a shared-prefix gather); write rows
    ``[start, start+s)`` and attend the suffix queries over rows
    ``[0, start+s)``.  x: (B, S_suffix, D) — already the residual stream
    of the suffix positions only.

    Because attention rows are independent (each output row reduces over
    the same key extent), the outputs and cache rows are BITWISE
    identical to the corresponding rows of ``attention_prefill`` over
    the full sequence — provided the suffix has >= 2 rows (a single-row
    matmul dispatches to a different XLA accumulation path) and the
    cache dtype equals the compute dtype (prefix rows are read back
    through the cache here, but attended uncast in full prefill).
    Linear layout only.  ``use_kernel=True`` runs the flash kernel over
    the suffix's q blocks only, with the keys padded and blocked as the
    full prefill's kernel call blocks them, so its rows match that call's
    rows too."""
    b, s, _ = x.shape
    q = linear(p["wq"], x).reshape(b, s, n_heads, head_dim)
    k = linear(p["wk"], x).reshape(b, s, n_kv_heads, head_dim)
    v = linear(p["wv"], x).reshape(b, s, n_kv_heads, head_dim)
    if cos is not None:
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
    q = constrain(q, "act_heads")
    s_cache = cache["k"].shape[1]
    assert start + s <= s_cache, (start, s, s_cache)
    ck = cache["k"].at[:, start:start + s].set(k.astype(cache["k"].dtype))
    cv = cache["v"].at[:, start:start + s].set(v.astype(cache["v"].dtype))
    ck = constrain(ck, "kv_cache")
    cv = constrain(cv, "kv_cache")

    groups = n_heads // n_kv_heads
    kk = _repeat_kv(ck[:, :start + s], groups)
    vv = _repeat_kv(cv[:, :start + s], groups)
    if use_kernel:
        from repro.kernels import flash_attention_ops
        out = flash_attention_ops.flash_attention_extend(
            q, kk, vv, q_offset=start)
    else:
        out = full_attention(q, kk, vv, causal=True, q_offset=start)
    out = constrain(out, "act_heads")
    return (linear(p["wo"], out.reshape(b, s, n_heads * head_dim)),
            {"k": ck, "v": cv})


def attention_decode(p, x, cos, sin, cache, index, *, n_heads, n_kv_heads,
                     head_dim, window=0, use_kernel: bool = False,
                     pages=None, layer=0, q_scale: float = 1.0
                     ) -> Tuple[jnp.ndarray, dict]:
    """One-token decode. x: (B, 1, D); cache: {"k","v"} (B, S_cache, Hkv, D)
    ring-buffered when ``window > 0`` (S_cache == window), else linear
    (S_cache == max_len). ``index`` is the absolute decode position (B,)
    or scalar.  ``use_kernel=True`` takes the Pallas flash-decode kernel
    for the linear layout (the ring buffer's valid set is not a prefix,
    so it keeps the jnp path).

    ``pages`` switches the cache to the PAGED layout (DESIGN.md §15):
    cache k/v are shared pools ``(N_pages, page_size, Hkv*D)`` and
    ``pages`` is the per-example block table ``(B, P)`` mapping logical
    page ``index // page_size`` to a pool page (-1 = unassigned).  Linear
    layout only (``window == 0``).  The pools may also be stacked over
    layers, ``(L, N_pages, page_size, Hkv*D)``: ``layer`` (an i32 scalar,
    traced inside the decode step's layer scan) picks the layer this call
    writes and reads, and the whole stack is returned."""
    b, one, _ = x.shape
    assert one == 1
    q = linear(p["wq"], x).reshape(b, 1, n_heads, head_dim)
    k = linear(p["wk"], x).reshape(b, 1, n_kv_heads, head_dim)
    v = linear(p["wv"], x).reshape(b, 1, n_kv_heads, head_dim)
    if cos is not None:
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
    if q_scale != 1.0:
        q = q * jnp.asarray(q_scale, q.dtype)
    if pages is not None:
        assert window == 0, "paged KV requires the linear layout"
        return _attention_decode_paged(
            p, q, k, v, cache, index, pages, layer, n_heads=n_heads,
            n_kv_heads=n_kv_heads, head_dim=head_dim,
            use_kernel=use_kernel, out_dtype=x.dtype)
    s_cache = cache["k"].shape[1]
    index = jnp.asarray(index)
    slot = index % s_cache if window > 0 else index  # ring buffer vs linear
    with jax.named_scope("kv_write"):
        if index.ndim == 0:
            ck = jax.lax.dynamic_update_slice_in_dim(
                cache["k"], k.astype(cache["k"].dtype), slot, axis=1)
            cv = jax.lax.dynamic_update_slice_in_dim(
                cache["v"], v.astype(cache["v"].dtype), slot, axis=1)
        else:
            ck = _scatter_rows(cache["k"], k, slot)
            cv = _scatter_rows(cache["v"], v, slot)
        ck = constrain(ck, "kv_cache")
        cv = constrain(cv, "kv_cache")

    groups = n_heads // n_kv_heads
    idx = index if index.ndim > 0 else index[None]
    if use_kernel and window == 0:
        # GQA resolved inside the kernel: the cache is read unrepeated
        from repro.kernels import flash_attention_ops
        lengths = jnp.broadcast_to(idx + 1, (b,))
        out = flash_attention_ops.flash_decode(q, ck, cv, lengths)
    else:
        kk = _repeat_kv(ck, groups)
        vv = _repeat_kv(cv, groups)
        scale = head_dim ** -0.5
        scores = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                            kk.astype(jnp.float32)) * scale
        kpos = jnp.arange(s_cache)[None, :]             # (1, S)
        if window > 0:
            # ring buffer: reconstruct the absolute position held by each
            # slot; valid iff written and within the window.
            abs_pos = _ring_abs_pos(idx, s_cache)       # (B, S)
            valid = (abs_pos <= idx[:, None]) \
                & (abs_pos > idx[:, None] - window) & (abs_pos >= 0)
        else:
            valid = kpos <= idx[:, None]
        scores = jnp.where(valid[:, None, None, :], scores, NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("bhqk,bkhd->bqhd", probs, vv.astype(jnp.float32))
    out = out.astype(x.dtype).reshape(b, 1, n_heads * head_dim)
    return linear(p["wo"], out), {"k": ck, "v": cv}


def _attention_decode_paged(p, q, k, v, cache, index, pages, layer, *,
                            n_heads, n_kv_heads, head_dim, use_kernel,
                            out_dtype) -> Tuple[jnp.ndarray, dict]:
    """Paged one-token decode: write this step's K/V row into the pool
    page that owns position ``index``, then attend over the pages listed
    in the block table.

    The pools are stacked over layers, ``(L, N_pages, page_size, ...)``,
    and ``layer`` selects the one this call works on (a 3-D pool is a
    stack of one, read at layer 0).  The row write is a scatter into the
    stack at ``(layer, page, off)`` and the kernel reads ``pool[layer]``
    through its index maps, so the decode step can carry the stacked
    pool through its layer scan without ever slicing out or restacking
    a layer's slab — both of which copy the slab.

    The jnp path gathers the table back into a ``(B, P*page_size, ...)``
    view — the same shape, row content, and masked-softmax reduction as
    the dense linear cache (``P * page_size == max_len``), so tokens are
    BITWISE identical to the dense engine.  The kernel path walks the
    table inside ``flash_decode_paged`` without materializing the gather.

    Write-safety: an example whose table has no page for ``index`` (an
    inactive engine slot, or index beyond the table) maps to pool page
    ``N_pages`` — out of bounds — and the ``mode="drop"`` scatter makes
    it a no-op.  A plain ``.at[-1]`` would *wrap* and corrupt the last
    pool page."""
    b = q.shape[0]
    ck, cv = cache["k"], cache["v"]
    one_layer = ck.ndim == 3
    if one_layer:
        ck, cv, layer = ck[None], cv[None], 0
    n_pg, page_size = ck.shape[1:3]
    p_tab = pages.shape[1]
    index = jnp.asarray(index)
    idx = index if index.ndim > 0 else jnp.broadcast_to(index[None], (b,))
    pidx = idx // page_size
    off = idx % page_size
    ar = jnp.arange(b)
    pid = jnp.where(pidx < p_tab,
                    pages[ar, jnp.minimum(pidx, p_tab - 1)], -1)
    safe = jnp.where(pid >= 0, pid, n_pg)          # unassigned -> OOB drop
    with jax.named_scope("kv_write"):
        ck = ck.at[layer, safe, off].set(
            k[:, 0].reshape(b, -1).astype(ck.dtype), mode="drop")
        cv = cv.at[layer, safe, off].set(
            v[:, 0].reshape(b, -1).astype(cv.dtype), mode="drop")
    # no kv_cache constrain here: the pool layout (N_pages, ...) does not
    # match the (B, S, H, D) sharding rule, and serving runs single-host

    groups = n_heads // n_kv_heads
    if use_kernel:
        from repro.kernels import flash_attention_ops
        lengths = idx + 1
        out = flash_attention_ops.flash_decode_paged(
            q, ck, cv, pages, lengths, layer)
    else:
        # gather the table into the dense linear view; unassigned pages
        # read pool page 0 but every such position is masked below.
        gpid = jnp.maximum(pages, 0)               # (B, P)
        gk = ck[layer, gpid].reshape(b, p_tab * page_size, n_kv_heads,
                                     head_dim)
        gv = cv[layer, gpid].reshape(b, p_tab * page_size, n_kv_heads,
                                     head_dim)
        kk = _repeat_kv(gk, groups)
        vv = _repeat_kv(gv, groups)
        scale = head_dim ** -0.5
        scores = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                            kk.astype(jnp.float32)) * scale
        kpos = jnp.arange(p_tab * page_size)[None, :]
        valid = kpos <= idx[:, None]
        scores = jnp.where(valid[:, None, None, :], scores, NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("bhqk,bkhd->bqhd", probs, vv.astype(jnp.float32))
    out = out.astype(out_dtype).reshape(b, 1, n_heads * head_dim)
    if one_layer:
        ck, cv = ck[0], cv[0]
    return linear(p["wo"], out), {"k": ck, "v": cv}


def _ring_abs_pos(idx: jnp.ndarray, s_cache: int) -> jnp.ndarray:
    """Absolute position stored in each ring slot after writing at
    ``idx % s_cache``. idx: (B,) -> (B, S)."""
    slots = jnp.arange(s_cache)[None, :]
    cur = idx[:, None] % s_cache
    # slot j holds abs position idx - ((cur - j) mod s_cache)
    back = (cur - slots) % s_cache
    return idx[:, None] - back


def _scatter_rows(cache: jnp.ndarray, new: jnp.ndarray,
                  slots: jnp.ndarray) -> jnp.ndarray:
    """Per-example dynamic row write: cache (B,S,H,D), new (B,1,H,D),
    slots (B,)."""
    b = cache.shape[0]
    return cache.at[jnp.arange(b), slots].set(
        new[:, 0].astype(cache.dtype))
