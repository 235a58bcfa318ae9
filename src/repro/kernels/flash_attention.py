"""Flash-attention Pallas TPU kernels — forward AND backward (trainable).

Forward grid: (B*H, n_q_blocks, n_kv_blocks) — the kv dimension is
innermost, so the running (m, l, acc) flash statistics live in VMEM
scratch across kv steps (TPU grids execute sequentially over the last
dimension). The forward also emits the per-row log-sum-exp
``lse = m + log(l)`` so the backward can recompute the probabilities
without materializing the (S, S) matrix.

Backward (recompute-based, DESIGN.md §11): with the standard
``D_i = rowsum(dO_i * O_i)`` trick,

    P_ij = exp(s_ij - lse_i)          s_ij = scale * q_i . k_j  (masked)
    dV_j = sum_i P_ij dO_i
    dP_ij = dO_i . v_j
    dS_ij = P_ij (dP_ij - D_i)
    dQ_i = scale * sum_j dS_ij k_j
    dK_j = scale * sum_i dS_ij q_i

split into two kernels so each output has a sequential accumulation
dimension innermost: the dq kernel iterates kv blocks innermost (dq tile
accumulates in VMEM), the dk/dv kernel iterates q blocks innermost
(dk/dv tiles accumulate in VMEM). D is a cheap fused jnp rowsum outside
the kernels.

TPU layout: a block's last two dims must be (8, 128) multiples or the
full array dims, so per-row statistics never travel as ``(1, BQ)`` slices
of a ``(B*H, S)`` array.  ``lse`` and D are stored lane-dense as
``(B*H, 1, S)`` (one ``(1, BQ)`` row per q block) and converted to/from
the ``(BQ, 1)`` column the tile math uses (``tiles.py``); the running
``m``/``l`` scratch is ``(BQ, 1)``. Everything is wired through
``jax.custom_vjp`` in ``flash_attention`` below, so ``jax.grad`` works natively on TPU and in
``interpret=True`` mode on CPU.

Block shapes are MXU-aligned (multiples of 128 on the matmul dims); the
VMEM working set per backward step is q/k/v/do blocks + the f32
accumulator + the (BQ, BK) score tile:
  (2*BQ*D + 2*BK*D) * 2B + BQ*D*4B + BQ*BK*4B ~= 0.6 MiB at
BQ=BK=D=128, comfortably inside the ~16 MiB v5e VMEM budget even with
double buffering. Sequences that are not a multiple of the block size
are zero-padded by ``flash_attention`` and masked inside the kernels via
the static ``seq_len`` bound (padding happens OUTSIDE the custom_vjp, so
cotangents of the pad rows are exactly zero).

Validated in ``interpret=True`` mode against ``ref.attention_ref`` (and
its ``jax.grad``) over a shape/dtype sweep (tests/test_kernels.py,
tests/test_kernel_grads.py); on CPU the ops wrapper always interprets
(this container has no TPU).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .tiles import col_to_row, row_to_col

NEG_INF = -1e30


def _score_mask(qi, ki, block_q, block_k, *, causal, window, seq_len,
                q_offset=0):
    """(BQ, BK) validity mask for the score tile at (q block qi, kv block
    ki). ``seq_len`` masks zero-padded kv columns (qpos >= seq_len rows
    are garbage by design — their outputs/cotangents are sliced/zeroed
    outside the kernel). ``q_offset`` is the absolute position of query
    row 0."""
    qpos = q_offset + qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    kpos = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    mask = kpos < seq_len
    if causal:
        mask &= qpos >= kpos
    if window > 0:
        mask &= kpos > qpos - window
    return mask


# ---------------------------------------------------------------------- #
# forward
# ---------------------------------------------------------------------- #
def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref,
                      acc_ref, *, block_q: int, block_k: int, causal: bool,
                      window: int, seq_len: int, n_kv_blocks: int,
                      q_offset: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)               # (BQ, D)
    k = k_ref[0].astype(jnp.float32)               # (BK, D)
    v = v_ref[0].astype(jnp.float32)
    d = q.shape[-1]
    s = jnp.dot(q * (d ** -0.5), k.T,
                preferred_element_type=jnp.float32)  # (BQ, BK)
    mask = _score_mask(qi, ki, block_q, block_k, causal=causal,
                       window=window, seq_len=seq_len, q_offset=q_offset)
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]                            # (BQ, 1)
    l_prev = l_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_new = l_prev * corr + p.sum(axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jnp.dot(
        p, v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new
    l_ref[...] = l_new

    @pl.when(ki == n_kv_blocks - 1)
    def _finalize():
        l = l_ref[...]
        o_ref[0] = (acc_ref[...] / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
        # lse = m + log(l); fully-masked rows get 0 so the backward's
        # exp(NEG_INF - lse) recompute stays exactly 0 (no inf * 0).
        # Stored lane-dense as a (1, BQ) row.
        lse_ref[0] = col_to_row(jnp.where(l > 0, m_ref[...] + jnp.log(
            jnp.maximum(l, 1e-30)), 0.0))


def flash_attention_fwd(q, k, v, *, causal=True, window=0,
                        block_q=128, block_k=128, interpret=False,
                        seq_len=None, return_lse=False, q_offset=0):
    """q: (B, H, Sq, D), k/v: (B, H, Sk, D) -> (B, H, Sq, D) [, lse
    (B, H, 1, Sq) f32].

    Raw divisible-shape primitive; ``flash_attention`` below adds padding
    and the custom VJP. ``seq_len`` masks kv positions >= seq_len (used
    when Sk includes zero padding); ``q_offset`` is the absolute position
    of query row 0 (suffix prefill: Sq < Sk)."""
    b, h, sq, d = q.shape
    s = k.shape[2]
    assert k.shape == v.shape == (b, h, s, d)
    block_q = min(block_q, sq)
    block_k = min(block_k, s)
    assert sq % block_q == 0 and s % block_k == 0, (sq, s, block_q, block_k)
    if seq_len is None:
        seq_len = s
    nq, nk = sq // block_q, s // block_k
    bh = b * h
    qr = q.reshape(bh, sq, d)
    kr = k.reshape(bh, s, d)
    vr = v.reshape(bh, s, d)

    kernel = functools.partial(
        _flash_fwd_kernel, block_q=block_q, block_k=block_k,
        causal=causal, window=window, seq_len=seq_len, n_kv_blocks=nk,
        q_offset=q_offset)
    out, lse = pl.pallas_call(
        kernel,
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, qi, ki: (bh, ki, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, qi, ki: (bh, ki, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, 1, block_q), lambda bh, qi, ki: (bh, 0, qi)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
            jax.ShapeDtypeStruct((bh, 1, sq), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),    # running max m
            pltpu.VMEM((block_q, 1), jnp.float32),    # running sum l
            pltpu.VMEM((block_q, d), jnp.float32),    # accumulator
        ],
        interpret=interpret,
    )(qr, kr, vr)
    out = out.reshape(b, h, sq, d)
    if return_lse:
        return out, lse.reshape(b, h, 1, sq)
    return out


# ---------------------------------------------------------------------- #
# backward
# ---------------------------------------------------------------------- #
def _recompute_p_ds(q, k, v, do, lse_row, delta_row, qi, ki, block_q,
                    block_k, *, causal, window, seq_len):
    """Shared bwd tile math: P = exp(s - lse) and dS = P * (dP - D)."""
    d = q.shape[-1]
    s = jnp.dot(q * (d ** -0.5), k.T,
                preferred_element_type=jnp.float32)    # (BQ, BK)
    mask = _score_mask(qi, ki, block_q, block_k, causal=causal,
                       window=window, seq_len=seq_len)
    s = jnp.where(mask, s, NEG_INF)
    p = jnp.exp(s - row_to_col(lse_row))              # masked entries -> 0
    dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
    ds = p * (dp - row_to_col(delta_row))
    return p, ds


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         dq_ref, acc_ref, *, block_q: int, block_k: int,
                         causal: bool, window: int, seq_len: int,
                         n_kv_blocks: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    do = do_ref[0].astype(jnp.float32)
    _, ds = _recompute_p_ds(q, k, v, do, lse_ref[0], delta_ref[0],
                            qi, ki, block_q, block_k, causal=causal,
                            window=window, seq_len=seq_len)
    d = q.shape[-1]
    acc_ref[...] += jnp.dot(ds, k, preferred_element_type=jnp.float32) \
        * (d ** -0.5)

    @pl.when(ki == n_kv_blocks - 1)
    def _finalize():
        dq_ref[0] = acc_ref[...].astype(dq_ref.dtype)


def _flash_bwd_dkdv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                           dk_ref, dv_ref, dk_acc, dv_acc, *, block_q: int,
                           block_k: int, causal: bool, window: int,
                           seq_len: int, n_q_blocks: int):
    ki = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    q = q_ref[0].astype(jnp.float32)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    do = do_ref[0].astype(jnp.float32)
    p, ds = _recompute_p_ds(q, k, v, do, lse_ref[0], delta_ref[0],
                            qi, ki, block_q, block_k, causal=causal,
                            window=window, seq_len=seq_len)
    d = q.shape[-1]
    dv_acc[...] += jnp.dot(p.T, do, preferred_element_type=jnp.float32)
    dk_acc[...] += jnp.dot(ds.T, q, preferred_element_type=jnp.float32) \
        * (d ** -0.5)

    @pl.when(qi == n_q_blocks - 1)
    def _finalize():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def flash_attention_bwd(q, k, v, o, lse, do, *, causal=True, window=0,
                        block_q=128, block_k=128, interpret=False,
                        seq_len=None):
    """Raw backward: (B, H, S, D) residuals + cotangent -> dq, dk, dv."""
    b, h, s, d = q.shape
    block_q = min(block_q, s)
    block_k = min(block_k, s)
    assert s % block_q == 0 and s % block_k == 0, (s, block_q, block_k)
    if seq_len is None:
        seq_len = s
    nq, nk = s // block_q, s // block_k
    bh = b * h
    qr, kr, vr, dor = (t.reshape(bh, s, d) for t in (q, k, v, do))
    lser = lse.reshape(bh, 1, s)
    # D_i = rowsum(dO_i * O_i): cheap fused elementwise outside the grid.
    delta = jnp.sum(dor.astype(jnp.float32)
                    * o.reshape(bh, s, d).astype(jnp.float32),
                    axis=-1).reshape(bh, 1, s)

    common = dict(block_q=block_q, block_k=block_k, causal=causal,
                  window=window, seq_len=seq_len)
    q_spec = pl.BlockSpec((1, block_q, d), lambda bh, qi, ki: (bh, qi, 0))
    k_spec = pl.BlockSpec((1, block_k, d), lambda bh, qi, ki: (bh, ki, 0))
    row_spec = pl.BlockSpec((1, 1, block_q), lambda bh, qi, ki: (bh, 0, qi))

    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, n_kv_blocks=nk, **common),
        grid=(bh, nq, nk),
        in_specs=[q_spec, k_spec, k_spec, q_spec, row_spec, row_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((bh, s, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
    )(qr, kr, vr, dor, lser, delta)

    # kv blocks outermost, q blocks innermost: dk/dv accumulate in VMEM.
    tq_spec = pl.BlockSpec((1, block_q, d), lambda bh, ki, qi: (bh, qi, 0))
    tk_spec = pl.BlockSpec((1, block_k, d), lambda bh, ki, qi: (bh, ki, 0))
    trow_spec = pl.BlockSpec((1, 1, block_q), lambda bh, ki, qi: (bh, 0, qi))
    dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_dkdv_kernel, n_q_blocks=nq, **common),
        grid=(bh, nk, nq),
        in_specs=[tq_spec, tk_spec, tk_spec, tq_spec, trow_spec, trow_spec],
        out_specs=[tk_spec, tk_spec],
        out_shape=[jax.ShapeDtypeStruct((bh, s, d), k.dtype),
                   jax.ShapeDtypeStruct((bh, s, d), v.dtype)],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        interpret=interpret,
    )(qr, kr, vr, dor, lser, delta)
    shape = (b, h, s, d)
    return dq.reshape(shape), dk.reshape(shape), dv.reshape(shape)


# ---------------------------------------------------------------------- #
# custom_vjp core (divisible shapes) + padded public entry
# ---------------------------------------------------------------------- #
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash_core(q, k, v, seq_len, causal, window, block_q, block_k,
                interpret):
    return flash_attention_fwd(q, k, v, causal=causal, window=window,
                               block_q=block_q, block_k=block_k,
                               interpret=interpret, seq_len=seq_len)


def _flash_core_fwd(q, k, v, seq_len, causal, window, block_q, block_k,
                    interpret):
    o, lse = flash_attention_fwd(q, k, v, causal=causal, window=window,
                                 block_q=block_q, block_k=block_k,
                                 interpret=interpret, seq_len=seq_len,
                                 return_lse=True)
    return o, (q, k, v, o, lse)


def _flash_core_bwd(seq_len, causal, window, block_q, block_k, interpret,
                    res, do):
    q, k, v, o, lse = res
    return flash_attention_bwd(q, k, v, o, lse, do, causal=causal,
                               window=window, block_q=block_q,
                               block_k=block_k, interpret=interpret,
                               seq_len=seq_len)


_flash_core.defvjp(_flash_core_fwd, _flash_core_bwd)


def flash_attention_hbm_bytes(b, h, s, d, *, block_q=128, block_k=128,
                              dtype_bytes=4):
    """Exact HBM (DMA) traffic of the flash kernels, from the same
    grid/BlockSpec geometry the ``pallas_call``s use: a block is fetched
    when its index-map output changes (Pallas elides refetches of an
    unchanged block across inner grid steps), score tiles and running
    statistics never leave VMEM. This is the TPU traffic measure used by
    ``benchmarks/kernels_bench.py``; interpret-mode HLO materializes the
    VMEM tiles into buffers and overcounts by orders of magnitude.
    Row statistics (lse, delta) are counted at ``dtype_bytes`` for
    simplicity (they are f32 regardless of the input dtype)."""
    bq, bk = min(block_q, s), min(block_k, s)
    assert s % bq == 0 and s % bk == 0, (s, bq, bk)
    nq, nk = s // bq, s // bk
    bh = b * h
    fwd = bh * (nq * bq * d                 # q: once per q block
                + nq * nk * 2 * bk * d      # k, v: refetched per (qi, ki)
                + nq * (bq * d + bq))       # o + lse writes
    delta = bh * (2 * s * d + s)            # rowsum(dO * O) read/write
    dq = bh * (nq * (2 * bq * d + 2 * bq)   # q, do, lse, delta: per qi
               + nq * nk * 2 * bk * d       # k, v: per (qi, ki)
               + nq * bq * d)               # dq write
    dkdv = bh * (nk * 2 * bk * d            # k, v: once per kv block
                 + nk * nq * (2 * bq * d + 2 * bq)  # q/do/lse/delta per (ki, qi)
                 + nk * 2 * bk * d)         # dk, dv writes
    out = {"fwd": float(fwd * dtype_bytes),
           "bwd": float((delta + dq + dkdv) * dtype_bytes)}
    out["fwd_bwd"] = out["fwd"] + out["bwd"]
    return out


def _padded_len(s, block_q, block_k):
    """(padded length, block_q, block_k) ``flash_attention`` uses for a
    sequence of ``s`` rows."""
    bq, bk = min(block_q, s), min(block_k, s)
    if s % bq or s % bk:
        lcm = math.lcm(block_q, block_k)
        s = lcm * pl.cdiv(s, lcm)
        bq, bk = min(block_q, s), min(block_k, s)
    return s, bq, bk


def flash_attention_extend(q, k, v, *, q_offset, block_q=128, block_k=128,
                           interpret=False):
    """Causal attention of suffix queries over a whole prefix + suffix
    (suffix prefill; forward only).  q: (B, H, Sq, D) at absolute
    positions ``[q_offset, q_offset + Sq)``; k/v: (B, H, q_offset + Sq, D).

    The keys are padded and blocked exactly as ``flash_attention`` pads
    them for the full sequence, so each query row reduces over the same
    kv blocks in the same order as the full prefill's row; only the
    suffix's q blocks are computed."""
    b, h, sq, d = q.shape
    sk = k.shape[2]
    assert sk == q_offset + sq, (sk, q_offset, sq)
    skp, bq, bk = _padded_len(sk, block_q, block_k)
    if skp > sk:
        pad = ((0, 0), (0, 0), (0, skp - sk), (0, 0))
        k, v = jnp.pad(k, pad), jnp.pad(v, pad)
    bq = min(bq, 8 * pl.cdiv(sq, 8))         # a q block: 8-row multiple
    sqp = bq * pl.cdiv(sq, bq)
    if sqp > sq:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, sqp - sq), (0, 0)))
    out = flash_attention_fwd(q, k, v, causal=True, block_q=bq,
                              block_k=bk, interpret=interpret, seq_len=sk,
                              q_offset=q_offset)
    return out[:, :, :sq]


def flash_attention(q, k, v, *, causal=True, window=0, block_q=128,
                    block_k=128, interpret=False):
    """Trainable flash attention, (B, H, S, D) layout, any S.

    Sequences that are not a multiple of the block size are zero-padded
    to the next block multiple and masked via the kernels' ``seq_len``
    bound; padding/slicing sit OUTSIDE the custom_vjp, so JAX's linear
    pad/slice rules zero the pad-row cotangents automatically."""
    s = q.shape[2]
    sp, bq, bk = _padded_len(s, block_q, block_k)
    if sp > s:
        pad = ((0, 0), (0, 0), (0, sp - s), (0, 0))
        q, k, v = (jnp.pad(t, pad) for t in (q, k, v))
    out = _flash_core(q, k, v, s, causal, window, bq, bk, interpret)
    return out[:, :, :s]
