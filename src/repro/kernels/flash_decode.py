"""Flash-decode Pallas TPU kernel — single-query attention over a paged
KV cache (the serving hot loop); the dense cache is the same kernel seen
through an identity block table.

Decode attention is one query row per (batch, head) against the cached
keys/values, of which only a dynamic prefix ``lengths[b]`` is valid (the
linear, non-ring layout: row ``t`` holds absolute position ``t``).  The
pool flattens the kv heads into the lane dim, ``(N_pages, page_size,
H_kv*D)``: with D = 64 the ``(H_kv, D)`` minor dims would be tile-padded
to (48, 128) in HBM, 2.7x the pool's bytes at minicpm-2b widths.  A grid
step DMAs one page — all kv heads — of one batch element; the block's
last two dims are the full array dims, as the TPU's tiling rule asks.
Grid ``(B, P)`` with the pages innermost, so the running flash
statistics (max ``m``, sum ``l``, weighted accumulator ``acc``) live in
VMEM scratch across kv steps.

Scores are one MXU product of the page against block-diagonal queries
(row j holds its query in kv head j's lanes), heads on sublanes and
positions on lanes; the per-head outputs are the diagonal blocks of the
accumulator.  GQA: the queries are grouped ``(B, groups, H_kv, ·)``
(head ``h`` reads kv head ``h // groups``) and a static loop over the
groups reuses each page for all of its query heads — the kv heads are
never repeated.

Masking: the page table and the lengths ride in SMEM (scalar prefetch);
positions ``>= length`` are masked.  Pages wholly past the length are
neither fetched again nor computed; probabilities of masked rows inside
a partial page are zeroed with an explicit ``where`` (``exp(NEG_INF -
NEG_INF) == 1`` otherwise), so they contribute exactly nothing to
``l``/``acc``.

There is no backward: nothing differentiates through the serving loop.
On CPU the wrapper in ``ops.py`` runs the kernel with ``interpret=True``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _group_queries(q, h_kv):
    """(B, 1, H, D) model layout -> (B, G, H_kv, D): head h = j*G + g."""
    b, one, h, d = q.shape
    assert one == 1, q.shape
    assert h % h_kv == 0, (h, h_kv)
    groups = h // h_kv
    return q.reshape(b, h_kv, groups, d).transpose(0, 2, 1, 3), groups


def _ungroup(out, h):
    b, _, _, d = out.shape
    return out.transpose(0, 2, 1, 3).reshape(b, 1, h, d)


def flash_decode(q, k, v, lengths, *, block_k=128, interpret=False):
    """q: (B, 1, H, D); k/v: (B, S, H_kv, D) linear KV cache (model
    layout; ``H % H_kv == 0``); lengths: (B,) i32 valid cache rows.
    Returns (B, 1, H, D).

    The cache is the paged kernel's pool seen through an identity block
    table: block ``ki`` of example ``b`` is page ``b * n_blocks + ki``."""
    b, s, h_kv, d = k.shape
    assert v.shape == k.shape and q.shape[3] == d, (q.shape, k.shape)
    # No silent clamping: the requested (possibly autotuned) block size is
    # honored exactly; the cache is zero-padded up to a block multiple.
    assert block_k > 0, block_k
    if s % block_k:
        sp = block_k * pl.cdiv(s, block_k)
        pad = ((0, 0), (0, sp - s), (0, 0), (0, 0))
        k, v = jnp.pad(k, pad), jnp.pad(v, pad)
        s = sp
    nk = s // block_k
    pool = (b * nk, block_k, h_kv * d)
    pages = jnp.arange(b * nk, dtype=jnp.int32).reshape(b, nk)
    return flash_decode_paged(q, k.reshape(pool), v.reshape(pool), pages,
                              lengths, interpret=interpret)


# ---------------------------------------------------------------------- #
# paged variant — KV lives in a shared page pool, addressed per slot via
# a block table (DESIGN.md §15)
# ---------------------------------------------------------------------- #
def _paged_body(len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
                *, page_size: int, n_pages_tab: int, groups: int,
                head_dim: int):
    """One (batch b, logical page ki) step on the flattened pool.
    q_ref: (1, G, H_kv, H_kv*D) block-diagonal queries (row j carries
    query head j*G+g in kv head j's lanes, zeros elsewhere); k/v_ref:
    (1, PS, H_kv*D); scratch m/l: (G, H_kv, 1), acc: (G, H_kv, H_kv*D).
    Scores come out of one MXU product per group, heads on sublanes and
    positions on lanes; the per-head outputs sit on the diagonal blocks
    of ``acc`` and are gathered at the end."""
    bi = pl.program_id(0)
    ki = pl.program_id(1)
    length = len_ref[bi]

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(ki * page_size < length)
    def _step():
        k = k_ref[0]                                       # (PS, H_kv*D)
        v = v_ref[0].astype(jnp.float32)
        pos = ki * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (1, page_size), 1)
        valid = pos < length                               # (1, PS)
        for g in range(groups):
            s = jax.lax.dot_general(
                q_ref[0, g], k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * head_dim ** -0.5
            s = jnp.where(valid, s, NEG_INF)               # (H_kv, PS)
            m_prev = m_ref[g]                              # (H_kv, 1)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
            corr = jnp.exp(m_prev - m_new)
            l_ref[g] = l_ref[g] * corr + jnp.sum(p, axis=-1, keepdims=True)
            acc_ref[g] = acc_ref[g] * corr + jnp.dot(
                p, v, preferred_element_type=jnp.float32)
            m_ref[g] = m_new

    @pl.when(ki == n_pages_tab - 1)
    def _finalize():
        h_kv, width = acc_ref.shape[1], acc_ref.shape[2]
        lo = jax.lax.broadcasted_iota(jnp.int32, (h_kv, width), 0) * head_dim
        lane = jax.lax.broadcasted_iota(jnp.int32, (h_kv, width), 1)
        own = (lane >= lo) & (lane < lo + head_dim)        # diagonal blocks
        for g in range(groups):
            out = acc_ref[g] / jnp.maximum(l_ref[g], 1e-30)
            o_ref[0, g:g + 1] = jnp.sum(jnp.where(own, out, 0.0), axis=0,
                                        keepdims=True).astype(o_ref.dtype)


def flash_decode_paged(q, k_pool, v_pool, pages, lengths, *,
                       interpret=False):
    """Paged flash decode. q: (B, 1, H, D); k_pool/v_pool:
    (N_pages, page_size, H_kv*D) shared page pools (kv heads flattened
    into the lane dim); pages: (B, P) i32 per-slot page table (-1 =
    unassigned); lengths: (B,) valid rows.  Returns (B, 1, H, D).

    Grid (B, P): one logical page per kv step.  The page table and the
    lengths are scalar-prefetch SMEM operands — the table drives the k/v
    index maps (which physical pool page to DMA next), so the indirection
    costs nothing per step.  Pages past a slot's length map to its last
    valid page, so their blocks are not fetched again, and the step is
    skipped; unassigned entries (-1) are clamped to pool page 0."""
    n_pg, page_size, width = k_pool.shape
    b, one, h, d = q.shape
    assert one == 1 and v_pool.shape == k_pool.shape and width % d == 0, (
        q.shape, k_pool.shape, v_pool.shape)
    h_kv = width // d
    qg, groups = _group_queries(q, h_kv)                    # (B, G, H_kv, D)
    qblk = jnp.einsum("bgjd,jk->bgjkd", qg, jnp.eye(h_kv, dtype=q.dtype)
                      ).reshape(b, groups, h_kv, width).astype(k_pool.dtype)
    p_tab = pages.shape[1]
    assert pages.shape == (b, p_tab), (pages.shape, q.shape)
    pages_i = jnp.maximum(pages.astype(jnp.int32), 0)  # -1 -> page 0, masked

    def kernel(pages_ref, len_ref, *refs):
        del pages_ref
        _paged_body(len_ref, *refs, page_size=page_size, n_pages_tab=p_tab,
                    groups=groups, head_dim=d)

    def kv_map(bi, ki, pr, lr):
        last = jnp.maximum(lr[bi] - 1, 0) // page_size
        return (pr[bi, jnp.minimum(ki, last)], 0, 0)

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, p_tab),
            in_specs=[
                pl.BlockSpec((1, groups, h_kv, width),
                             lambda bi, ki, pr, lr: (bi, 0, 0, 0)),
                pl.BlockSpec((1, page_size, width), kv_map),
                pl.BlockSpec((1, page_size, width), kv_map),
            ],
            out_specs=pl.BlockSpec((1, groups, width),
                                   lambda bi, ki, pr, lr: (bi, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((groups, h_kv, 1), jnp.float32),      # max m
                pltpu.VMEM((groups, h_kv, 1), jnp.float32),      # sum l
                pltpu.VMEM((groups, h_kv, width), jnp.float32),  # acc
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, groups, width), q.dtype),
        interpret=interpret,
    )(pages_i, lengths.astype(jnp.int32), qblk, k_pool, v_pool)
    return _ungroup(out.reshape(b, groups, h_kv, d), h)
