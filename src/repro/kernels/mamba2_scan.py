"""Mamba2 SSD chunk-scan Pallas TPU kernels — forward AND backward.

Forward grid: (B, H, n_chunks) — chunks innermost, so the inter-chunk
state (P, N) persists in VMEM scratch across chunk steps (TPU grid order
is sequential over the last dimension). Per chunk the kernel computes
the intra-chunk attention-like term (an (L, L) masked matmul on the
MXU), the inter-chunk contribution from the carried state, and the state
update — exactly the structure of ``repro.models.ssm.ssd_chunked`` (the
jnp reference path used by the model on CPU). When taking gradients the
forward additionally spills each chunk's INPUT state to HBM
((B, H, nc, P, N), the only residual beyond the inputs themselves).

Backward (DESIGN.md §11): the same grid iterated in REVERSE chunk order
(via the index maps — the grid itself stays forward-ordered) carrying
``dstate`` (P, N) in VMEM scratch. Per chunk it recomputes the cheap
forward intermediates (cumsum, decay tile, scores) from the saved input
state and emits dx, ddt, d(dA), and per-head dB/dC partials (summed over
heads outside, since Bm/Cm are shared across heads and revisiting one
output block non-consecutively would break TPU accumulation). The
``dA = A * dt`` chain rule runs outside the kernel in jnp, keeping the
kernel oblivious to the A/dt factorization. Everything is wired through
``jax.custom_vjp`` in ``ssd`` below.

TPU layout: dt and dA travel lane-dense as (B, H, 1, S), one (1, L) row
per chunk (a block of (1, L) over (B, H, S) would violate the (8, 128)
tiling rule); the kernels turn them into (L, 1) columns with the exact
relayouts of ``tiles.py`` and take the cumulative sums as masked row sums.

VMEM working set per backward step at L=256, P=64, N=64:
  x/dt/dA/B/C/state/dy blocks + (L, L) decay+score f32 tiles + the
  (P, N) dstate scratch ~= 1.3 MiB. All matmul dims are multiples of
64/128 -> MXU-aligned. Non-multiple sequence lengths are zero-padded by
``ssd`` (dt = 0 on the pad makes the extra positions exact no-ops:
dA = 0 so the decay is 1 and the state passes through unchanged).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .tiles import col_to_row, row_to_col

NEG_INF = -1e30


def _cumsum_col(row, chunk):
    """Inclusive prefix sum of a (1, L) row, as an (L, 1) column."""
    ii = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    return jnp.sum(jnp.where(jj <= ii, row, 0.0), axis=1, keepdims=True)


def _chunk_terms(dt_ref, dA_ref, chunk):
    """Per-chunk scalars as (L, 1) columns: dt, cum = cumsum(dA), and the
    masked decay tile exp(cum_i - cum_j) (i >= j, else 0); total = cum[-1]
    as (1, 1)."""
    dt = row_to_col(dt_ref[0, 0].astype(jnp.float32))    # (L, 1)
    cum = _cumsum_col(dA_ref[0, 0].astype(jnp.float32), chunk)
    total = cum[chunk - 1:chunk]                          # (1, 1)
    ii = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    diff = cum - col_to_row(cum)                          # (L, L)
    decay = jnp.exp(jnp.where(ii >= jj, diff, NEG_INF))
    return dt, cum, total, decay


def _rectangle_sums(t, ii, jj):
    """(L, 1) column r_k = sum_{i >= k > j} t_ij for an (L, L) tile: an
    exclusive prefix sum along lanes (log-step shifted adds) followed by
    a masked column sum."""
    chunk = t.shape[1]
    pre = jnp.where(jj >= 1, pltpu.roll(t, 1, 1), 0.0)   # pre_ik = t_i,k-1
    shift = 1
    while shift < chunk:                      # pre_ik = sum_{j < k} t_ij
        pre = pre + jnp.where(jj >= shift, pltpu.roll(pre, shift, 1), 0.0)
        shift *= 2
    return row_to_col(jnp.sum(jnp.where(ii >= jj, pre, 0.0), axis=0,
                              keepdims=True))


def _ssd_fwd_only_kernel(x_ref, dt_ref, dA_ref, b_ref, c_ref, y_ref,
                         state_ref, *, chunk: int):
    _ssd_kernel(x_ref, dt_ref, dA_ref, b_ref, c_ref, y_ref, None,
                state_ref, chunk=chunk)


def _ssd_kernel(x_ref, dt_ref, dA_ref, b_ref, c_ref, y_ref, st_ref,
                state_ref, *, chunk: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    if st_ref is not None:
        st_ref[0, 0, 0] = state_ref[...]

    x = x_ref[0, 0].astype(jnp.float32)          # (L, P)
    Bm = b_ref[0].astype(jnp.float32)            # (L, N)
    Cm = c_ref[0].astype(jnp.float32)            # (L, N)
    dt, cum, total, decay = _chunk_terms(dt_ref, dA_ref, chunk)

    # intra-chunk: masked decay * (C B^T)
    scores = jnp.dot(Cm, Bm.T, preferred_element_type=jnp.float32)
    m = scores * decay                           # (L, L)
    xdt = x * dt                                 # (L, P)
    y_intra = jnp.dot(m, xdt, preferred_element_type=jnp.float32)
    # inter-chunk from carried state (P, N)
    state = state_ref[...]
    y_inter = jnp.dot(Cm, state.T,
                      preferred_element_type=jnp.float32) * jnp.exp(cum)
    # state update
    w = jnp.exp(total - cum) * dt                # (L, 1)
    s_local = jnp.dot((x * w).T, Bm,
                      preferred_element_type=jnp.float32)   # (P, N)
    state_ref[...] = jnp.exp(total) * state + s_local
    y_ref[0, 0] = (y_intra + y_inter).astype(y_ref.dtype)


def _ssd_bwd_kernel(x_ref, dt_ref, dA_ref, b_ref, c_ref, st_ref, dy_ref,
                    dx_ref, ddt_ref, ddA_ref, db_ref, dc_ref,
                    dstate_ref, *, chunk: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():                                  # d(final state) == 0
        dstate_ref[...] = jnp.zeros_like(dstate_ref)

    x = x_ref[0, 0].astype(jnp.float32)          # (L, P)
    Bm = b_ref[0].astype(jnp.float32)            # (L, N)
    Cm = c_ref[0].astype(jnp.float32)            # (L, N)
    s0 = st_ref[0, 0, 0]                         # (P, N) input state
    dy = dy_ref[0, 0].astype(jnp.float32)        # (L, P)
    ds1 = dstate_ref[...]                        # d(output state)

    # ---- recompute the cheap forward intermediates ------------------- #
    dt, cum, total, decay = _chunk_terms(dt_ref, dA_ref, chunk)
    scores = jnp.dot(Cm, Bm.T, preferred_element_type=jnp.float32)
    m = scores * decay
    xdt = x * dt
    expcum = jnp.exp(cum)                        # (L, 1)
    w = jnp.exp(total - cum) * dt                # (L, 1)
    et = jnp.exp(total)                          # (1, 1)

    def rsum(t):                                 # full sum as (1, 1)
        return jnp.sum(jnp.sum(t, axis=1, keepdims=True), axis=0,
                       keepdims=True)

    # ---- state update: state_out = exp(total) s0 + (x*w)^T B --------- #
    ds0 = et * ds1
    dtotal = et * rsum(ds1 * s0)
    g = jnp.dot(x, ds1, preferred_element_type=jnp.float32)      # (L, N)
    db = w * g
    dxw = jnp.dot(Bm, ds1.T, preferred_element_type=jnp.float32)  # (L, P)
    dx = w * dxw
    dw = jnp.sum(x * dxw, axis=-1, keepdims=True)                 # (L, 1)
    ddt = dw * jnp.exp(total - cum)
    dcum = -(dw * w)
    dtotal += rsum(dw * w)

    # ---- inter-chunk: y_inter = (C s0^T) * exp(cum) ------------------ #
    dyec = dy * expcum                                            # (L, P)
    y_inter = jnp.dot(Cm, s0.T, preferred_element_type=jnp.float32) * expcum
    dc = jnp.dot(dyec, s0, preferred_element_type=jnp.float32)    # (L, N)
    ds0 += jnp.dot(dyec.T, Cm, preferred_element_type=jnp.float32)
    dcum += jnp.sum(dy * y_inter, axis=-1, keepdims=True)

    # ---- intra-chunk: y_intra = (scores * decay) @ (x * dt) ---------- #
    dm = jnp.dot(dy, xdt.T, preferred_element_type=jnp.float32)   # (L, L)
    dxdt = jnp.dot(m.T, dy, preferred_element_type=jnp.float32)   # (L, P)
    dscores = dm * decay
    ddecay = dm * scores
    dc += jnp.dot(dscores, Bm, preferred_element_type=jnp.float32)
    db += jnp.dot(dscores.T, Cm, preferred_element_type=jnp.float32)
    ddiff = ddecay * decay          # masked entries: decay == 0 -> 0
    dx += dxdt * dt
    ddt += jnp.sum(dxdt * x, axis=-1, keepdims=True)

    # total = cum[-1]; cum = cumsum(dA) -> ddA = inclusive suffix sum of
    # dcum, plus the diff_ij = cum_i - cum_j term taken directly as
    # ddA_k += sum_{i >= k > j} ddiff_ij.  (Suffix-summing its row sums
    # minus its column sums gives the same value, but the (i, j >= k)
    # block cancels only after rounding, an error that grows with L.)
    last = jax.lax.broadcasted_iota(jnp.int32, (chunk, 1), 0)
    dcum += jnp.where(last == chunk - 1, dtotal, 0.0)
    ii = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    ddA = (jnp.sum(jnp.where(jj >= ii, col_to_row(dcum), 0.0), axis=1,
                   keepdims=True)
           + _rectangle_sums(ddiff, ii, jj))

    dx_ref[0, 0] = dx.astype(dx_ref.dtype)
    ddt_ref[0, 0] = col_to_row(ddt)
    ddA_ref[0, 0] = col_to_row(ddA)
    db_ref[0, 0] = db
    dc_ref[0, 0] = dc
    dstate_ref[...] = ds0


def _ssd_layouts(x, dt, A):
    """Kernel layouts: x (B,H,S,P); dt and dA = A*dt lane-dense as
    (B,H,1,S) so a chunk is one (1, L) row block."""
    xr = x.transpose(0, 2, 1, 3)                     # (B,H,S,P)
    dtr = dt.transpose(0, 2, 1)[:, :, None, :]       # (B,H,1,S)
    dAr = (A[None, :, None, None] * dtr).astype(jnp.float32)
    return xr, dtr, dAr


def ssd_fwd(x, dt, A, Bm, Cm, *, chunk=256, interpret=False,
            return_states=False):
    """x: (B,S,H,P), dt: (B,S,H), A: (H,), Bm/Cm: (B,S,N) -> y (B,S,H,P)
    [, per-chunk input states (B,H,nc,P,N)].

    Raw divisible-shape primitive (same contract as
    ``repro.models.ssm.ssd_chunked`` / ``repro.kernels.ref.ssd_ref``);
    ``ssd`` below adds padding and the custom VJP."""
    b, s, h, p = x.shape
    n = Bm.shape[-1]
    chunk = min(chunk, s)
    assert s % chunk == 0, (s, chunk)
    nc = s // chunk
    # layout: (B, H, S, *) with chunks innermost in the grid
    xr, dtr, dAr = _ssd_layouts(x, dt, A)

    # the per-chunk-states residual output exists only when the caller
    # will differentiate — plain forwards don't pay for the buffer
    out_specs = [pl.BlockSpec((1, 1, chunk, p),
                              lambda bi, hi, ci: (bi, hi, ci, 0))]
    out_shape = [jax.ShapeDtypeStruct((b, h, s, p), x.dtype)]
    if return_states:
        kernel = functools.partial(_ssd_kernel, chunk=chunk)
        out_specs.append(pl.BlockSpec(
            (1, 1, 1, p, n), lambda bi, hi, ci: (bi, hi, ci, 0, 0)))
        out_shape.append(
            jax.ShapeDtypeStruct((b, h, nc, p, n), jnp.float32))
    else:
        kernel = functools.partial(_ssd_fwd_only_kernel, chunk=chunk)

    out = pl.pallas_call(
        kernel,
        name="ssd_fwd",
        grid=(b, h, nc),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, p), lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((1, 1, 1, chunk), lambda bi, hi, ci: (bi, hi, 0, ci)),
            pl.BlockSpec((1, 1, 1, chunk), lambda bi, hi, ci: (bi, hi, 0, ci)),
            pl.BlockSpec((1, chunk, n), lambda bi, hi, ci: (bi, ci, 0)),
            pl.BlockSpec((1, chunk, n), lambda bi, hi, ci: (bi, ci, 0)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        interpret=interpret,
    )(xr, dtr, dAr, Bm, Cm)
    y = out[0].transpose(0, 2, 1, 3)                 # (B,S,H,P)
    if return_states:
        return y, out[1]
    return y


def ssd_bwd(x, dt, A, Bm, Cm, states, dy, *, chunk=256, interpret=False):
    """Raw backward: inputs + saved chunk states + cotangent dy
    (B,S,H,P) -> (dx, ddt, dA, dBm, dCm) matching the input shapes."""
    b, s, h, p = x.shape
    n = Bm.shape[-1]
    chunk = min(chunk, s)
    assert s % chunk == 0, (s, chunk)
    nc = s // chunk
    xr, dtr, dAr = _ssd_layouts(x, dt, A)
    dyr = dy.transpose(0, 2, 1, 3)                   # (B,H,S,P)

    # all chunk-indexed dims run REVERSED so dstate flows backward
    rev = nc - 1
    row_spec = pl.BlockSpec((1, 1, 1, chunk),
                            lambda bi, hi, ci: (bi, hi, 0, rev - ci))
    kernel = functools.partial(_ssd_bwd_kernel, chunk=chunk)
    dx_r, ddt_r, ddA_r, dbh, dch = pl.pallas_call(
        kernel,
        name="ssd_bwd",
        grid=(b, h, nc),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, p),
                         lambda bi, hi, ci: (bi, hi, rev - ci, 0)),
            row_spec, row_spec,
            pl.BlockSpec((1, chunk, n),
                         lambda bi, hi, ci: (bi, rev - ci, 0)),
            pl.BlockSpec((1, chunk, n),
                         lambda bi, hi, ci: (bi, rev - ci, 0)),
            pl.BlockSpec((1, 1, 1, p, n),
                         lambda bi, hi, ci: (bi, hi, rev - ci, 0, 0)),
            pl.BlockSpec((1, 1, chunk, p),
                         lambda bi, hi, ci: (bi, hi, rev - ci, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, p),
                         lambda bi, hi, ci: (bi, hi, rev - ci, 0)),
            row_spec, row_spec,
            pl.BlockSpec((1, 1, chunk, n),
                         lambda bi, hi, ci: (bi, hi, rev - ci, 0)),
            pl.BlockSpec((1, 1, chunk, n),
                         lambda bi, hi, ci: (bi, hi, rev - ci, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, s, p), x.dtype),
            jax.ShapeDtypeStruct((b, h, 1, s), jnp.float32),
            jax.ShapeDtypeStruct((b, h, 1, s), jnp.float32),
            jax.ShapeDtypeStruct((b, h, s, n), jnp.float32),
            jax.ShapeDtypeStruct((b, h, s, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        interpret=interpret,
    )(xr, dtr, dAr, Bm, Cm, states, dyr)

    # chain rule through dA = A * dt (done here, not in the kernel)
    ddt_r, ddA_r, dtr = ddt_r[:, :, 0], ddA_r[:, :, 0], dtr[:, :, 0]
    dx = dx_r.transpose(0, 2, 1, 3).astype(x.dtype)
    ddt = (ddt_r + ddA_r * A[None, :, None]).transpose(0, 2, 1)
    dA_out = jnp.sum(ddA_r * dtr.astype(jnp.float32), axis=(0, 2))
    dBm = dbh.sum(axis=1)                            # heads share Bm/Cm
    dCm = dch.sum(axis=1)
    return (dx, ddt.astype(dt.dtype), dA_out.astype(A.dtype),
            dBm.astype(Bm.dtype), dCm.astype(Cm.dtype))


# ---------------------------------------------------------------------- #
# custom_vjp core (divisible shapes) + padded public entry
# ---------------------------------------------------------------------- #
@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _ssd_core(x, dt, A, Bm, Cm, chunk, interpret):
    return ssd_fwd(x, dt, A, Bm, Cm, chunk=chunk, interpret=interpret)


def _ssd_core_fwd(x, dt, A, Bm, Cm, chunk, interpret):
    y, states = ssd_fwd(x, dt, A, Bm, Cm, chunk=chunk, interpret=interpret,
                        return_states=True)
    return y, (x, dt, A, Bm, Cm, states)


def _ssd_core_bwd(chunk, interpret, res, dy):
    x, dt, A, Bm, Cm, states = res
    return ssd_bwd(x, dt, A, Bm, Cm, states, dy, chunk=chunk,
                   interpret=interpret)


_ssd_core.defvjp(_ssd_core_fwd, _ssd_core_bwd)


def ssd(x, dt, A, Bm, Cm, *, chunk=256, interpret=False):
    """Trainable Mamba2 SSD, any sequence length.

    Non-multiple S is zero-padded to the next chunk multiple: dt = 0 on
    the pad makes dA = 0, so the padded positions leave the carried state
    untouched and contribute nothing to real outputs or gradients."""
    b, s, h, p = x.shape
    ck = min(chunk, s)
    if s % ck:
        sp = ck * pl.cdiv(s, ck)
        x = jnp.pad(x, ((0, 0), (0, sp - s), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, sp - s), (0, 0)))
        Bm = jnp.pad(Bm, ((0, 0), (0, sp - s), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, sp - s), (0, 0)))
    y = _ssd_core(x, dt, A, Bm, Cm, ck, interpret)
    return y[:, :s]
