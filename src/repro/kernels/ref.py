"""Pure-jnp oracles for the Pallas kernels.

These are deliberately the *naive* formulations (full softmax attention;
strictly sequential SSD recurrence) so kernel tests compare against an
implementation whose correctness is obvious.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def attention_ref(q, k, v, *, causal=True, window=0):
    """q/k/v: (B, H, S, D). Full-softmax reference."""
    b, h, s, d = q.shape
    scores = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * d ** -0.5
    qpos = jnp.arange(s)[:, None]
    kpos = jnp.arange(s)[None, :]
    mask = jnp.ones((s, s), bool)
    if causal:
        mask &= qpos >= kpos
    if window > 0:
        mask &= kpos > qpos - window
    scores = jnp.where(mask[None, None], scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)


def flash_decode_ref(q, k, v, lengths):
    """Single-query decode attention, XLA path — *model layout*.

    q: (B, 1, H, D); k/v: (B, S_cache, H, D) with kv heads already
    repeated; lengths: (B,) valid-prefix rows.  This mirrors the masked
    softmax in ``repro.models.attention.attention_decode`` operation for
    operation, so when the autotuner routes ``ops.flash_decode`` here the
    serving path stays BITWISE identical to the non-kernel engine (the
    token-identity tests rely on that).
    """
    b, one, h, d = q.shape
    s_cache = k.shape[1]
    scale = d ** -0.5
    scores = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    kpos = jnp.arange(s_cache)[None, :]
    valid = kpos < lengths[:, None]
    scores = jnp.where(valid[:, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v.astype(jnp.float32))
    return out.astype(q.dtype)


def flash_decode_paged_ref(q, k_pool, v_pool, pages, lengths):
    """Paged decode attention, XLA path — *model layout*.

    q: (B, 1, H, D); k_pool/v_pool: (N_pages, page_size, H_kv*D) shared
    pools; pages: (B, P) block tables (-1 = unassigned); lengths: (B,)
    valid rows.  Gathers each slot's pages into a linear cache (-1 rows
    are gathered from page 0 but masked by ``lengths`` — the engine only
    maps pages covering valid rows), repeats KV heads for GQA, and
    defers to ``flash_decode_ref`` — so when the autotuner routes
    ``ops.flash_decode_paged`` here the paged serving path stays BITWISE
    identical to the engine's jnp path."""
    b, p_tab = pages.shape
    h, d = q.shape[2], q.shape[3]
    n_pages, ps, width = k_pool.shape
    h_kv = width // d
    safe = jnp.maximum(pages, 0)
    k = k_pool[safe].reshape(b, p_tab * ps, h_kv, d)
    v = v_pool[safe].reshape(b, p_tab * ps, h_kv, d)
    groups = h // h_kv
    if groups > 1:
        k = jnp.repeat(k, groups, axis=2)
        v = jnp.repeat(v, groups, axis=2)
    return flash_decode_ref(q, k, v, lengths)


def ssd_ref(x, dt, A, Bm, Cm):
    """Sequential Mamba2/SSD recurrence (the obviously-correct oracle).

    x: (B,S,H,P), dt: (B,S,H), A: (H,) (<0), Bm/Cm: (B,S,N).
    h_t = exp(A*dt_t) h_{t-1} + dt_t * x_t (outer) B_t ;  y_t = C_t . h_t
    """
    b, s, h, p = x.shape
    n = Bm.shape[-1]

    def step(state, inp):
        xt, dtt, bt, ct = inp                      # (b,h,p),(b,h),(b,n),(b,n)
        da = jnp.exp(A[None, :] * dtt)             # (b,h)
        state = da[..., None, None] * state + jnp.einsum(
            "bh,bn,bhp->bhpn", dtt, bt, xt)
        y = jnp.einsum("bhpn,bn->bhp", state, ct)
        return state, y

    state0 = jnp.zeros((b, h, p, n), jnp.float32)
    xs = (x.transpose(1, 0, 2, 3).astype(jnp.float32),
          dt.transpose(1, 0, 2).astype(jnp.float32),
          Bm.transpose(1, 0, 2).astype(jnp.float32),
          Cm.transpose(1, 0, 2).astype(jnp.float32))
    _, ys = jax.lax.scan(step, state0, xs)
    return ys.transpose(1, 0, 2, 3).astype(x.dtype)   # (B,S,H,P)
