"""Pallas kernel validation (interpret=True on CPU): shape/dtype sweeps
against the pure-jnp oracles in ``repro.kernels.ref``, plus consistency
of the model's jnp paths (chunked attention / ssd_chunked) with the same
oracles — kernel, model path and oracle must all agree."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import (flash_attention,
                                          flash_attention_extend,
                                          flash_attention_fwd)
from repro.kernels.mamba2_scan import ssd_fwd
from repro.kernels.ref import attention_ref, ssd_ref
from repro.models.attention import chunked_attention, full_attention
from repro.models.ssm import ssd_chunked


def _tol(dtype):
    return dict(atol=2e-2, rtol=2e-2) if dtype == jnp.bfloat16 \
        else dict(atol=2e-4, rtol=2e-4)


# ---------------------------------------------------------------------- #
# flash attention
# ---------------------------------------------------------------------- #
@pytest.mark.parametrize("b,h,s,d", [
    (1, 1, 128, 64), (2, 3, 256, 64), (1, 2, 512, 128), (2, 1, 384, 32),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_sweep(b, h, s, d, dtype, causal):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q, k, v = [jax.random.normal(kk, (b, h, s, d), dtype) for kk in ks]
    out = flash_attention_fwd(q, k, v, causal=causal, block_q=128,
                              block_k=128, interpret=True)
    ref = attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        **_tol(dtype))


@pytest.mark.parametrize("window", [32, 100, 256])
def test_flash_attention_window(window):
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q, k, v = [jax.random.normal(kk, (2, 2, 256, 64)) for kk in ks]
    out = flash_attention_fwd(q, k, v, causal=True, window=window,
                              interpret=True)
    ref = attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("bq,bk", [(64, 64), (128, 64), (64, 128)])
def test_flash_attention_block_shapes(bq, bk):
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q, k, v = [jax.random.normal(kk, (1, 2, 256, 64)) for kk in ks]
    out = flash_attention_fwd(q, k, v, block_q=bq, block_k=bk,
                              interpret=True)
    ref = attention_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("s,start", [(288, 256), (300, 40), (20, 14),
                                     (256, 128)])
def test_flash_attention_extend_rows_match_full(s, start):
    """Suffix queries at an offset reproduce the full call's rows
    bitwise, computing only the suffix's q blocks."""
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    q, k, v = [jax.random.normal(kk, (2, 3, s, 64)) for kk in ks]
    full = flash_attention(q, k, v, interpret=True)
    ext = flash_attention_extend(q[:, :, start:], k, v, q_offset=start,
                                 interpret=True)
    assert ext.shape == (2, 3, s - start, 64)
    np.testing.assert_array_equal(np.asarray(ext),
                                  np.asarray(full[:, :, start:]))


def test_model_chunked_attention_matches_ref():
    """The model's jnp flash path (used for long sequences under jit)."""
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    b, s, h, d = 2, 512, 2, 64
    q, k, v = [jax.random.normal(kk, (b, s, h, d)) for kk in ks]
    out = chunked_attention(q, k, v, causal=True, q_chunk=128, kv_chunk=128)
    ref = attention_ref(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                        v.transpose(0, 2, 1, 3), causal=True)
    np.testing.assert_allclose(np.asarray(out.transpose(0, 2, 1, 3)),
                               np.asarray(ref), atol=2e-4, rtol=2e-4)
    full = full_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(full),
                               atol=2e-4, rtol=2e-4)


# ---------------------------------------------------------------------- #
# mamba2 ssd
# ---------------------------------------------------------------------- #
@pytest.mark.parametrize("b,s,h,p,n,chunk", [
    (1, 128, 1, 16, 8, 64), (2, 512, 3, 32, 16, 128),
    (1, 256, 2, 64, 64, 256), (2, 384, 2, 32, 16, 128),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_kernel_sweep(b, s, h, p, n, chunk, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    x = jax.random.normal(ks[0], (b, s, h, p), dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h))).astype(dtype)
    A = -jnp.exp(jax.random.normal(ks[2], (h,)))
    Bm = jax.random.normal(ks[3], (b, s, n), dtype)
    Cm = jax.random.normal(ks[4], (b, s, n), dtype)
    out = ssd_fwd(x, dt, A, Bm, Cm, chunk=chunk, interpret=True)
    ref = ssd_ref(x, dt, A, Bm, Cm)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        atol=3e-2 if dtype == jnp.bfloat16 else 2e-3, rtol=2e-2)


def test_model_ssd_chunked_matches_ref():
    """The model's jnp chunked path vs the sequential oracle."""
    ks = jax.random.split(jax.random.PRNGKey(1), 5)
    b, s, h, p, n = 2, 256, 2, 16, 8
    x = jax.random.normal(ks[0], (b, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
    A = -jnp.exp(jax.random.normal(ks[2], (h,)))
    Bm = jax.random.normal(ks[3], (b, s, n))
    Cm = jax.random.normal(ks[4], (b, s, n))
    out = ssd_chunked(x, dt, A, Bm, Cm, chunk=64)
    ref = ssd_ref(x, dt, A, Bm, Cm)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-3, rtol=2e-3)


def test_ssd_kernel_state_continuity():
    """Chunk boundaries must be seamless: one long kernel call == the
    oracle on a sequence spanning many chunks."""
    ks = jax.random.split(jax.random.PRNGKey(2), 5)
    b, s, h, p, n = 1, 1024, 1, 8, 4
    x = jax.random.normal(ks[0], (b, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)) - 1.0)
    A = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.5)
    Bm = jax.random.normal(ks[3], (b, s, n))
    Cm = jax.random.normal(ks[4], (b, s, n))
    out = ssd_fwd(x, dt, A, Bm, Cm, chunk=128, interpret=True)
    ref = ssd_ref(x, dt, A, Bm, Cm)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-3, rtol=2e-3)
