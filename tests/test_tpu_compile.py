"""Every Pallas kernel compiles for a TPU v5e at real widths.

On the CPU the kernels run in interpret mode, which accepts block shapes
and scratch layouts the TPU's compiler refuses.  These tests lower each
kernel (forward, and backward where there is one) for a *described*
v5e chip — libtpu compiles it without the chip being attached — and
check that a Mosaic kernel (``tpu_custom_call``) is in the program:

* flash attention fwd+bwd at minicpm-2b widths (36 heads x 64, bf16),
  S = 1024, 2048, 4096, and its suffix-prefill forward;
* ``flash_decode`` and ``flash_decode_paged`` at minicpm-2b serving
  widths (8 slots, 2048-row cache, 16-row pages), and the paged kernel
  at qwen2-vl-2b's GQA widths (12 query heads over 2 kv heads x 128);
* the SSD scan fwd+bwd at zamba2-7b widths (112 heads x 64, state 64).

The topology is described inside a module fixture (never at import: the
TPU library admits one process at a time), and the persistent
compilation cache is off for the whole file, since an entry written
without a chip cannot be read back.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import flash_attention as _flash
from repro.kernels import flash_decode as _decode
from repro.kernels import mamba2_scan as _ssd


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:           # no libtpu, or it is held elsewhere
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


BF16, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32


@pytest.mark.parametrize("seq", [1024, 2048, 4096])
def test_flash_attention_fwd_bwd(one_chip, seq):
    shape = ((1, 36, seq, 64), BF16)

    def fwd_bwd(q, k, v):
        def loss(q, k, v):
            return _flash.flash_attention(q, k, v).astype(F32).sum()
        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    _compile(fwd_bwd, one_chip, shape, shape, shape)


def test_flash_attention_extend(one_chip):
    """Suffix prefill of a prefix hit: 32 suffix rows over 288 keys."""
    _compile(functools.partial(_flash.flash_attention_extend, q_offset=256),
             one_chip, ((1, 36, 32, 64), BF16), ((1, 36, 288, 64), BF16),
             ((1, 36, 288, 64), BF16))


def test_flash_decode(one_chip):
    _compile(_decode.flash_decode, one_chip,
             ((8, 1, 36, 64), BF16), ((8, 2048, 36, 64), BF16),
             ((8, 2048, 36, 64), BF16), ((8,), I32))


@pytest.mark.parametrize("h,h_kv,d,n_pages", [
    (36, 36, 64, 512),          # minicpm-2b (MHA)
    (12, 2, 128, 512),          # qwen2-vl-2b (GQA, 6 query heads per kv)
])
def test_flash_decode_paged(one_chip, h, h_kv, d, n_pages):
    pool = ((n_pages, 16, h_kv * d), BF16)
    _compile(_decode.flash_decode_paged, one_chip,
             ((8, 1, h, d), BF16), pool, pool, ((8, 128), I32), ((8,), I32))


def test_ssd_fwd_bwd(one_chip):
    b, s, h, p, n = 1, 4096, 112, 64, 64

    def fwd_bwd(x, dt, A, Bm, Cm):
        def loss(*a):
            return _ssd.ssd(*a).astype(F32).sum()
        return jax.grad(loss, argnums=(0, 1, 2, 3, 4))(x, dt, A, Bm, Cm)

    _compile(fwd_bwd, one_chip, ((b, s, h, p), BF16), ((b, s, h), BF16),
             ((h,), F32), ((b, s, n), BF16), ((b, s, n), BF16))
