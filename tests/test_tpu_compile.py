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
  at qwen2-vl-2b's GQA widths (12 query heads over 2 kv heads x 128),
  each over one layer's pool and over a pool stacked over layers;
* the paged engine's decode segment at minicpm-2b widths (4 layers),
  whose compiled program must write the stacked KV pool in place:
  no per-layer slab of it is sliced out, restacked or copied;
* the SSD scan fwd+bwd at zamba2-7b widths (112 heads x 64, state 64)
  and at granite-4.0-h-micro's (64 heads x 64, state 128, float32
  operands as the model passes them);
* granite-4.0-h-micro's fused training step at 10 layers (one period),
  batch 2 x 2048, kernels on and state donated: the SSD and flash
  kernels are in it, and it fits a v5e's memory.

The topology is described inside a module fixture (never at import: the
TPU library admits one process at a time), and the persistent
compilation cache is off for the whole file, since an entry written
without a chip cannot be read back.
"""
import dataclasses
import functools
import os
import re

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


@pytest.mark.parametrize("h,h_kv,d,n_pages,layers", [
    pytest.param(36, 36, 64, 512, 0, id="36-36-64-512"),  # minicpm-2b MHA
    pytest.param(12, 2, 128, 512, 0, id="12-2-128-512"),  # qwen2-vl GQA 6:1
    pytest.param(36, 36, 64, 512, 4, id="36-36-64-512-stacked"),
    pytest.param(12, 2, 128, 512, 4, id="12-2-128-512-stacked"),
])
def test_flash_decode_paged(one_chip, h, h_kv, d, n_pages, layers):
    """``layers`` 0: one layer's (N, PS, W) pool; otherwise an
    (L, N, PS, W) pool read at a traced layer index."""
    if not layers:
        pool = ((n_pages, 16, h_kv * d), BF16)
        _compile(_decode.flash_decode_paged, one_chip,
                 ((8, 1, h, d), BF16), pool, pool, ((8, 128), I32),
                 ((8,), I32))
        return
    pool = ((layers, n_pages, 16, h_kv * d), BF16)
    _compile(_decode.flash_decode_paged, one_chip,
             ((8, 1, h, d), BF16), pool, pool, ((8, 128), I32), ((8,), I32),
             ((), I32))


def _results(text, shape):
    """(name, opcode) of every instruction whose result is ``shape``."""
    pat = re.compile(r"^\s*(?:ROOT )?%(\S+) = " + re.escape(shape)
                     + r"\{[^}]*\} ([\w-]+)\(", re.M)
    return pat.findall(text)


def test_decode_segment_writes_pool_in_place(one_chip, monkeypatch):
    """The paged engine's 16-step decode segment at minicpm-2b widths
    with 4 layers, 8 slots of 2048 positions over a 512 x 16 pool,
    kernels on, the cache donated.  The pool rides in the layer scan's
    carry and the paged kernel reads it through a layer index, so the
    program holds no per-layer slab of the pool, and nothing copies,
    slices or restacks the stacked pool; the only pool-sized results
    are the in-place row scatters (and the loop plumbing)."""
    from types import SimpleNamespace

    from repro.configs import get_config
    from repro.kernels import ops
    from repro.launch.engine import DecodeEngine
    from repro.models import init_paged_cache, init_params

    monkeypatch.setattr(ops, "_route", lambda kind, use_ref=False: False)
    cfg = dataclasses.replace(get_config("minicpm-2b"), n_layers=4)
    seg = DecodeEngine._make_segment_fn(
        SimpleNamespace(cfg=cfg, segment=16, use_kernels=True))

    def cache():
        c = init_paged_cache(cfg, 8, 2048, page_size=16, n_pages=512)
        c["index"] = jnp.zeros((8,), I32)
        return c

    def spec(tree):
        return jax.tree.map(lambda t: jax.ShapeDtypeStruct(
            t.shape, t.dtype, sharding=one_chip), tree)

    params = spec(jax.eval_shape(
        lambda: init_params(cfg, jax.random.PRNGKey(0))))
    text = jax.jit(seg, donate_argnums=(1,)).lower(
        params, spec(jax.eval_shape(cache)),
        jax.ShapeDtypeStruct((8, 1), I32, sharding=one_chip)
    ).compile().as_text()
    assert "tpu_custom_call" in text

    slab = (_results(text, "bf16[512,16,2304]")
            + _results(text, "bf16[1,512,16,2304]"))
    assert not slab, slab
    stacked = _results(text, "bf16[4,512,16,2304]")
    moved = [(name, op) for name, op in stacked
             if op in ("copy", "dynamic-slice", "dynamic-update-slice",
                       "custom-call")          # custom-call: AllocateBuffer
             or (op == "fusion" and re.search(r"copy|dynamic", name))]
    assert not moved, moved
    assert any(op == "scatter" for _, op in stacked), stacked


def test_ssd_fwd_bwd(one_chip):
    b, s, h, p, n = 1, 4096, 112, 64, 64

    def fwd_bwd(x, dt, A, Bm, Cm):
        def loss(*a):
            return _ssd.ssd(*a).astype(F32).sum()
        return jax.grad(loss, argnums=(0, 1, 2, 3, 4))(x, dt, A, Bm, Cm)

    _compile(fwd_bwd, one_chip, ((b, s, h, p), BF16), ((b, s, h), BF16),
             ((h,), F32), ((b, s, n), BF16), ((b, s, n), BF16))


def test_ssd_fwd_bwd_granite(one_chip):
    b, s, h, p, n = 2, 2048, 64, 64, 128

    def fwd_bwd(x, dt, A, Bm, Cm):
        def loss(*a):
            return _ssd.ssd(*a).sum()
        return jax.grad(loss, argnums=(0, 1, 2, 3, 4))(x, dt, A, Bm, Cm)

    _compile(fwd_bwd, one_chip, ((b, s, h, p), F32), ((b, s, h), F32),
             ((h,), F32), ((b, s, n), F32), ((b, s, n), F32))


def test_hybrid_group_step(one_chip, monkeypatch):
    from repro.configs import get_config
    from repro.kernels import ops
    from repro.launch.cluster import JobSpec, make_group_step
    from repro.models import init_params
    from repro.train import adamw_init

    monkeypatch.setattr(ops, "_route", lambda kind, use_ref=False: False)
    cfg = dataclasses.replace(get_config("granite-4.0-h-micro"), n_layers=10)
    step = make_group_step([JobSpec(cfg, batch=2, seq=2048,
                                    use_kernels=True)], donate=True)

    def spec(tree):
        return jax.tree.map(lambda t: jax.ShapeDtypeStruct(
            t.shape, t.dtype, sharding=one_chip), tree)
    params = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    tokens = jax.ShapeDtypeStruct((2, 2048), I32, sharding=one_chip)
    compiled = step.lower(spec(params), spec(jax.eval_shape(
        lambda: adamw_init(params))), {"tokens": tokens, "labels": tokens}
    ).compile()
    text = compiled.as_text()
    kernels = re.findall(r"^\s*(?:ROOT )?%(\w+?)(?:\.\d+)? = .*"
                         r'custom_call_target="tpu_custom_call"', text, re.M)
    # nine Mamba2 layers: a forward, its recompute and a backward each
    assert kernels.count("ssd_fwd") == 18 and kernels.count("ssd_bwd") == 9
    assert {"flash_attention_fwd", "flash_attention_dq",
            "flash_attention_dkv"} <= set(kernels)
    m = compiled.memory_analysis()
    peak = (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)
    assert peak < 15e9, peak
