"""Paged KV cache: paged == dense token identity across cache families,
page-table growth/reclaim on slot reuse, and the paged flash-decode
kernel against its gather oracle."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.kernels import ops
from repro.launch import serve
from repro.launch.engine import DecodeEngine
from repro.models import init_paged_cache, init_params
from repro.models.attention import attention_decode, attention_init

# every family with a linear KV cache (the ones paging applies to)
PAGED_ARCHS = [
    ("minicpm-2b", {}),                                    # dense
    ("granite-moe-3b-a800m", {"moe_capacity_factor": 8.0}),  # moe
    ("qwen2-vl-2b", {}),                                   # vlm
    ("zamba2-7b", {}),                                     # hybrid + SSM state
]


def _cfg(name, **kw):
    return dataclasses.replace(get_config(name).reduced(),
                               dtype="float32", **kw)


# ====================================================================== #
# paged flash-decode kernel
# ====================================================================== #
class TestFlashDecodePagedKernel:
    def test_matches_gather_oracle(self):
        rng = np.random.default_rng(0)
        b, h, hkv, d = 3, 4, 2, 16               # GQA groups = 2
        ps, n_pg, p_tab = 8, 11, 4               # table covers 32 rows
        q = jnp.asarray(rng.standard_normal((b, 1, h, d)), jnp.float32)
        pool_k = jnp.asarray(rng.standard_normal((n_pg, ps, hkv * d)),
                             jnp.float32)
        pool_v = jnp.asarray(rng.standard_normal((n_pg, ps, hkv * d)),
                             jnp.float32)
        lengths = jnp.asarray([5, 17, 32], jnp.int32)
        pages = np.full((b, p_tab), -1, np.int32)
        free = list(range(n_pg))
        for bi in range(b):
            for pi in range(-(-int(lengths[bi]) // ps)):
                pages[bi, pi] = free.pop()
        pages = jnp.asarray(pages)

        out = ops.flash_decode_paged(q, pool_k, pool_v, pages, lengths)

        gk = pool_k[jnp.maximum(pages, 0)].reshape(b, p_tab * ps, hkv, d)
        gv = pool_v[jnp.maximum(pages, 0)].reshape(b, p_tab * ps, hkv, d)
        rep = lambda t: jnp.repeat(t, h // hkv, axis=2)   # noqa: E731
        from repro.kernels.ref import flash_decode_ref
        ref = flash_decode_ref(q, rep(gk), rep(gv), lengths)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5)


# ====================================================================== #
# attention_decode paged branch
# ====================================================================== #
class TestAttentionDecodePaged:
    def _setup(self, b=3, max_len=32, ps=8, n_pg=16):
        rng = np.random.default_rng(1)
        d_model, nh, nkv, hd = 64, 4, 2, 16
        p = attention_init(jax.random.PRNGKey(0), d_model, nh, nkv, hd)
        x = jnp.asarray(rng.standard_normal((b, 1, d_model)), jnp.float32)
        idx = jnp.asarray([3, 11, 30])
        p_tab = max_len // ps
        dense = {"k": jnp.asarray(rng.standard_normal((b, max_len, nkv, hd)),
                                  jnp.float32),
                 "v": jnp.asarray(rng.standard_normal((b, max_len, nkv, hd)),
                                  jnp.float32)}
        # build pool + tables holding the same rows as the dense cache
        pages = np.full((b, p_tab), -1, np.int32)
        pool_k = np.zeros((n_pg, ps, nkv * hd), np.float32)
        pool_v = np.zeros((n_pg, ps, nkv * hd), np.float32)
        free = list(range(n_pg))
        for bi in range(b):
            for pi in range(-(-(int(idx[bi]) + 1) // ps)):
                pg = free.pop()
                pages[bi, pi] = pg
                pool_k[pg] = np.asarray(
                    dense["k"][bi, pi * ps:(pi + 1) * ps]).reshape(ps, -1)
                pool_v[pg] = np.asarray(
                    dense["v"][bi, pi * ps:(pi + 1) * ps]).reshape(ps, -1)
        paged = {"k": jnp.asarray(pool_k), "v": jnp.asarray(pool_v)}
        kw = dict(n_heads=nh, n_kv_heads=nkv, head_dim=hd)
        return p, x, idx, dense, paged, jnp.asarray(pages), kw

    def test_paged_jnp_bitwise_equals_dense(self):
        p, x, idx, dense, paged, pages, kw = self._setup()
        out_d, _ = attention_decode(p, x, None, None, dense, idx, **kw)
        out_p, _ = attention_decode(p, x, None, None, paged, idx,
                                    pages=pages, **kw)
        assert (np.asarray(out_d) == np.asarray(out_p)).all()

    def test_paged_kernel_close_to_dense_kernel(self):
        p, x, idx, dense, paged, pages, kw = self._setup()
        out_d, _ = attention_decode(p, x, None, None, dense, idx,
                                    use_kernel=True, **kw)
        out_p, _ = attention_decode(p, x, None, None, paged, idx,
                                    use_kernel=True, pages=pages, **kw)
        np.testing.assert_allclose(np.asarray(out_d), np.asarray(out_p),
                                   atol=1e-5, rtol=1e-5)

    def test_write_lands_in_owning_page(self):
        p, x, idx, dense, paged, pages, kw = self._setup()
        _, cache_p = attention_decode(p, x, None, None, paged, idx,
                                      pages=pages, **kw)
        _, cache_d = attention_decode(p, x, None, None, dense, idx, **kw)
        ps = paged["k"].shape[1]
        for bi, i in enumerate(np.asarray(idx)):
            pg = int(pages[bi, i // ps])
            np.testing.assert_array_equal(
                np.asarray(cache_p["k"][pg, i % ps]),
                np.asarray(cache_d["k"][bi, i]).reshape(-1))

    def test_unassigned_page_write_drops(self):
        """An example whose table has no page for its index (an inactive
        engine slot) must not corrupt the pool — in particular not the
        LAST page, which a wrapping ``.at[-1]`` would hit."""
        p, x, idx, dense, paged, pages, kw = self._setup()
        blank = jnp.full_like(pages, -1)
        _, cache_p = attention_decode(p, x, None, None, paged, idx,
                                      pages=blank, **kw)
        assert (np.asarray(cache_p["k"]) == np.asarray(paged["k"])).all()
        assert (np.asarray(cache_p["v"]) == np.asarray(paged["v"])).all()


# ====================================================================== #
# init_paged_cache contract
# ====================================================================== #
class TestInitPagedCache:
    def test_rejects_indivisible_page_size(self):
        cfg = _cfg("minicpm-2b")
        with pytest.raises(AssertionError):
            init_paged_cache(cfg, 2, 33, page_size=8, n_pages=8)

    def test_rejects_sliding_window(self):
        cfg = _cfg("glm4-9b", sliding_window=8)
        with pytest.raises(AssertionError):
            init_paged_cache(cfg, 2, 32, page_size=8, n_pages=8)

    def test_rejects_pure_ssm(self):
        cfg = _cfg("xlstm-1.3b")
        with pytest.raises(ValueError):
            init_paged_cache(cfg, 2, 32, page_size=8, n_pages=8)

    def test_pool_shapes(self):
        cfg = _cfg("minicpm-2b")
        cache = init_paged_cache(cfg, 2, 32, page_size=8, n_pages=8)
        assert cache["pages"].shape == (2, 4)
        assert (np.asarray(cache["pages"]) == -1).all()
        k = cache["units"]["k"]
        assert k.shape == (cfg.n_units, 8, 8, cfg.n_kv_heads * cfg.head_dim)


# ====================================================================== #
# paged DecodeEngine
# ====================================================================== #
class TestPagedEngine:
    @pytest.mark.parametrize("name,kw", PAGED_ARCHS)
    def test_paged_tokens_identical_to_dense_and_solo(self, name, kw):
        """The PR-4 slot no-leak scenario, run through the paged engine:
        more requests than slots, every request must match both the dense
        engine and its solo generation bit for bit."""
        cfg = _cfg(name, **kw)
        params = init_params(cfg, jax.random.PRNGKey(0))
        rng = np.random.default_rng(0)
        prompts = [rng.integers(0, cfg.vocab, (pl,))
                   for pl in (5, 8, 3, 8, 6)]
        news = [7, 4, 9, 6, 5]
        dense = DecodeEngine(cfg, params, n_slots=2, max_len=32, segment=4)
        rd = [dense.submit(p, n) for p, n in zip(prompts, news)]
        out_d = dense.run()
        paged = DecodeEngine(cfg, params, n_slots=2, max_len=32, segment=4,
                             paged=True, page_size=8, n_pages=8)
        rp = [paged.submit(p, n) for p, n in zip(prompts, news)]
        out_p = paged.run()
        for a, b, prompt, n in zip(rd, rp, prompts, news):
            assert out_d[a] == out_p[b], f"request {b} diverged from dense"
            solo = serve.generate(
                cfg, params, jnp.asarray(prompt, jnp.int32)[None, :],
                max_new_tokens=n, max_len=32)
            assert out_p[b] == [int(t) for t in np.asarray(solo)[0]], \
                f"request {b} diverged from its solo generation"

    def test_paged_kernel_tokens_identical_to_dense_kernel(self):
        cfg = _cfg("minicpm-2b")
        params = init_params(cfg, jax.random.PRNGKey(0))
        rng = np.random.default_rng(0)
        prompts = [rng.integers(0, cfg.vocab, (pl,)) for pl in (5, 8, 3)]
        outs = []
        for paged in (False, True):
            eng = DecodeEngine(cfg, params, n_slots=2, max_len=32,
                               segment=4, use_kernels=True, paged=paged,
                               page_size=8, n_pages=8)
            rids = [eng.submit(p, 6) for p in prompts]
            out = eng.run()
            outs.append([out[r] for r in rids])
        assert outs[0] == outs[1]

    def test_growth_and_reclaim_on_slot_reuse(self):
        """Pages are assigned lazily (prompt pages at admission, decode
        pages one segment ahead) and every page and reservation returns
        to the pool when a slot frees — across slot reuse."""
        cfg = _cfg("minicpm-2b")
        params = init_params(cfg, jax.random.PRNGKey(0))
        rng = np.random.default_rng(0)
        eng = DecodeEngine(cfg, params, n_slots=2, max_len=32, segment=4,
                           paged=True, page_size=8, n_pages=8)
        # need = 5 prompt + 8 decode rows = 13 -> reserve 2 pages, but
        # only 1 is assigned at admission (prompt fits one page)
        eng.submit(rng.integers(0, cfg.vocab, (5,)), 8)
        eng._admit()
        assert eng._slot_npages[0] == 1 and eng._slot_reserve[0] == 2
        assert eng._avail_pages == 8 - 2
        eng._grow()           # covers rows [0, 5+4) -> second page assigned
        assert eng._slot_npages[0] == 2
        assert len(eng._free_pages) == 8 - 2
        # drain; then run more requests through the same slots
        while eng.queue or eng.active.any():
            eng.step_segment()
        for _ in range(3):
            eng.submit(rng.integers(0, cfg.vocab, (6,)), 7)
        eng.run()
        # full reclaim: every page free, every reservation returned
        assert sorted(eng._free_pages) == list(range(8))
        assert eng._avail_pages == 8
        assert (eng._pages_np == -1).all()
        assert (eng._slot_npages == 0).all()
        assert (eng._slot_reserve == 0).all()
        assert eng.stats["pages_in_use"] >= 0
        assert eng.stats["peak_pages_in_use"] > 0

    def test_admission_defers_until_pages_free(self):
        """With pages for only two concurrent requests, the rest of the
        queue waits (FIFO) and still completes identical to solo."""
        cfg = _cfg("minicpm-2b")
        params = init_params(cfg, jax.random.PRNGKey(0))
        rng = np.random.default_rng(0)
        eng = DecodeEngine(cfg, params, n_slots=4, max_len=32, segment=4,
                           paged=True, page_size=8, n_pages=4)
        prompts = [rng.integers(0, cfg.vocab, (5,)) for _ in range(4)]
        rids = [eng.submit(p, 7) for p in prompts]
        out = eng.run()
        assert eng.stats["admission_deferred_pages"] > 0
        assert eng.stats["peak_active_slots"] == 2   # 4 pages / 2 per req
        for rid, prompt in zip(rids, prompts):
            solo = serve.generate(
                cfg, params, jnp.asarray(prompt, jnp.int32)[None, :],
                max_new_tokens=7, max_len=32)
            assert out[rid] == [int(t) for t in np.asarray(solo)[0]]

    def test_more_slots_than_dense_at_equal_memory(self):
        """The acceptance scenario in miniature: at the same pool rows a
        paged engine runs 4x the concurrent requests of the dense one."""
        cfg = _cfg("minicpm-2b")
        params = init_params(cfg, jax.random.PRNGKey(0))
        rng = np.random.default_rng(0)
        prompts = [rng.integers(0, cfg.vocab, (8,)) for _ in range(8)]
        # dense: 2 slots x 64 rows = 128; paged: same 128 rows as 16 pages
        dense = DecodeEngine(cfg, params, n_slots=2, max_len=64, segment=8)
        rd = [dense.submit(p, 8) for p in prompts]
        out_d = dense.run()
        paged = DecodeEngine(cfg, params, n_slots=8, max_len=64, segment=8,
                             paged=True, page_size=8, n_pages=16)
        rp = [paged.submit(p, 8) for p in prompts]
        out_p = paged.run()
        assert [out_d[a] for a in rd] == [out_p[b] for b in rp]
        assert dense.stats["peak_active_slots"] == 2
        assert paged.stats["peak_active_slots"] == 8      # 4x
        assert paged.stats["segments"] < dense.stats["segments"]

    def test_rejects_non_linear_kv(self):
        cfg = _cfg("xlstm-1.3b")
        params = init_params(cfg, jax.random.PRNGKey(0))
        with pytest.raises(ValueError, match="linear"):
            DecodeEngine(cfg, params, n_slots=2, max_len=32, paged=True)
        cfg = _cfg("glm4-9b", sliding_window=8)
        params = init_params(cfg, jax.random.PRNGKey(0))
        with pytest.raises(ValueError, match="linear"):
            DecodeEngine(cfg, params, n_slots=2, max_len=32, paged=True)
