"""Prefix-shared paged KV cache (DESIGN.md §18): radix-trie index,
suffix-extend prefill bitwise identity, copy-on-write forking, credit
accounting, LRU retention, brown-out eviction, and property-based
refcount invariants over admit/decode/fork/release sequences."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.kernels import autotune, ops
from repro.kernels import ref as kref
from repro.launch import serve
from repro.launch.engine import DecodeEngine
from repro.launch.prefix import PrefixTrie
from repro.models import init_cache, init_params, prefill, prefill_extend

from _hypothesis_compat import HealthCheck, given, settings, st

# families whose suffix-extend prefill is bitwise-stable (the gate for
# prefix_share); moe qualifies only under the per-token dense dispatch
SHARE_ARCHS = [
    ("minicpm-2b", {}),                                    # dense
    ("qwen2-vl-2b", {}),                                   # vlm
    ("granite-moe-3b-a800m", {"moe_capacity_factor": 8.0,
                              "moe_dispatch": "dense"}),   # moe
]


def _cfg(name, **kw):
    return dataclasses.replace(get_config(name).reduced(),
                               dtype="float32", **kw)


_PARAMS = {}


def _params(cfg):
    if cfg.name not in _PARAMS:
        _PARAMS[cfg.name] = init_params(cfg, jax.random.PRNGKey(0))
    return _PARAMS[cfg.name]


# ====================================================================== #
# radix trie
# ====================================================================== #
class TestPrefixTrie:
    def test_miss_on_empty(self):
        t = PrefixTrie(4)
        pages, n = t.match([1, 2, 3])
        assert pages == [] and n == 0

    def test_insert_then_match_full_and_partial(self):
        t = PrefixTrie(4)
        new = t.insert([1, 2, 3, 4, 5, 6, 7, 8], [10, 11])
        assert new == [10, 11]
        assert t.page_count() == 2
        pages, n = t.match([1, 2, 3, 4, 5, 6, 7, 8, 9])
        assert pages == [10, 11] and n == 8
        # mid-node divergence: matched rows counted, chain ends there
        pages, n = t.match([1, 2, 3, 4, 5, 6, 99, 0])
        assert pages == [10, 11] and n == 6

    def test_reinsert_reuses_nodes(self):
        t = PrefixTrie(4)
        t.insert([1, 2, 3, 4], [7])
        assert t.insert([1, 2, 3, 4], [9]) == []   # node 7 authoritative
        assert t.page_count() == 1
        assert t.match([1, 2, 3, 4])[0] == [7]

    def test_partial_tail_covered_by_longer_sibling_is_skipped(self):
        t = PrefixTrie(4)
        t.insert([1, 2, 3, 4], [7])
        assert t.insert([1, 2], [8]) == []         # rows served by 7
        assert t.page_count() == 1

    def test_divergent_tail_becomes_sibling(self):
        t = PrefixTrie(4)
        t.insert([1, 2, 3, 4, 5, 5], [7, 8])
        new = t.insert([1, 2, 3, 4, 6, 6], [7, 9])
        assert new == [9]
        assert t.match([1, 2, 3, 4, 6, 6]) == ([7, 9], 6)
        assert t.match([1, 2, 3, 4, 5, 5]) == ([7, 8], 6)

    def test_lru_eviction_leaves_first_oldest_first(self):
        t = PrefixTrie(4)
        refs = np.ones(16, np.int32)
        t.insert([1, 2, 3, 4, 5, 6, 7, 8], [10, 11])
        t.insert([1, 2, 3, 4, 9, 9, 9, 9], [10, 12])
        t.match([1, 2, 3, 4, 9, 9, 9, 9])          # 12 most recent
        assert t.evict_lru(refs) == 11             # LRU leaf first
        assert t.evict_lru(refs) == 12
        assert t.evict_lru(refs) == 10             # interior drained
        assert t.evict_lru(refs) is None

    def test_pinned_page_blocks_eviction_but_not_siblings(self):
        t = PrefixTrie(4)
        refs = np.ones(16, np.int32)
        t.insert([1, 2, 3, 4, 5, 6, 7, 8], [10, 11])
        t.insert([1, 2, 3, 4, 9, 9, 9, 9], [10, 12])
        refs[11] = 2                               # a slot still maps 11
        assert t.evictable_pages(refs) == 1        # only 12 (10 blocked)
        assert t.evict_lru(refs) == 12
        refs[11] = 1
        assert t.evictable_pages(refs) == 2


# ====================================================================== #
# suffix-extend prefill: bitwise vs the full one-shot prefill
# ====================================================================== #
class TestPrefillExtend:
    @pytest.mark.parametrize("use_kernels", [False, True],
                             ids=["jnp", "kernel"])
    @pytest.mark.parametrize("name,kw", SHARE_ARCHS,
                             ids=[a for a, _ in SHARE_ARCHS])
    def test_bitwise_identity_suffix_ge_2(self, name, kw, use_kernels):
        cfg = _cfg(name, **kw)
        params = _params(cfg)
        rng = np.random.default_rng(0)
        max_len = 32
        for plen, start in [(12, 7), (9, 2), (16, 8), (13, 11)]:
            toks = jnp.asarray(rng.integers(0, cfg.vocab, (1, plen)),
                               jnp.int32)
            lg_full, c_full = prefill(cfg, params,
                                      init_cache(cfg, 1, max_len), toks,
                                      use_kernels=use_kernels)
            c_pre = init_cache(cfg, 1, max_len)

            def take(dst, src):
                if dst.ndim >= 3 and dst.shape[2] == max_len:
                    return dst.at[:, :, :start].set(src[:, :, :start])
                return dst
            c_pre["units"] = jax.tree.map(take, c_pre["units"],
                                          c_full["units"])
            lg_ext, c_ext = prefill_extend(cfg, params, c_pre,
                                           toks[:, start:], start=start,
                                           use_kernels=use_kernels)
            assert (np.asarray(lg_full[:, start:])
                    == np.asarray(lg_ext)).all(), (name, plen, start)

            def rows_equal(a, b):
                if a.ndim >= 3 and a.shape[2] == max_len:
                    assert (np.asarray(a[:, :, :plen])
                            == np.asarray(b[:, :, :plen])).all()
            jax.tree.map(rows_equal, c_full["units"], c_ext["units"])

    def test_rejects_unsupported_family(self):
        cfg = _cfg("zamba2-7b")
        with pytest.raises(AssertionError):
            prefill_extend(cfg, _params(cfg), init_cache(cfg, 1, 32),
                           jnp.zeros((1, 4), jnp.int32), start=4)


# ====================================================================== #
# engine: gating, identity, COW, capacity, reclaim
# ====================================================================== #
def _share_engine(cfg, params, **kw):
    kw.setdefault("n_slots", 4)
    kw.setdefault("max_len", 64)
    kw.setdefault("segment", 8)
    kw.setdefault("paged", True)
    kw.setdefault("page_size", 8)
    kw.setdefault("n_pages", 32)
    kw.setdefault("debug", True)
    return DecodeEngine(cfg, params, **kw)


def _drain(eng, prompts, tokens=8):
    rids = [eng.submit(p, tokens) for p in prompts]
    eng.run()
    return {r: eng.outputs[r] for r in rids}


class TestPrefixEngineGating:
    def test_requires_paged(self):
        cfg = _cfg("minicpm-2b")
        with pytest.raises(ValueError, match="paged"):
            DecodeEngine(cfg, _params(cfg), prefix_share=True)

    @pytest.mark.parametrize("name,kw,msg", [
        ("zamba2-7b", {}, "bitwise-stable"),               # hybrid
        ("granite-moe-3b-a800m",
         {"moe_capacity_factor": 8.0}, "bitwise-stable"),  # moe einsum
    ])
    def test_rejects_unstable_families(self, name, kw, msg):
        cfg = _cfg(name, **kw)
        with pytest.raises(ValueError, match=msg):
            DecodeEngine(cfg, _params(cfg), paged=True, page_size=8,
                         n_pages=32, max_len=64, prefix_share=True)

    def test_accepts_moe_dense_dispatch(self):
        cfg = _cfg("granite-moe-3b-a800m", moe_capacity_factor=8.0,
                   moe_dispatch="dense")
        eng = _share_engine(cfg, _params(cfg), prefix_share=True)
        assert eng.prefix_share


class TestPrefixEngine:
    @pytest.mark.parametrize("use_kernels", [False, True],
                             ids=["jnp", "kernel"])
    @pytest.mark.parametrize("name,kw", SHARE_ARCHS,
                             ids=[a for a, _ in SHARE_ARCHS])
    def test_identity_vs_private_and_solo(self, name, kw, use_kernels):
        """Shared-prefix tokens == private-pages tokens == solo
        generation, across every family supporting the paged layout
        with a bitwise-stable extend path."""
        cfg = _cfg(name, **kw)
        params = _params(cfg)
        rng = np.random.default_rng(0)
        shared = rng.integers(0, cfg.vocab, 20)    # 2.5 pages: COW too
        prompts = [np.concatenate([shared, rng.integers(0, cfg.vocab, 6)])
                   for _ in range(5)]
        base = _drain(_share_engine(cfg, params, prefix_share=False,
                                    use_kernels=use_kernels), prompts)
        eng = _share_engine(cfg, params, prefix_share=True,
                            use_kernels=use_kernels)
        out = _drain(eng, prompts)
        assert out == base
        assert eng.stats["prefix_hits"] >= 4
        assert eng.stats["prefill_tokens_saved"] > 0
        solo = serve.generate(cfg, params, jnp.asarray(prompts[1])[None, :],
                              max_new_tokens=8, max_len=64,
                              use_kernels=use_kernels)
        assert list(np.asarray(solo)[0]) == out[1]

    def test_cow_fork_on_boundary_page(self):
        """An unaligned prompt publishes its tail page; the first decode
        write forks it (shared-then-diverge == fully-private)."""
        cfg = _cfg("minicpm-2b")
        params = _params(cfg)
        rng = np.random.default_rng(1)
        shared = rng.integers(0, cfg.vocab, 20)
        prompts = [np.concatenate([shared, rng.integers(0, cfg.vocab, 6)])
                   for _ in range(4)]               # plen 26 = 3.25 pages
        base = _drain(_share_engine(cfg, params, prefix_share=False),
                      prompts)
        eng = _share_engine(cfg, params, prefix_share=True)
        assert _drain(eng, prompts) == base
        assert eng.stats["cow_forks"] >= 1

    def test_capacity_at_equal_memory(self):
        """Sharing admits >= 2x the concurrent requests of the private
        baseline at the same page pool."""
        cfg = _cfg("minicpm-2b")
        params = _params(cfg)
        rng = np.random.default_rng(2)
        shared = rng.integers(0, cfg.vocab, 24)     # 3 full pages
        prompts = [np.concatenate([shared, rng.integers(0, cfg.vocab, 8)])
                   for _ in range(12)]              # plen 32, +1 decode pg
        kw = dict(n_slots=12, max_len=64, n_pages=20)
        private = _share_engine(cfg, params, prefix_share=False, **kw)
        base = _drain(private, prompts)
        eng = _share_engine(cfg, params, prefix_share=True, **kw)
        assert _drain(eng, prompts) == base
        assert private.stats["peak_active_slots"] == 4   # 20 // 5
        assert eng.stats["peak_active_slots"] >= 8

    def test_drain_returns_all_pages_below_watermark(self):
        """After a full drain with retain_pages=0 every page is back on
        the free list, the trie is empty, and credit is zero."""
        cfg = _cfg("minicpm-2b")
        params = _params(cfg)
        rng = np.random.default_rng(3)
        shared = rng.integers(0, cfg.vocab, 20)
        prompts = [np.concatenate([shared, rng.integers(0, cfg.vocab, 6)])
                   for _ in range(5)]
        eng = _share_engine(cfg, params, prefix_share=True, retain_pages=0)
        _drain(eng, prompts)
        assert sorted(eng._free_pages) == list(range(eng.n_pages))
        assert eng._trie.page_count() == 0
        assert (eng._page_refs == 0).all()
        assert eng._committed == 0
        assert (eng._pages_np == -1).all()
        assert eng.stats["prefix_evictions"] > 0
        eng._check_invariants()

    def test_retention_watermark_bounds_trie(self):
        cfg = _cfg("minicpm-2b")
        params = _params(cfg)
        rng = np.random.default_rng(4)
        prompts = [rng.integers(0, cfg.vocab, 16) for _ in range(6)]
        eng = _share_engine(cfg, params, prefix_share=True, retain_pages=4)
        _drain(eng, prompts)
        assert eng._trie.evictable_pages(eng._page_refs) <= 4
        assert eng.stats["prefix_evictions"] > 0

    def test_default_watermark_retains_prefixes(self):
        """With the default watermark (the whole pool) cached prefixes
        persist across drains — a later identical prompt still hits."""
        cfg = _cfg("minicpm-2b")
        params = _params(cfg)
        rng = np.random.default_rng(5)
        shared = rng.integers(0, cfg.vocab, 16)
        eng = _share_engine(cfg, params, prefix_share=True)
        _drain(eng, [np.concatenate([shared,
                                     rng.integers(0, cfg.vocab, 8)])])
        assert eng._trie.page_count() > 0
        _drain(eng, [np.concatenate([shared,
                                     rng.integers(0, cfg.vocab, 8)])])
        assert eng.stats["prefix_hits"] == 1

    def test_brownout_evicts_prefixes_before_shedding(self):
        """Satellite 6: under brown-out the engine reclaims zero-ref
        cached prefixes first (counted separately from shed requests),
        and sheds only what freed memory cannot admit."""
        cfg = _cfg("minicpm-2b")
        params = _params(cfg)
        rng = np.random.default_rng(6)
        shared = rng.integers(0, cfg.vocab, 24)
        mk = lambda: np.concatenate(  # noqa: E731
            [shared, rng.integers(0, cfg.vocab, 8)])
        eng = _share_engine(cfg, params, prefix_share=True, n_slots=8,
                            n_pages=20, max_len=64, brownout_depth=1)
        _drain(eng, [mk()])                        # cold cache: seed trie
        assert eng._trie.page_count() > 0
        rids = [eng.submit(mk(), 8) for _ in range(8)]
        eng.run()
        assert eng.stats["brownout_prefix_evictions"] > 0
        served = [r for r in rids if r not in eng.shed]
        for r in served:
            assert len(eng.outputs[r]) == 8
        # evictions are counted separately from shed requests, and the
        # freed pages admit more of the burst than the plain brown-out
        # formula (queue - depth = 7 shed) would have served
        assert eng.stats["shed_brownout"] == len(eng.shed)
        assert len(eng.shed) < len(rids) - eng.brownout_depth
        assert len(served) == len(rids) - len(eng.shed) >= 4

    def test_debug_asserts_on_sentinel_corruption(self):
        """Satellite 2: a -1 sentinel inside the mapped range (or a
        mapped entry past it) trips the debug audit."""
        cfg = _cfg("minicpm-2b")
        params = _params(cfg)
        eng = _share_engine(cfg, params, prefix_share=True)
        eng.submit(np.arange(8, dtype=np.int64) % cfg.vocab, 16)
        eng.step_segment()                 # debug mode audited this step
        assert eng.active.any()            # 8 tokens left: slot still live
        slot = int(np.argmax(eng.active))
        keep = eng._pages_np[slot, 0]
        eng._pages_np[slot, 0] = -1
        with pytest.raises(AssertionError, match="sentinel"):
            eng._check_invariants()
        eng._pages_np[slot, 0] = keep
        eng._pages_np[slot, 7] = 0                 # past npages
        with pytest.raises(AssertionError, match="past npages"):
            eng._check_invariants()


# ====================================================================== #
# tuned routing for the paged decode kernel (satellite 1)
# ====================================================================== #
class TestPagedDecodeRouting:
    def _args(self):
        rng = np.random.default_rng(7)
        b, h, hkv, d, ps, n_pg, p_tab = 2, 4, 2, 32, 8, 6, 2
        q = jnp.asarray(rng.standard_normal((b, 1, h, d)), jnp.float32)
        kp = jnp.asarray(rng.standard_normal((n_pg, ps, hkv * d)),
                         jnp.float32)
        vp = jnp.asarray(rng.standard_normal((n_pg, ps, hkv * d)),
                         jnp.float32)
        pages = jnp.asarray([[0, 1], [2, -1]], jnp.int32)
        lengths = jnp.asarray([16, 5], jnp.int32)
        return q, kp, vp, pages, lengths

    def _table(self, entries):
        return autotune.AutotuneTable(
            {"version": autotune.AUTOTUNE_VERSION, "created": 0.0,
             "meta": {"backend": jax.default_backend(), "interpret": True,
                      "smoke": True, "iters": 1},
             "entries": entries})

    def test_ref_entry_routes_to_gather_oracle_bitwise(self):
        q, kp, vp, pages, lengths = self._args()
        key = autotune.shape_key("flash_decode_paged", kp.shape[1],
                                 q.shape[3], q.dtype)
        try:
            autotune.set_table(self._table({key: {"backend": "ref"}}))
            out = ops.flash_decode_paged(q, kp, vp, pages, lengths)
        finally:
            autotune.reset_table()
        ref = kref.flash_decode_paged_ref(q, kp, vp, pages, lengths)
        assert (np.asarray(out) == np.asarray(ref)).all()

    def test_kernel_entry_keeps_kernel_path(self):
        q, kp, vp, pages, lengths = self._args()
        key = autotune.shape_key("flash_decode_paged", kp.shape[1],
                                 q.shape[3], q.dtype)
        try:
            autotune.set_table(self._table({key: {"backend": "kernel"}}))
            out = ops.flash_decode_paged(q, kp, vp, pages, lengths)
        finally:
            autotune.reset_table()
        ref = kref.flash_decode_paged_ref(q, kp, vp, pages, lengths)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5)

    def test_page_size_classes_do_not_collide(self):
        keys = {autotune.shape_key("flash_decode_paged", ps, 64,
                                   jnp.float32) for ps in (8, 16, 32)}
        assert len(keys) == 3


# ====================================================================== #
# property-based refcount invariants (satellite 3)
# ====================================================================== #
_ENGINES = {}


def _prop_engine(key, **kw):
    """One long-lived engine per property (jits compile once; state
    persisting across hypothesis examples is the point — the invariants
    must hold from ANY starting trie/refcount state)."""
    if key not in _ENGINES:
        cfg = _cfg("minicpm-2b")
        _ENGINES[key] = _share_engine(cfg, _params(cfg), n_slots=3,
                                      n_pages=24, **kw)
    return _ENGINES[key]


@st.composite
def _workloads(draw):
    """A sequence of prompts over a tiny shared-prefix family: tenant
    choice, prefix reuse length, and decode length all vary, covering
    admit/extend/COW-fork/release interleavings."""
    n = draw(st.integers(2, 5))
    reqs = [(draw(st.integers(0, 2)),              # tenant
             draw(st.sampled_from([8, 14, 20, 26])),   # plen (bounded:
             draw(st.sampled_from([8, 16])))       # one jit per plen)
            for _ in range(n)]
    retain = draw(st.sampled_from([0, None]))
    return reqs, retain


class TestRefcountProperties:
    @settings(max_examples=10, deadline=None,
              suppress_health_check=[HealthCheck.too_slow,
                                     HealthCheck.data_too_large])
    @given(_workloads())
    def test_invariants_hold_through_any_sequence(self, workload):
        """sum(refcounts) == mapped block-table entries + trie nodes at
        every segment (debug mode audits each step); no page is both
        free and referenced; a full drain returns every reservation."""
        reqs, retain = workload
        eng = _prop_engine(("inv", retain), prefix_share=True,
                           retain_pages=retain)
        rng = np.random.default_rng(8)
        tenants = [rng.integers(0, eng.cfg.vocab, 32) for _ in range(3)]
        rids = []
        for tenant, plen, tokens in reqs:
            rids.append((eng.submit(tenants[tenant][:plen], tokens),
                         tokens))
        eng.run()                      # debug=True audits every segment
        for rid, tokens in rids:
            assert len(eng.outputs[rid]) == tokens
        # full drain: every page accounted for
        refs = eng._page_refs
        assert len(eng._free_pages) + int((refs > 0).sum()) == eng.n_pages
        assert int(refs.sum()) == eng._trie.page_count()
        assert eng._committed == 0
        if retain == 0:
            assert eng._trie.page_count() == 0
            assert sorted(eng._free_pages) == list(range(eng.n_pages))
        eng._check_invariants()

    @settings(max_examples=6, deadline=None,
              suppress_health_check=[HealthCheck.too_slow,
                                     HealthCheck.data_too_large])
    @given(st.lists(st.tuples(st.integers(0, 2), st.integers(0, 5)),
                    min_size=2, max_size=4))
    def test_shared_tokens_bitwise_match_private(self, spec):
        """Bit-identical tokens, shared-prefix vs private-pages, over
        arbitrary tenant/suffix combinations (the shared engine's trie
        carries over between examples, so later examples mix warm hits
        with cold misses)."""
        rng = np.random.default_rng(9)
        cfg = _cfg("minicpm-2b")
        tenants = [rng.integers(0, cfg.vocab, 12) for _ in range(3)]
        sufs = [rng.integers(0, cfg.vocab, 6) for _ in range(6)]
        prompts = [np.concatenate([tenants[t], sufs[s]])
                   for t, s in spec]
        base = _drain(_prop_engine("bit-private", prefix_share=False),
                      prompts)
        out = _drain(_prop_engine("bit-shared", prefix_share=True),
                     prompts)
        assert list(base.values()) == list(out.values())
