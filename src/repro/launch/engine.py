"""Slot-based continuous batching: the serving twin of the simulator's
sharing scheduler.

A ``DecodeEngine`` owns a fixed number of decode *slots* (the batch
dimension of one shared cache pytree) and a FIFO queue of requests.
Decoding advances all slots together in fused ``lax.scan`` segments (one
dispatch per ``segment`` tokens, per-slot absolute positions carried in
the cache's ``index`` vector); between segments, finished slots are
freed and queued requests are admitted into them — each admission runs
the single-shot prefill for that request alone and scatters the
resulting cache rows into the slot, so a reused slot never observes the
previous occupant's state.

Inactive slots keep stepping (their compute is masked out only by
discarding the emitted tokens) — exactly the fixed-shape trade the
paper's GPU-sharing scheduler makes: pay a bounded, predictable cost per
step in exchange for never re-compiling and never stalling the batch.

Whisper-style encoder-decoder configs are not supported here (each
request would carry its own encoder pass; use ``serve.generate``).
"""
from __future__ import annotations

import contextlib
import dataclasses
import random
import time
from collections import deque
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.flash_decode import kv_grid
from repro.launch.cluster import ScriptedFaults, TransientFault
from repro.launch.prefix import PrefixTrie
from repro.launch.serve import _make_scan_generate, prefill_extend_cached
from repro.models import init_cache, init_paged_cache, prefill
from repro.util import spans
from repro.util.retry import RetryPolicy, retry_call


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray            # (plen,) i32
    max_new_tokens: int
    deadline: Optional[float] = None   # absolute clock time; None = none
    priority: int = 0                  # higher = more important
    submitted_at: float = 0.0


class DecodeEngine:
    """Continuous-batching decode engine over ``n_slots`` fixed slots.

    ``paged=True`` (DESIGN.md §15) swaps the dense per-slot KV cache for
    a shared page pool plus per-slot block tables: a slot holds only the
    pages its request actually occupies, so ``n_slots`` can far exceed
    what ``n_slots x max_len`` dense rows would allow at the same cache
    memory.  Admission is bounded by a page *reservation* — a request is
    admitted only when its worst-case page count (prompt + all decode
    segments) is available — while physical pages are assigned lazily,
    one segment ahead of the decode index, and reclaimed the moment the
    slot frees.  Tokens are bitwise identical to the dense engine.

    ``prefix_share=True`` (DESIGN.md §18) adds copy-on-write prefix
    sharing on top of paging: a radix trie over token IDs maps each
    incoming prompt to its longest cached prefix, whose pages are mapped
    read-only into the new slot (per-page refcounts; a page is writable
    only at refcount 1).  Admission charges reservation credit only for
    the request's *unique* pages, prefill computes only the un-cached
    suffix, and the first decode write into a still-shared boundary page
    forks just that page.  Zero-ref cached prefixes are reclaimed LRU
    under the ``retain_pages`` watermark — and eagerly under brown-out,
    so cache memory sheds before queued requests do."""

    def __init__(self, cfg, params, *, n_slots: int = 4, max_len: int = 256,
                 segment: int = 8, use_kernels: bool = False,
                 paged: bool = False, page_size: int = 16,
                 n_pages: Optional[int] = None,
                 prefix_share: bool = False,
                 retain_pages: Optional[int] = None,
                 debug: bool = False,
                 clock=time.monotonic,
                 brownout_depth: int = 0,
                 fault_injector: Optional[ScriptedFaults] = None,
                 retry_policy: Optional[RetryPolicy] = None,
                 retry_seed: int = 0,
                 sleep=time.sleep):
        assert not cfg.is_encoder_decoder, \
            "encoder-decoder configs are served via serve.generate"
        self.cfg, self.params = cfg, params
        self.n_slots, self.max_len, self.segment = n_slots, max_len, segment
        self.use_kernels = use_kernels
        self.paged = paged
        self.prefix_share = prefix_share
        self.debug = debug

        if prefix_share:
            if not paged:
                raise ValueError("prefix_share requires paged=True")
            # bitwise contract: suffix prefill (prefill_extend) must
            # reproduce the full prefill's rows exactly.  Proven for
            # dense/vlm attention and for MoE under the per-token
            # "dense" dispatch; the einsum/scatter MoE dispatches shape
            # their capacity buffers by sequence length, and SSM/hybrid
            # state is not page-addressable at all.
            ok = cfg.family in ("dense", "vlm") or (
                cfg.family == "moe" and cfg.moe_dispatch == "dense")
            if not ok:
                raise ValueError(
                    f"prefix_share needs a bitwise-stable suffix prefill; "
                    f"family {cfg.family!r} (moe_dispatch "
                    f"{getattr(cfg, 'moe_dispatch', None)!r}) has none")

        if paged:
            if not _has_linear_kv(cfg):
                raise ValueError(
                    f"paged KV requires a linear-layout KV cache; family "
                    f"{cfg.family!r} (window {cfg.sliding_window}) has none")
            if n_pages is None:     # dense-equivalent memory by default
                n_pages = n_slots * (max_len // page_size)
            # leaf classification below is by shape: the pool must not
            # coincide with the dense (n_slots, max_len) allocation
            assert not (n_pages == n_slots and page_size == max_len), \
                "degenerate paging (one max_len page per slot)"
            self.page_size, self.n_pages = page_size, n_pages
            cache = init_paged_cache(cfg, n_slots, max_len,
                                     page_size=page_size, n_pages=n_pages)
            dense_shapes = jax.eval_shape(
                lambda: init_cache(cfg, n_slots, max_len)["units"])
            self._is_pool = jax.tree.map(
                lambda pg, dn: pg.shape != dn.shape,
                cache["units"], dense_shapes)
            # the paged kernel's grid: per decode step, one call per
            # attention layer (a k and a v pool each), steps per slot each
            pools = [t for t, pl in zip(jax.tree.leaves(cache["units"]),
                                        jax.tree.leaves(self._is_pool))
                     if pl]
            self._kv_layers = sum(t.shape[0] for t in pools) // 2
            n, self._kv_steps = kv_grid(page_size, pools[0].shape[-1],
                                        max_len // page_size,
                                        pools[0].dtype.itemsize)
            self._kv_rows = n * page_size
            # host-side paging state
            self._free_pages: List[int] = list(range(n_pages))
            self._pages_np = np.full((n_slots, max_len // page_size), -1,
                                     np.int32)
            self._slot_npages = np.zeros(n_slots, np.int64)  # assigned
            self._slot_reserve = np.zeros(n_slots, np.int64)  # total credit
            self._slot_unique = np.zeros(n_slots, np.int64)  # non-shared
            self._index_np = np.zeros(n_slots, np.int64)     # decode pos
            # per-page refcounts: one per mapped block-table entry plus
            # one per trie node.  Free <=> 0; writable by a slot <=> 1.
            self._page_refs = np.zeros(n_pages, np.int32)
            # outstanding credit: sum over slots of (reserve - unique),
            # i.e. pages promised but not yet physically taken
            self._committed = 0
            self._trie = PrefixTrie(page_size) if prefix_share else None
            self.retain_pages = (n_pages if retain_pages is None
                                 else int(retain_pages))
        else:
            cache = init_cache(cfg, n_slots, max_len)
        cache["index"] = jnp.zeros((n_slots,), jnp.int32)  # per-slot position
        self.cache = cache
        self.tok = jnp.zeros((n_slots, 1), jnp.int32)      # next input token
        self.active = np.zeros(n_slots, bool)
        self.remaining = np.zeros(n_slots, np.int64)
        self.slot_rid: List[int] = [-1] * n_slots

        self.queue: deque = deque()
        self.outputs: Dict[int, List[int]] = {}
        self._next_rid = 0
        self._prefill_fns: Dict[Any, Any] = {}
        self._built: set = set()          # (program, shape) called once
        # the cache is donated: a segment updates the KV pool in place
        # instead of holding a second pool-sized copy while it runs
        self._segment_fn = jax.jit(self._make_segment_fn(),
                                   donate_argnums=(1,))
        # degraded-mode serving (DESIGN.md §16): per-request deadlines
        # with timeout-shedding, admission brown-out under overload, and
        # bounded retry of transient segment faults. All off by default.
        self._clock = clock
        self.brownout_depth = int(brownout_depth)
        self.fault_injector = fault_injector
        self.retry_policy = retry_policy or RetryPolicy()
        self._retry_rng = random.Random(retry_seed)
        self._sleep = sleep
        self.slot_deadline: List[Optional[float]] = [None] * n_slots
        self.shed: Dict[int, str] = {}        # rid -> shed reason
        self.retry_after: Dict[int, float] = {}   # rid -> backoff hint (s)
        self._seg_ewma = 0.0    # EWMA segment time, dispatch to tokens (s)
        self.stats = {"segments": 0, "admitted": 0, "wasted_slot_steps": 0,
                      "peak_active_slots": 0, "shed_deadline": 0,
                      "shed_brownout": 0, "deadline_miss": 0, "retries": 0}
        if paged:
            self.stats.update({
                "pages_total": n_pages, "pages_in_use": 0,
                "peak_pages_in_use": 0, "page_occupancy": 0.0,
                "page_fragmentation": 0.0, "admission_deferred_pages": 0,
                "kv_blocks_issued": 0, "kv_blocks_live": 0})
        if prefix_share:
            self.stats.update({
                "prefix_hits": 0, "prefix_misses": 0,
                "prefix_hit_rate": 0.0, "prefill_tokens_saved": 0,
                "prompt_tokens_total": 0, "cow_forks": 0,
                "prefix_evictions": 0, "brownout_prefix_evictions": 0,
                "shared_pages": 0, "unique_pages": 0, "trie_pages": 0})

    # -- page credit / refcounts (DESIGN.md §15, §18) ------------------- #
    @property
    def _avail_pages(self) -> int:
        """Admission credit: physically free pages minus outstanding
        reservations, plus pages reclaimable from zero-ref cached
        prefixes (the trie yields under admission pressure).  Without
        prefix sharing this equals ``n_pages - sum(reservations)``."""
        avail = len(self._free_pages) - self._committed
        if self.prefix_share:
            avail += self._trie.evictable_pages(self._page_refs)
        return avail

    def _take_page(self) -> int:
        """Pop a physically free page (refcount 0 -> 1), evicting the
        LRU zero-ref cached prefix page first if the free list is dry.
        An IndexError here means the reservation credit was violated."""
        if not self._free_pages and self.prefix_share:
            page = self._trie.evict_lru(self._page_refs)
            if page is not None:
                self._page_refs[page] -= 1
                self._free_pages.append(page)
                self.stats["prefix_evictions"] += 1
        page = self._free_pages.pop()
        self._page_refs[page] = 1
        return page

    # ------------------------------------------------------------------ #
    def submit(self, prompt, max_new_tokens: int = 16, *,
               deadline: Optional[float] = None,
               priority: int = 0) -> int:
        """Queue a request; returns its id (key into ``outputs``).

        ``deadline`` is relative (seconds from now on the engine clock):
        a request that has not *completed* by then is shed — from the
        queue or mid-decode — with its rid recorded in ``shed`` and a
        ``retry_after`` hint. ``priority`` orders brown-out shedding
        under overload (lower priorities shed first); admission itself
        stays FIFO."""
        prompt = np.asarray(prompt, np.int32)
        if _has_linear_kv(self.cfg):
            # a linear KV cache holds one row per prompt + generated
            # token, and a slot keeps stepping to the end of its last
            # segment — writes past max_len would be clamped/dropped
            # silently while the validity mask still trusts them
            segs = -(-max_new_tokens // self.segment)
            need = prompt.shape[0] + segs * self.segment
            assert need <= self.max_len, (
                f"request needs {need} cache rows (prompt "
                f"{prompt.shape[0]} + {segs}x{self.segment}-step "
                f"segments) but max_len is {self.max_len}")
        rid = self._next_rid
        self._next_rid += 1
        now = self._clock()
        self.queue.append(Request(
            rid, prompt, max_new_tokens,
            deadline=(now + deadline) if deadline is not None else None,
            priority=int(priority), submitted_at=now))
        self.outputs[rid] = []
        return rid

    # -- degraded mode (DESIGN.md §16) --------------------------------- #
    def _retry_after_hint(self) -> float:
        """Coarse back-pressure hint for a shed request: the EWMA
        segment walltime times the current queue depth — roughly when
        the backlog ahead of it will have drained a slot."""
        return self._seg_ewma * (1 + len(self.queue))

    def _shed_request(self, req: Request, reason: str) -> None:
        self.shed[req.rid] = reason
        self.retry_after[req.rid] = self._retry_after_hint()
        self.stats["shed_" + reason] += 1

    def _free_slot(self, slot: int) -> None:
        self.active[slot] = False
        self.slot_rid[slot] = -1
        self.slot_deadline[slot] = None
        self.remaining[slot] = 0
        if self.paged:
            self._free_slot_pages(slot)

    def _shed_expired(self, now: float) -> None:
        """Timeout-shedding: queued requests past their deadline never
        admit; active slots past theirs free immediately (the partial
        output stays in ``outputs`` — the caller sees what was decoded
        before the deadline)."""
        kept = deque()
        for req in self.queue:
            if req.deadline is not None and now > req.deadline:
                self._shed_request(req, "deadline")
            else:
                kept.append(req)
        self.queue = kept
        for slot in range(self.n_slots):
            dl = self.slot_deadline[slot]
            if self.active[slot] and dl is not None and now > dl:
                rid = self.slot_rid[slot]
                self.shed[rid] = "deadline"
                self.retry_after[rid] = self._retry_after_hint()
                self.stats["shed_deadline"] += 1
                self._free_slot(slot)

    def _admissible_now(self) -> int:
        """How many queued requests (FIFO prefix of the queue) could be
        admitted right now into free slots with the current page credit
        — the brown-out pass sheds only beyond this."""
        free_slots = int((~self.active).sum())
        avail, n = self._avail_pages, 0
        for req in self.queue:
            if n >= free_slots:
                break
            reserve, _ = self._plan_admission(req, touch=False)
            if reserve > avail:
                break
            avail -= reserve
            n += 1
        return n

    def _brownout(self) -> None:
        """Overload graceful degradation: when the queue is deeper than
        ``brownout_depth``, shed the lowest-priority (then youngest)
        queued requests until it fits — load sheds before latency
        collapses, and paying tiers degrade last.

        With prefix sharing the engine sheds *cache memory* first:
        every zero-ref cached prefix is evicted (counted separately in
        ``brownout_prefix_evictions``, not as shed requests), and only
        requests beyond what the freed pages can admit are dropped —
        the fewer-shed accounting of DESIGN.md §18."""
        if self.brownout_depth <= 0 or len(self.queue) <= self.brownout_depth:
            return
        if self.prefix_share:
            while True:
                page = self._trie.evict_lru(self._page_refs)
                if page is None:
                    break
                self._page_refs[page] -= 1
                self._free_pages.append(page)
                self.stats["brownout_prefix_evictions"] += 1
            excess = (len(self.queue) - self._admissible_now()
                      - self.brownout_depth)
            if excess <= 0:
                return
        else:
            excess = len(self.queue) - self.brownout_depth
        order = sorted(self.queue,
                       key=lambda r: (r.priority, -r.submitted_at))
        drop = {r.rid for r in order[:excess]}
        kept = deque()
        for req in self.queue:
            if req.rid in drop:
                self._shed_request(req, "brownout")
            else:
                kept.append(req)
        self.queue = kept

    # ------------------------------------------------------------------ #
    def _make_segment_fn(self):
        """One fused greedy scan segment — serve's scan body with the
        PRNG key pinned (greedy ignores it), continuing the carry."""
        run = _make_scan_generate(self.cfg, self.segment, True,
                                  self.use_kernels)
        key = jax.random.PRNGKey(0)

        def decode_segment(params, cache, tok):
            toks, cache, tok, _ = run(params, cache, tok, key)
            return toks, cache, tok
        return decode_segment

    def _first_call(self, kind: str, shape):
        """An ``engine.build`` span around the first call of program
        ``kind`` at ``shape`` (where it compiles, or loads from the
        persistent cache), counted in ``program_builds``; else nothing."""
        if (kind, shape) in self._built:
            return contextlib.nullcontext()
        self._built.add((kind, shape))
        spans.count("program_builds")
        return spans.span("engine.build", kind=kind, shape=str(shape))

    def _prefill_fn(self, plen: int):
        # hits (suffix extend) and misses (full prefill) take the same
        # attention path, kernel or jnp, so their rows stay
        # bitwise-consistent with each other and with a private engine
        key = ("prefill", plen)
        fn = self._prefill_fns.get(key)
        if fn is None:
            cfg, max_len, uk = self.cfg, self.max_len, self.use_kernels

            def run(params, tokens):
                cache = init_cache(cfg, 1, max_len)
                return prefill(cfg, params, cache, tokens, use_kernels=uk)
            fn = self._prefill_fns[key] = jax.jit(spans.named("prefill", run))
        return fn

    def _gather_fn(self, n_pg: int):
        """Jitted pool->dense gather: copy ``n_pg`` pool pages into rows
        ``[0, n_pg*page_size)`` of a fresh batch-1 dense cache, the
        launchpad for the suffix-extend prefill."""
        key = ("gather", n_pg)
        fn = self._prefill_fns.get(key)
        if fn is None:
            cfg, max_len, ps = self.cfg, self.max_len, self.page_size
            is_pool = self._is_pool

            def gather_prefix(units, pids):
                cache = init_cache(cfg, 1, max_len)

                def take(dn, pool, pl):
                    if not pl:
                        return dn
                    u = pool.shape[0]      # pool: (U, n_pages, ps, H*D)
                    rows = pool[:, pids].reshape(
                        (u, 1, n_pg * ps) + dn.shape[3:])
                    return dn.at[:, :, :n_pg * ps].set(rows.astype(dn.dtype))
                cache["units"] = jax.tree.map(
                    take, cache["units"], units, is_pool)
                return cache
            fn = self._prefill_fns[key] = jax.jit(gather_prefix)
        return fn

    # ------------------------------------------------------------------ #
    def _pages_needed(self, req: Request) -> int:
        """Worst-case page count for a request: one row per prompt token
        plus every position its slot will step through (the slot runs
        whole segments, so the last partial segment still writes rows)."""
        segs = -(-req.max_new_tokens // self.segment)
        rows = req.prompt.shape[0] + segs * self.segment
        return -(-rows // self.page_size)

    def _plan_admission(self, req: Request, *, touch: bool = True):
        """Reservation and prefix plan for one request.

        Returns ``(reserve, match)``.  Without prefix sharing,
        ``reserve`` is the worst-case page count and ``match`` is None.
        With it, the trie is consulted: ``match = (pages_m, L, f)``
        where ``L`` is the usable matched prefix length and ``f`` the
        fully-shared page count.  ``reserve`` charges only unique pages
        — the total minus the ``f`` shared ones — plus a one-page
        *boundary-fork allowance* whenever the prompt ends mid-page:
        publishing the tail page into the trie leaves it shared, and
        the first decode write must fork it.

        ``L`` is capped at ``plen - 2``: a one-row suffix matmul takes a
        different XLA accumulation path than the same row of the full
        prefill, so the bitwise contract needs >= 2 recomputed rows."""
        total = self._pages_needed(req)
        if not self.prefix_share:
            return total, None
        ps = self.page_size
        plen = req.prompt.shape[0]
        pages_m, matched = self._trie.match(req.prompt, touch=touch)
        L = max(0, min(matched, plen - 2))
        f = L // ps
        reserve = total - f + (1 if plen % ps else 0)
        return reserve, (pages_m, L, f)

    def _admit(self) -> None:
        """Fill every free slot from the queue: solo single-shot prefill,
        then scatter the request's cache rows into the slot (dense) or
        into freshly assigned pool pages (paged).  Paged admission is
        credit-gated: the request's worst-case *unique* page count is
        reserved up front (FIFO — an oversized head blocks the queue
        rather than being bypassed), so ``_grow`` can never run out of
        pages mid-flight."""
        for slot in range(self.n_slots):
            if self.active[slot] or not self.queue:
                continue
            reserve = match = None
            if self.paged:
                req = self.queue[0]
                reserve, match = self._plan_admission(req)
                if reserve > self._avail_pages:
                    self.stats["admission_deferred_pages"] += 1
                    break
                self.queue.popleft()
            else:
                req = self.queue.popleft()
            with spans.span("engine.admit", rid=req.rid,
                            matched=match[1] if match is not None else 0,
                            plen=req.prompt.shape[0]):
                self._admit_one(slot, req, reserve, match)

    def _admit_one(self, slot: int, req: Request, reserve, match) -> None:
        """Prefill ``req`` into ``slot`` and make the slot decode it."""
        plen = req.prompt.shape[0]
        if self.paged:
            logits = self._admit_paged(slot, req, reserve, match)
        else:
            assert plen <= self.max_len
            with self._first_call("prefill", plen):
                logits, pcache = self._prefill_fn(plen)(
                    self.params, jnp.asarray(req.prompt)[None, :])
            self.cache["units"] = _scatter_slot(
                self.cache["units"], pcache["units"], slot)
        self.cache["index"] = self.cache["index"].at[slot].set(plen)
        first = jnp.argmax(logits[0, -1]).astype(jnp.int32)
        self.tok = self.tok.at[slot, 0].set(first)
        self.active[slot] = True
        self.remaining[slot] = req.max_new_tokens
        self.slot_rid[slot] = req.rid
        self.slot_deadline[slot] = req.deadline
        self.stats["admitted"] += 1

    def _admit_paged(self, slot: int, req: Request, reserve: int, match):
        """Paged admission: map the fully-matched shared prefix pages
        read-only (refcount +1, no credit), allocate unique pages for
        the rest, prefill only the un-cached suffix (gathered through a
        fresh dense cache), scatter the suffix rows, and publish the
        prompt's pages into the trie."""
        ps = self.page_size
        plen = req.prompt.shape[0]
        assert plen <= self.max_len
        npf = -(-plen // ps)
        pages_m, L, f = match if match is not None else ([], 0, 0)

        self._pages_np[slot, :] = -1
        for j in range(f):                      # shared prefix, read-only
            p = int(pages_m[j])
            self._pages_np[slot, j] = p
            self._page_refs[p] += 1
        for j in range(f, npf):                 # private suffix pages
            self._pages_np[slot, j] = self._take_page()
        self._slot_npages[slot] = npf
        self._slot_reserve[slot] = reserve
        self._slot_unique[slot] = npf - f
        self._committed += reserve - (npf - f)
        self._index_np[slot] = plen

        if L > 0:
            # gather every page with matched rows — including a
            # partially-matched boundary page, used as a read source
            # only (never mapped) — then extend from row L
            n_m = -(-L // ps)
            pids_m = jnp.asarray([int(p) for p in pages_m[:n_m]], jnp.int32)
            with self._first_call("gather_prefix", n_m):
                gathered = self._gather_fn(n_m)(self.cache["units"], pids_m)
            with self._first_call("prefill_extend", (L, plen - L)):
                logits, pcache = prefill_extend_cached(
                    self.cfg, self.params, gathered,
                    jnp.asarray(req.prompt)[None, L:], start=L,
                    use_kernels=self.use_kernels)
            self.stats["prefix_hits"] += 1
            self.stats["prefill_tokens_saved"] += L
        else:
            with self._first_call("prefill", plen):
                logits, pcache = self._prefill_fn(plen)(
                    self.params, jnp.asarray(req.prompt)[None, :])
            if self.prefix_share:
                self.stats["prefix_misses"] += 1
        if self.prefix_share:
            self.stats["prompt_tokens_total"] += plen
        pids = [int(p) for p in self._pages_np[slot, f:npf]]
        self.cache["units"] = self._scatter_paged(
            pcache["units"], pids, slot, first_page=f)
        if self.prefix_share:
            for p in self._trie.insert(
                    req.prompt, [int(x) for x in self._pages_np[slot, :npf]]):
                self._page_refs[p] += 1
            self._trim_trie()
        return logits

    def _scatter_paged(self, punits, pids: List[int], slot: int, *,
                       first_page: int = 0):
        """Scatter a solo prefill cache into the paged engine cache: pool
        leaves take the prompt's rows page by page starting at prompt
        page ``first_page`` (shared prefix pages before it are already
        populated); per-slot leaves (SSM state, whisper cross K/V)
        scatter into the slot axis as in the dense engine."""
        n = len(pids)
        key = ("scatter", n)
        fn = self._prefill_fns.get(key)
        if fn is None:
            ps, is_pool = self.page_size, self._is_pool

            def scatter_pages(units, punits, pids_a, lo, slot):
                def put(dst, src, is_pool):
                    if not is_pool:
                        return _scatter_slot_leaf(dst, src, slot)
                    u = src.shape[0]           # src: (U, 1, max_len, H, D)
                    rows = jax.lax.dynamic_slice_in_dim(
                        src[:, 0], lo, n * ps, axis=1)
                    rows = rows.reshape((u, n, ps) + dst.shape[3:])
                    return dst.at[:, pids_a].set(rows.astype(dst.dtype))
                return jax.tree.map(put, units, punits, is_pool)
            # donated: the pool is written in place, never copied
            fn = self._prefill_fns[key] = jax.jit(scatter_pages,
                                                  donate_argnums=(0,))
        with self._first_call("scatter_pages", n):
            return fn(self.cache["units"], punits,
                      jnp.asarray(pids, jnp.int32),
                      first_page * self.page_size, slot)

    def _fork_page(self, slot: int, j: int) -> None:
        """Copy-on-write: give ``slot`` a private copy of block-table
        entry ``j`` before it writes into a still-shared page.  Only the
        boundary page of a freshly-published prompt can hit this, and
        its admission pre-charged the fork allowance."""
        old = int(self._pages_np[slot, j])
        new = self._take_page()                 # refs[new] = 1
        self._page_refs[old] -= 1
        fn = self._prefill_fns.get("fork")
        if fn is None:
            is_pool = self._is_pool

            def fork_page(units, new, old):
                return jax.tree.map(
                    lambda leaf, pl: leaf.at[:, new].set(leaf[:, old])
                    if pl else leaf, units, is_pool)
            fn = self._prefill_fns["fork"] = jax.jit(fork_page,
                                                     donate_argnums=(0,))
        with self._first_call("fork_page", ()):
            self.cache["units"] = fn(self.cache["units"], new, old)
        self._pages_np[slot, j] = new
        self._slot_unique[slot] += 1
        self._committed -= 1
        self.stats["cow_forks"] += 1

    def _grow(self) -> None:
        """Assign pool pages covering the upcoming segment for every
        active slot — lazy assignment against the admission reservation,
        so a slot only ever holds pages for rows it is about to write.
        With prefix sharing, any still-shared page the segment will
        write into is copy-on-write forked first."""
        ps = self.page_size
        for slot in range(self.n_slots):
            if not self.active[slot]:
                continue
            idx = int(self._index_np[slot])
            pend = -(-(idx + self.segment) // ps)
            if self.prefix_share:
                for j in range(idx // ps, min(pend,
                                              int(self._slot_npages[slot]))):
                    if self._page_refs[int(self._pages_np[slot, j])] > 1:
                        self._fork_page(slot, j)
            while self._slot_npages[slot] < pend:
                self._pages_np[slot, self._slot_npages[slot]] = \
                    self._take_page()
                self._slot_npages[slot] += 1
                self._slot_unique[slot] += 1
                self._committed -= 1

    def step_segment(self) -> None:
        """One fused scan segment + post-segment bookkeeping/admission.
        Degraded-mode pre-pass: expired requests shed (queued and
        active) and the queue brown-outs before admission refills the
        freed slots.

        Spans: ``engine.step_segment`` holds ``engine.shed``, one
        ``engine.admit`` per admission, ``engine.grow`` (page growth,
        copy-on-write forks, the block-table push), ``engine.dispatch``,
        ``engine.wait`` (until the tokens are on the host) and
        ``engine.bookkeep`` (outputs, frees, stats)."""
        with spans.span("engine.step_segment",
                        segment=self.stats["segments"]):
            with spans.span("engine.shed"):
                self._shed_expired(self._clock())
                self._brownout()
            self._admit()
            with spans.span("engine.grow"):
                self._grow_and_push()
            self._decode()

    def _grow_and_push(self) -> None:
        if self.paged:
            self._grow()
            # one host->device push of the (n_slots, P) block table per
            # segment covers admissions, growth, and last-segment frees
            self.cache["pages"] = jnp.asarray(self._pages_np)
            in_use = int(self._slot_npages.sum())
            self.stats["pages_in_use"] = in_use
            self.stats["peak_pages_in_use"] = max(
                self.stats["peak_pages_in_use"], in_use)
            rows = int((self._index_np[self.active] + self.segment).sum())
            occ = rows / (in_use * self.page_size) if in_use else 0.0
            self.stats["page_occupancy"] = occ
            self.stats["page_fragmentation"] = 1.0 - occ
            if self.prefix_share:
                refs = self._page_refs
                self.stats["shared_pages"] = int((refs > 1).sum())
                self.stats["unique_pages"] = int((refs == 1).sum())
                self.stats["trie_pages"] = self._trie.page_count()
                h, m = self.stats["prefix_hits"], self.stats["prefix_misses"]
                self.stats["prefix_hit_rate"] = h / (h + m) if h + m else 0.0
            if self.debug:
                self._check_invariants()
        self.stats["peak_active_slots"] = max(
            self.stats["peak_active_slots"], int(self.active.sum()))

    def _decode(self) -> None:
        """Run the segment, wait for its tokens, and hand them out."""
        def attempt():
            # faults strike before the call, while the donated cache is
            # still intact, so a retried segment replays the identical
            # computation
            if self.fault_injector is not None:
                self.fault_injector.check(self.stats["segments"],
                                          ("segment",))
            return self._segment_fn(self.params, self.cache, self.tok)

        with spans.span("engine.dispatch"):
            t0 = time.perf_counter()
            with self._first_call("decode_segment", ()):
                toks, self.cache, self.tok = retry_call(
                    attempt, policy=self.retry_policy,
                    retry_on=(TransientFault,), rng=self._retry_rng,
                    sleep=self._sleep,
                    on_retry=lambda *_: self.stats.__setitem__(
                        "retries", self.stats["retries"] + 1))
        with spans.span("engine.wait"):
            toks = np.asarray(toks)                 # (n_slots, segment)
            dt = time.perf_counter() - t0
        # the segment's time runs from dispatch to its tokens on the host
        self._seg_ewma = (dt if self._seg_ewma == 0.0
                          else 0.2 * dt + 0.8 * self._seg_ewma)
        with spans.span("engine.bookkeep"):
            self._hand_out(toks)

    def _hand_out(self, toks: np.ndarray) -> None:
        self.stats["segments"] += 1
        self.stats["wasted_slot_steps"] += int(
            (~self.active).sum()) * self.segment
        if self.paged:
            self._count_kv_blocks()
        for slot in range(self.n_slots):
            if not self.active[slot]:
                continue
            if self.paged:
                self._index_np[slot] += self.segment
            take = int(min(self.segment, self.remaining[slot]))
            self.outputs[self.slot_rid[slot]].extend(
                int(t) for t in toks[slot, :take])
            self.remaining[slot] -= take
            self.stats["wasted_slot_steps"] += self.segment - take
            if self.remaining[slot] == 0:
                dl = self.slot_deadline[slot]
                if dl is not None and self._clock() > dl:
                    # completed, delivered — but late
                    self.stats["deadline_miss"] += 1
                self._free_slot(slot)               # slot freed for reuse

    def _count_kv_blocks(self) -> None:
        """The paged kernel's grid steps in the segment just run: every
        slot's, and those whose rows hold a live position of an active
        slot (at its t-th step a slot attends to ``index + t`` rows).  A
        freed slot's steps are issued all the same: its device length
        runs on from where it stopped."""
        self.stats["kv_blocks_issued"] += (
            self.n_slots * self._kv_steps * self.segment * self._kv_layers)
        rows = (self._index_np[self.active][:, None]
                + np.arange(1, self.segment + 1))
        live = np.minimum(-(-rows // self._kv_rows), self._kv_steps)
        self.stats["kv_blocks_live"] += int(live.sum()) * self._kv_layers

    def _free_slot_pages(self, slot: int) -> None:
        """Reclaim a freed slot's pages and reservation.  Each mapped
        page is dereferenced and returns to the free list only at
        refcount 0 — shared prefix pages outlive the slot through their
        other holders (the trie, sibling slots).  The block table row is
        cleared to the -1 sentinel immediately (pushed to the device
        before the next segment), so the stale slot's continued writes
        drop instead of corrupting whoever gets the pages next."""
        npg = int(self._slot_npages[slot])
        for p in self._pages_np[slot, :npg]:
            p = int(p)
            self._page_refs[p] -= 1
            if self._page_refs[p] == 0:
                self._free_pages.append(p)
        self._pages_np[slot, :] = -1
        self._slot_npages[slot] = 0
        self._committed -= (int(self._slot_reserve[slot])
                            - int(self._slot_unique[slot]))
        self._slot_reserve[slot] = 0
        self._slot_unique[slot] = 0
        self._index_np[slot] = 0
        if self.prefix_share:
            self._trim_trie()

    def _trim_trie(self) -> None:
        """LRU-trim cached prefixes down to the ``retain_pages``
        watermark: pages held only by the trie are evicted oldest-first
        until the evictable set fits."""
        while self._trie.evictable_pages(self._page_refs) > self.retain_pages:
            page = self._trie.evict_lru(self._page_refs)
            if page is None:
                break
            self._page_refs[page] -= 1
            self._free_pages.append(page)
            self.stats["prefix_evictions"] += 1

    def _check_invariants(self) -> None:
        """Debug-mode structural audit of the paging state (the
        refcount/free-list/credit contract of DESIGN.md §15/§18)."""
        refs = self._page_refs
        mapped = 0
        for slot in range(self.n_slots):
            npg = int(self._slot_npages[slot])
            row = self._pages_np[slot]
            assert (row[npg:] == -1).all(), \
                f"slot {slot}: mapped entries past npages"
            assert (row[:npg] >= 0).all(), \
                f"slot {slot}: -1 sentinel read inside mapped range"
            mapped += npg
            if self.active[slot]:
                need = -(-(int(self._index_np[slot]) + self.segment)
                         // self.page_size)
                assert npg >= need, f"slot {slot}: segment pages unmapped"
                for p in row[:npg]:
                    assert refs[int(p)] >= 1, f"slot {slot}: freed page {p}"
        trie_pages = self._trie.page_count() if self.prefix_share else 0
        assert int(refs.sum()) == mapped + trie_pages, \
            "refcounts out of sync with block tables + trie"
        assert len(set(self._free_pages)) == len(self._free_pages), \
            "duplicate page on free list"
        for p in self._free_pages:
            assert refs[p] == 0, f"page {p} both free and referenced"
        assert (refs >= 0).all(), "negative refcount"
        assert len(self._free_pages) + int((refs > 0).sum()) == self.n_pages, \
            "page leak: free + referenced != total"
        assert self._committed == int(
            (self._slot_reserve - self._slot_unique).sum()) >= 0, \
            "reservation credit out of sync"

    def run(self) -> Dict[int, List[int]]:
        """Drain the queue and all active slots; returns {rid: tokens}."""
        while self.queue or self.active.any():
            self.step_segment()
        return self.outputs


# ---------------------------------------------------------------------- #
def _has_linear_kv(cfg) -> bool:
    """True if decode writes one linear KV-cache row per absolute
    position (so prompt + generation must fit in max_len).  Ring buffers
    (sliding window) wrap and SSM/xLSTM state is O(1)."""
    if cfg.sliding_window > 0:
        return False
    return cfg.family in ("dense", "vlm", "moe", "audio") or (
        cfg.family == "hybrid" and cfg.attn_every > 0)


def _scatter_slot_leaf(dst, src, slot: int):
    """Write one batch-1 cache leaf into slot ``slot`` of a
    batch-``n_slots`` leaf.  The slot (batch) axis position varies per
    leaf ((U, B, ...) for KV, (U, u, B, ...) for stacked SSM layers), so
    it is identified as the one axis where the shapes differ."""
    ax = None
    for i, (a, b) in enumerate(zip(dst.shape, src.shape)):
        if a != b:
            ax = i
            break
    if ax is None:                  # n_slots == 1: plain replacement
        return src.astype(dst.dtype)
    idx = (slice(None),) * ax + (slot,)
    return dst.at[idx].set(jnp.squeeze(src, axis=ax).astype(dst.dtype))


def _scatter_slot(dst_tree, src_tree, slot: int):
    """Tree-wide ``_scatter_slot_leaf``."""
    return jax.tree.map(lambda d, s: _scatter_slot_leaf(d, s, slot),
                        dst_tree, src_tree)
