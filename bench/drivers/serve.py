"""Serving cells: ``DecodeEngine`` (paged, prefix-shared, Pallas kernels)
under the traffic file's requests.

Set-up makes the weights from the seed, builds the engine as the
workload file states it, and warms every shape the traffic can ask for:
each whole-prompt prefill (a miss) and each system-prompt-plus-suffix
extend (a hit), through the engine's own ``submit`` and ``step_segment``.

The window: requests are submitted when their arrival time has passed
(an open loop; a backlog arrives at once), and ``step_segment`` runs
while any request is queued or decoding.  A token's time is the moment
``step_segment`` returns it.  Time to first token runs from a request's
scheduled arrival; after the window the engine runs on, without new
requests, until every request due in the window has its first token, a
minute at most.

Check: a sample of finished requests drawn from the seed, with the
longest among them, is run through ``bench.reference`` in float32 over
its prompt and served tokens; ``max_regret`` is the widest gap by which a
served token's reference logit lies below the reference's best at that
position.  The first served token comes from the prefill (whole, or the
extend of a hit), the rest from the paged decode.
"""
from __future__ import annotations

import gc
import time
from typing import Any, Dict, List

import numpy as np

from bench import reference as ref
from bench import traffic
from bench.harness import memory_peak_bytes, percentile, say
from bench.programs import program_config

DRAIN_S = 60.0


def warm(eng, shapes, vocab: int, rng, avoid) -> int:
    """Make the engine compile every shape the traffic can ask for,
    through ``submit`` and ``run`` with one new token each: a miss of
    each whole-prompt length, then each system prompt once (a miss), then
    each (system, own) pair as a hit.  Warm-up prompts start with tokens
    that no request starts a system prompt or its own text with, so the
    prefix index never matches them.  Returns the prompts served."""
    free = np.setdiff1d(np.arange(vocab), np.asarray(sorted(avoid)))
    firsts = iter(rng.choice(free, size=2 * (len(shapes["miss"])
                                             + len(shapes["hit"])) + 8,
                             replace=False))

    def fresh(n):
        t = rng.integers(0, vocab, n, dtype=np.int64)
        t[0] = next(firsts)
        return t.astype(np.int32)
    systems = {s: fresh(s) for s, _ in shapes["hit"]}
    shortest = {s: min(o for t, o in shapes["hit"] if t == s)
                for s in systems}
    rounds = [[fresh(n) for n in sorted(shapes["miss"])],
              [np.concatenate([p, fresh(shortest[s])])
               for s, p in systems.items()],
              [np.concatenate([systems[s], fresh(o)])
               for s, o in sorted(shapes["hit"])]]
    for prompts in rounds:
        for p in prompts:
            eng.submit(p, 1)
        eng.run()
    return sum(len(r) for r in rounds)


def run(h) -> Dict[str, Any]:
    import jax
    from repro.launch.engine import DecodeEngine
    from repro.models import init_params as program_init

    wl, tr = h.cell.workload, h.cell.traffic
    conf = h.cell.config
    dims = ref.Dims.of(conf)
    cfg = program_config(conf)
    eng_kw = wl["engine"]
    params = ref.init_params(dims, h.seed)
    want = jax.eval_shape(lambda: program_init(cfg, jax.random.PRNGKey(0)))
    if jax.tree.structure(want) != jax.tree.structure(params):
        raise SystemExit("bench: the benchmark's weights do not match the "
                         "program's parameter tree")
    eng = DecodeEngine(cfg, params, use_kernels=True, paged=True,
                       prefix_share=True, **eng_kw)
    reqs = traffic.generate(tr, h.seed, dims.vocab, h.window_seconds)
    shapes = traffic.prompt_shapes(tr)
    avoid = {int(r.tokens[k]) for r in reqs for k in (0, r.shared)}
    n_warm = warm(eng, shapes, dims.vocab,
                  np.random.default_rng([h.seed, 7]), avoid)
    jax.block_until_ready(eng.cache)
    stats0 = dict(eng.stats)
    setup_s = h.setup_done()
    say(f"serve: {len(reqs)} requests drawn; warmed {n_warm} prompts; "
        f"set-up {setup_s:.2f} s")

    # ---- window ----
    n = len(reqs)
    rid_of: List[int] = [-1] * n
    first_t = np.full(n, np.inf)
    last_t = np.full(n, np.inf)
    submit_t = np.full(n, np.nan)
    got = np.zeros(n, int)
    in_window = np.zeros(n, int)             # tokens received in window
    seg_s: List[float] = []
    nxt = 0
    by_rid: Dict[int, int] = {}
    end = h.window_seconds

    def submit_due(now):
        nonlocal nxt
        while nxt < n and reqs[nxt].arrival <= now:
            r = reqs[nxt]
            with h.span("bench.submit"):
                rid = eng.submit(r.tokens, r.new_tokens)
            rid_of[nxt], by_rid[rid] = rid, nxt
            submit_t[nxt] = now
            nxt += 1

    start_of = np.zeros(n, int)              # matched rows at admission
    track = h.trace and "tenants" in tr

    def segment(t0, window_open):
        if track:
            for rid in [q.rid for q in eng.queue]:
                i = by_rid[rid]
                _, m = eng._trie.match(reqs[i].tokens, touch=False)
                start_of[i] = min(m, len(reqs[i].tokens) - 2)
        with h.span("bench.step_segment"):
            s0 = time.perf_counter()
            eng.step_segment()
            s1 = time.perf_counter()
        seg_s.append(s1 - s0)
        now = s1 - t0
        for rid, i in list(by_rid.items()):
            k = len(eng.outputs[rid])
            if k > got[i]:
                if got[i] == 0:
                    first_t[i] = now
                if window_open:
                    in_window[i] += k - got[i]
                got[i] = k
                if k >= reqs[i].new_tokens:
                    last_t[i] = now
                    del by_rid[rid]
        return now

    with h.window():
        t0 = time.perf_counter()
        now = 0.0
        while now < end:
            submit_due(now)
            if eng.queue or eng.active.any():
                now = segment(t0, True)
            else:
                nxt_t = reqs[nxt].arrival if nxt < n else end
                time.sleep(max(0.0, min(nxt_t, end) - now))
                now = time.perf_counter() - t0
    t_win0, t_win1 = h.window_span
    window = t_win1 - t_win0
    stats1 = dict(eng.stats)
    segs_window = len(seg_s)
    # ---- drain: due requests get their first token ----
    # (a backlog is more than the window can drain by design: its
    # requests are not due, and only the ones served are counted)
    due = np.array([r.arrival < window for r in reqs])
    due &= (np.arange(n) < nxt) & (tr["kind"] == "open")
    t_drain = time.perf_counter()
    while ((np.isinf(first_t) & due).any()
           and time.perf_counter() - t_drain < DRAIN_S):
        segment(t0, False)
    peak = memory_peak_bytes()

    arrival = np.array([r.arrival for r in reqs])
    ttft, tpot = latencies(arrival, first_t, last_t,
                           np.array([r.new_tokens for r in reqs]), due,
                           window)
    done_in_window = np.isfinite(last_t) & (last_t <= window)
    late = (submit_t - arrival)[:nxt]
    e2e = {"serve_tokens_per_s": float(in_window.sum() / window)}
    if tr["kind"] == "open":
        e2e["ttft_p90_ms"] = 1e3 * percentile(list(ttft), 90)
        e2e["tpot_p90_ms"] = 1e3 * percentile(tpot, 90)
    saved = stats1["prefill_tokens_saved"] - stats0["prefill_tokens_saved"]
    prompt = stats1["prompt_tokens_total"] - stats0["prompt_tokens_total"]
    say(f"serve: window {window:.3f} s, {segs_window} segments, "
        f"{int(due.sum())} requests due, {int(done_in_window.sum())} "
        f"completed, {int(in_window.sum())} tokens; ttft p50 "
        f"{1e3 * percentile(list(ttft), 50):.1f} ms; generator late mean "
        f"{1e3 * float(np.mean(late)):.1f} ms max "
        f"{1e3 * float(np.max(late)):.1f} ms; backlog at close "
        f"{len(eng.queue)} queued, {int(eng.active.sum())} active")
    say(f"serve: engine stats over the window "
        f"{ {k: stats1[k] - stats0[k] for k in stats1 if isinstance(stats1[k], int) and k in stats0} }")

    # decode and prefill work received in the window, for the readers:
    # the i-th token of a request (i >= 1) came from a decode step that
    # attends to plen + i rows
    decode_lengths: List[int] = []
    prefills: List[tuple] = []
    for i in range(nxt):
        plen = len(reqs[i].tokens)
        decode_lengths.extend(plen + k for k in range(1, in_window[i]))
        if in_window[i]:
            prefills.append((int(start_of[i]), plen))
    counters = {"segment_s": seg_s[:segs_window], "window_s": window,
                "prefill_tokens_saved": saved, "prompt_tokens_total": prompt,
                "decode_lengths": decode_lengths, "prefills": prefills,
                "requests_due": int(due.sum()),
                "requests_completed": int(done_in_window.sum()),
                "generator_late_mean_s": float(np.mean(late)),
                "generator_late_max_s": float(np.max(late)),
                "window_compiles": h.window_compiles}

    # ---- check ----
    finished = [i for i in range(nxt) if got[i] >= reqs[i].new_tokens]
    pick = sample(finished, reqs, h.seed, wl["check"])
    seqs = [np.concatenate([reqs[i].tokens,
                            eng.outputs[rid_of[i]][:reqs[i].new_tokens]])
            for i in pick]
    starts = [len(reqs[i].tokens) for i in pick]
    max_len = eng.max_len
    del eng, params
    gc.collect()
    precisions = ["float32"] + (["fp8"] if h.calibrate else [])
    t_ref = time.perf_counter()
    out = ref.served_logits(dims, h.seed, seqs, starts, max_len, precisions)
    r32 = out["float32"]
    regret = max(float(np.max(r["best"] - r["served"])) for r in r32)
    say(f"serve: reference over {len(pick)} requests, "
        f"{sum(len(s) - st for s, st in zip(seqs, starts))} served tokens, "
        f"{time.perf_counter() - t_ref:.1f} s")
    calibration = {}
    if h.calibrate:
        calibration["control_fp8.max_regret"] = max(
            float(np.max(r["best"] - r["fp8_argmax_logit"])) for r in r32)
    failed = int((np.isinf(first_t) & due).sum())
    return {"setup_s": setup_s, "end_to_end": e2e,
            "attempted": int(due.sum()) if tr["kind"] == "open"
            else int((in_window > 0).sum()),
            "failed": failed, "correct": len(pick) > 0,
            "checks": {"max_regret": {"value": regret,
                                      "limit": wl["limits"]["max_regret"]}},
            "calibration": calibration, "memory_peak_bytes": peak,
            "counters": counters}


def latencies(arrival, first_t, last_t, new_tokens, due, window):
    """Open-loop latencies, in seconds.  Time to first token runs from
    each due request's scheduled ``arrival`` (not from when it was
    submitted), ``inf`` where none came; time per output token is
    (last - first) / (n - 1) over requests completed inside the window."""
    ttft = list((first_t - arrival)[due])
    done = np.isfinite(last_t) & (last_t <= window)
    tpot = list((last_t[done] - first_t[done]) / (new_tokens[done] - 1))
    return ttft, tpot


def sample(finished: List[int], reqs, seed: int, spec: Dict[str, Any]
           ) -> List[int]:
    """The longest finished request, then others drawn from the seed
    until ``tokens`` served tokens or ``max_requests`` requests."""
    if not finished:
        return []
    longest = max(finished, key=lambda i: (len(reqs[i].tokens)
                                           + reqs[i].new_tokens, i))
    rng = np.random.default_rng([seed, 11])
    rest = [i for i in rng.permutation(finished) if i != longest]
    pick, total = [longest], reqs[longest].new_tokens
    for i in rest:
        if total >= spec["tokens"] or len(pick) >= spec["max_requests"]:
            break
        pick.append(int(i))
        total += reqs[i].new_tokens
    return pick
