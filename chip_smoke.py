"""Smoke run of the system's two device paths on one TPU, at published
widths, through the entry points a user calls.

    python chip_smoke.py            # serving + sharing phases, one chip
    python chip_smoke.py --fleet4   # fleet phase only: four agents, one
                                    # per chip, against one executor

Serving phase: ``DecodeEngine(paged=True, prefix_share=True,
use_kernels=True)`` on minicpm-2b exactly as ``configs/minicpm_2b.py``
states it (40 layers, d_model 2304, 36 MHA heads, vocab 122753, bf16),
random weights from ``--seed``, with a page pool a deployment would hold.
Requests share prompt prefixes so the radix trie gets hits.  Checks:
one request's prefill-then-decode logits, as a miss and as a hit,
against ``models.forward`` in float32 at highest matmul precision; every
served token against the same reference, teacher-forced on the served
sequence; miss requests bitwise the ``prefix_share=False`` engine's;
two all-hit passes identical.  A hit with its prefix rows zeroed is a
control that the logits and token checks must reject.

Sharing phase (the paper's mechanism): a two-job SJF-BSBF schedule is
simulated with ``paper_interference_model()``; the second job shares the
first job's device with a sub-batch and gradient accumulation, the donor
is reconfigured at the sharing point and restored when the sharer
leaves.  ``plan_from_sim`` turns the log into a plan that
``ScheduleExecutor`` runs with minicpm-2b and qwen2-vl-2b at published
widths, depth cut to whole layers so both jobs' params, Adam moments and
gradient buffers fit on the chip side by side.  Check: each member's
per-step loss and final params against the same job run alone through
the executor; the sharer with one accumulation microbatch dropped is a
control that the params check must reject.

Every kernel call must resolve to a native Pallas kernel: a reference
route or the interpreter fails the run.  The last line of standard
output is ``{"ok": true, "device": {...}}``, printed only when every
phase passed; without a TPU the script exits non-zero before any phase.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import gc
import json
import os
import subprocess
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

SERVE_ARCH = "minicpm-2b"
SHARE_ARCHS = ("minicpm-2b", "qwen2-vl-2b")
SHARE_LAYERS = 2          # depth each sharing job keeps (whole layers)
# One request's logits against the float32 reference: relative L2 error
# per position.  bf16 activations round at 2**-8 (3.9e-3) relative; the
# residual stream passes ~10 roundings per layer, which add up like a
# random walk over 40 layers: sqrt(400) * 3.9e-3 ~= 0.08.  A hit whose
# prefix rows were lost must exceed it (checked in every run).
LOGIT_TOL = 0.1
# A fused group member's final params against the same job run alone,
# per leaf: ||group - solo|| / ||solo - init||.  Rounding differences
# flip the last bit of a few percent of the bf16 elements, a few
# hundredths of the update; a wrong update moves most elements (a dropped
# accumulation microbatch flips the sign of ~1/4 of the first Adam
# steps, ~1 of the update).  A dropped microbatch must exceed it
# (checked in every run).
UPDATE_TOL = 0.25


def say(msg: str) -> None:
    print(msg, flush=True)


def device_info() -> dict:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def require_tpu() -> dict:
    info = device_info()
    if info["platform"] != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU, JAX found "
                         f"{info['platform']!r} ({info['kind']})")
    return info


def _peak_bytes():
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use"), stats.get("bytes_in_use")


def _check_routes(routes, failures: list, phase: str) -> dict:
    """Report which implementation each kernel call resolved to; any
    reference route or interpreter on the chip is a failure."""
    import jax
    routes = {k: sorted(v) for k, v in sorted(routes.items())}
    say(f"{phase}: kernels resolved to {json.dumps(routes)}")
    if jax.default_backend() == "tpu":
        bad = {k: v for k, v in routes.items() if v != ["pallas"]}
        if bad:
            failures.append(f"{phase}: kernels not native: {bad}")
    return routes


def _recorded(phase: str):
    """Run a phase inside ``ops.recording()`` and add its kernel routes
    (and any non-native route as a failure) to the phase's result."""
    def wrap(fn):
        @functools.wraps(fn)
        def run(*args, **kwargs):
            from repro.kernels import ops
            with ops.recording() as routes:
                out = fn(*args, **kwargs)
            out["kernels"] = _check_routes(routes, out["failures"], phase)
            return out
        return run
    return wrap


# ---------------------------------------------------------------------- #
# serving
# ---------------------------------------------------------------------- #
def _probe_logits(cfg, params, prompt, fed, *, start, max_len, page_size):
    """Logits of one request through the engine's own model entry points,
    three ways, each followed by decode steps that feed the tokens
    ``fed`` through the paged kernel on an identity block table: a miss
    (kernel prefill of the whole prompt), a prefix hit (rows ``[0,
    start)`` taken from the miss's cache, kernel suffix extend from
    ``start``) and a control the checks must reject: a hit whose prefix
    rows are zero.  Returns (miss (plen + len(fed), V), hit, zeroed
    (plen - start + len(fed), V) each) as float32 host arrays."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import (decode_step, init_cache, init_paged_cache,
                              prefill, prefill_extend)
    plen = len(prompt)
    toks = jnp.asarray(prompt, jnp.int32)[None, :]
    logits, dense = jax.jit(lambda p, t: prefill(
        cfg, p, init_cache(cfg, 1, max_len), t, use_kernels=True))(
            params, toks)
    extend = jax.jit(lambda p, c, t: prefill_extend(
        cfg, p, c, t, start=start, use_kernels=True))
    hit = extend(params, dict(dense, units=jax.tree.map(
        lambda d: jnp.zeros_like(d).at[:, :, :start].set(d[:, :, :start]),
        dense["units"])), toks[:, start:])
    zeroed = extend(params, dict(dense, units=jax.tree.map(
        jnp.zeros_like, dense["units"])), toks[:, start:])
    n_tab = max_len // page_size
    step = jax.jit(lambda p, c, t: decode_step(cfg, p, c, t,
                                               use_kernels=True))

    def decode(first, dense_cache):
        cache = init_paged_cache(cfg, 1, max_len, page_size=page_size,
                                 n_pages=n_tab)
        # identity block table: page j holds rows [j*ps, (j+1)*ps)
        cache["units"] = jax.tree.map(
            lambda d: d.reshape(d.shape[0], n_tab, page_size, -1),
            dense_cache["units"])
        cache["pages"] = jnp.arange(n_tab, dtype=jnp.int32)[None, :]
        cache["index"] = jnp.full((1,), plen, jnp.int32)
        rows = [np.asarray(first[0].astype(jnp.float32))]
        for t in fed:
            lg, cache = step(params, cache, jnp.full((1, 1), t, jnp.int32))
            rows.append(np.asarray(lg[0].astype(jnp.float32)))
        return np.concatenate(rows)

    out = [decode(logits, dense)]
    del dense
    for pair in (hit, zeroed):
        out.append(decode(*pair))
    return tuple(out)


def _reference(cfg, host_params, seqs, plen, probe_rows):
    """``models.forward`` in float32 at highest matmul precision, from a
    host copy of the weights (the bf16 device copy must be gone: both
    would not fit on one chip), over each sequence of prompt + served
    tokens, teacher-forced; every prompt has ``plen`` tokens.  Returns
    ({sequence: (top-1, top-2, served token) reference logits at each
    served position}, rows ``[0, probe_rows)`` of ``seqs[0]``)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import forward
    cfg32 = dataclasses.replace(cfg, dtype="float32")
    params32 = jax.tree.map(lambda a: jnp.asarray(np.asarray(a, np.float32)),
                            host_params)
    total = len(seqs[0])
    fwd = jax.jit(lambda p, t: forward(cfg32, p, {"tokens": t},
                                       remat=False)[0][0])

    @jax.jit
    def served(logits, chosen):
        rows = logits[plen - 1:]          # the row before each served token
        top = jax.lax.top_k(rows, 2)[0]
        pick = jnp.take_along_axis(rows, chosen[:, None], axis=1)[:, 0]
        return top[:, 0], top[:, 1], pick

    out, probe = {}, None
    with jax.default_matmul_precision("highest"):
        for seq in seqs:
            assert len(seq) == total, (len(seq), total)
            if seq in out:
                continue
            toks = jnp.asarray(seq, jnp.int32)
            logits = fwd(params32, toks[None, :-1])
            out[seq] = tuple(np.asarray(a) for a in served(logits,
                                                           toks[plen:]))
            if probe is None:
                probe = np.asarray(logits[:probe_rows])
    return out, probe


def _compare_logits(got, want):
    """Per-position error of ``got`` against the matching (last
    ``len(got)``) rows of the reference ``want``."""
    import numpy as np
    want = want[-got.shape[0]:]
    rel = (np.linalg.norm(got - want, axis=-1)
           / np.maximum(np.linalg.norm(want, axis=-1), 1e-30))
    regret = want.max(-1) - np.take_along_axis(
        want, got.argmax(-1)[:, None], axis=-1)[:, 0]
    return {"positions": int(got.shape[0]),
            "max_rel_err": float(rel.max()),
            "mean_rel_err": float(rel.mean()),
            "max_abs_err": float(np.abs(got - want).max()),
            "top1_agreement": float(np.mean(got.argmax(-1)
                                            == want.argmax(-1))),
            "max_regret": float(regret.max())}


def _divergence(a, b) -> dict:
    """Requests whose token lists differ, with the first differing
    position of each."""
    first = {}
    for i, (x, y) in enumerate(zip(a, b)):
        if x != y:
            first[i] = next(j for j, (u, v) in enumerate(zip(x, y))
                            if u != v)
    return {"requests_differing": len(first), "first_diff_at": first}


@_recorded("serving")
def serving_phase(cfg, *, n_slots=8, max_len=2048, page_size=16,
                  n_pages=512, segment=16, n_requests=12, shared_len=256,
                  suffix_len=32, new_tokens=32, probe_tokens=4, seed=0,
                  logit_tol=LOGIT_TOL) -> dict:
    import jax
    import numpy as np

    from repro.launch.engine import DecodeEngine
    from repro.models import init_params, param_count
    failures: list = []
    t0 = time.perf_counter()
    params = init_params(cfg, jax.random.PRNGKey(seed))
    jax.block_until_ready(params)
    n_params = param_count(params)
    say(f"serving: {cfg.name} layers={cfg.n_layers} d_model={cfg.d_model} "
        f"heads={cfg.n_heads}/{cfg.n_kv_heads} d_ff={cfg.d_ff} "
        f"vocab={cfg.vocab} dtype={cfg.dtype} params={n_params:,} "
        f"(init {time.perf_counter() - t0:.1f}s)")
    kv_row = 2 * cfg.n_layers * cfg.n_kv_heads * cfg.head_dim * 2
    say(f"serving: pool {n_pages} pages x {page_size} rows x {kv_row} B "
        f"= {n_pages * page_size * kv_row / 1e9:.2f} GB; {n_slots} slots, "
        f"max_len {max_len}, segment {segment}")
    rng = np.random.default_rng(seed)
    n_prefixes = 2
    prefixes = [rng.integers(0, cfg.vocab, shared_len)
                for _ in range(n_prefixes)]
    prompts = [np.concatenate([prefixes[i % n_prefixes],
                               rng.integers(0, cfg.vocab, suffix_len)])
               for i in range(n_requests)]
    plen = len(prompts[0])

    def serve(eng):
        rids = [eng.submit(p, new_tokens) for p in prompts]
        t = time.perf_counter()
        out = eng.run()
        return [out[r] for r in rids], time.perf_counter() - t

    kw = dict(n_slots=n_slots, max_len=max_len, segment=segment,
              use_kernels=True, paged=True, page_size=page_size,
              n_pages=n_pages)
    eng = DecodeEngine(cfg, params, prefix_share=True, **kw)
    first, t_first = serve(eng)              # compiles on first use
    misses = eng.stats["prefix_misses"]      # the first of each prefix
    again, t_again = serve(eng)              # whole prompts now hit
    third, t_third = serve(eng)              # same shapes: no compiles
    stats = dict(eng.stats)
    del eng
    gc.collect()
    tps = sum(len(t) for t in third) / t_third
    say(f"serving: {n_requests} requests x {new_tokens} tokens per pass: "
        f"{t_first:.2f}s, {t_again:.2f}s (compiles included), then "
        f"{t_third:.3f}s = {tps:.1f} tokens/s")
    say(f"serving: prefix hits {stats['prefix_hits']} misses "
        f"{stats['prefix_misses']} hit_rate {stats['prefix_hit_rate']:.3f} "
        f"prefill_tokens_saved {stats['prefill_tokens_saved']} cow_forks "
        f"{stats['cow_forks']} peak_pages_in_use "
        f"{stats['peak_pages_in_use']}/{n_pages}")
    if stats["prefix_hits"] <= 0:
        failures.append("serving: no prefix hits")
    if misses != n_prefixes:
        failures.append(f"serving: {misses} misses in the first pass, "
                        f"expected one per prefix ({n_prefixes})")
    if again != third:          # same shapes: must be deterministic
        failures.append("serving: two all-hit passes disagree")

    private = DecodeEngine(cfg, params, prefix_share=False, **kw)
    base, _ = serve(private)
    del private
    gc.collect()
    # A miss runs the private engine's programs at the same shapes, so
    # its tokens must match bitwise.  A hit computes its suffix with
    # matmuls of another row count, which XLA:TPU may round differently:
    # its tokens are held to the float32 reference below instead.
    tokens = {"vs_private": _divergence(first, base),
              "pass2_vs_private": _divergence(again, base),
              "pass1_vs_pass2": _divergence(first, again)}
    say(f"serving: token identity with the prefix_share=False engine and "
        f"across passes: {json.dumps(tokens)}")
    miss_ids = list(range(n_prefixes))
    miss_same = all(first[i] == base[i] for i in miss_ids)
    say(f"serving: miss requests {miss_ids} bitwise equal to the "
        f"prefix_share=False engine's: {miss_same}")
    if not miss_same:
        failures.append("serving: a miss request's tokens differ from the "
                        "prefix_share=False engine's")
    peak, in_use = _peak_bytes()
    say(f"serving: peak_bytes_in_use {peak} (bytes_in_use {in_use})")

    fed = base[0][:probe_tokens]
    probe = _probe_logits(cfg, params, prompts[0], fed, start=shared_len,
                          max_len=max_len, page_size=page_size)
    host = jax.device_get(params)
    del params
    gc.collect()
    engines = {"shared_pass1": first, "shared_pass2": again,
               "private": base}

    def key(r, out):
        return tuple(int(t) for t in prompts[r]) + tuple(out[r])
    seqs = [key(0, base)] + [key(r, out) for out in engines.values()
                             for r in range(n_requests)]
    ref, want = _reference(cfg, host, seqs, plen, plen + probe_tokens)
    del host
    gc.collect()
    check = {path: _compare_logits(got, want)
             for path, got in zip(("miss", "hit", "zeroed_prefix"), probe)}
    say(f"serving: logits vs float32 forward (highest precision), prompt "
        f"{plen} + {probe_tokens} decode steps, hit from row "
        f"{shared_len}: {json.dumps(check)} tol {logit_tol}")
    for path in ("miss", "hit"):
        if not check[path]["max_rel_err"] <= logit_tol:
            failures.append(f"serving: {path} logits rel err "
                            f"{check[path]['max_rel_err']:.4g} > "
                            f"{logit_tol}")

    # Served tokens.  Where each engine's logits are within e of the
    # reference's (e: the probe's largest absolute error), greedy picks a
    # token whose reference logit is within 2e of the reference's best:
    # its regret top1 - served <= 2e.  And two engines can first part
    # only where the reference's top-2 margin is <= 2e.
    bound = 2 * max(check["miss"]["max_abs_err"],
                    check["hit"]["max_abs_err"])
    regret = {name: max(float(np.max(ref[key(r, out)][0]
                                     - ref[key(r, out)][2]))
                        for r in range(n_requests))
              for name, out in engines.items()}
    near_tie = {}
    for name, div in (("shared_pass1", "vs_private"),
                      ("shared_pass2", "pass2_vs_private")):
        near_tie[name] = {
            r: float(ref[key(r, base)][0][j] - ref[key(r, base)][1][j])
            for r, j in tokens[div]["first_diff_at"].items()}
    say(f"serving: served tokens vs float32 reference (teacher-forced): "
        f"max regret {json.dumps(regret)}; reference top-2 margin where "
        f"each shared pass first parts from the private engine "
        f"{json.dumps(near_tie)}; bound 2e = {bound:.4g}")
    for name, r in regret.items():
        if not r <= bound:
            failures.append(f"serving: {name} served a token {r:.4g} "
                            f"below the reference's best (bound {bound:.4g})")
    for name, margins in near_tie.items():
        wide = {r: m for r, m in margins.items() if not m <= bound}
        if wide:
            failures.append(f"serving: {name} parts from the private "
                            f"engine where the reference's margin exceeds "
                            f"{bound:.4g}: {wide}")
    # the control: a hit that lost its prefix rows must fail both checks
    z = check["zeroed_prefix"]
    if not (z["max_rel_err"] > logit_tol and z["max_regret"] > bound):
        failures.append(f"serving: zeroed prefix rows pass the checks "
                        f"(rel err {z['max_rel_err']:.4g}, regret "
                        f"{z['max_regret']:.4g})")
    peak, _ = _peak_bytes()
    return {"tokens_per_s": tps, "stats": stats, "logits": check,
            "tokens": tokens, "regret": regret, "near_tie": near_tie,
            "bound": bound, "peak_bytes_in_use": peak,
            "failures": failures}


# ---------------------------------------------------------------------- #
# sharing: simulated schedule -> plan -> executor
# ---------------------------------------------------------------------- #
def sharing_plan(model_a: str, model_b: str, *, n_servers: int = 1,
                 batch: int = 2, iters_a: int = 6, iters_b: int = 2):
    """SJF-BSBF schedule on ``n_servers`` one-GPU servers: one donor of
    ``model_a`` per server from t=0, then one ``model_b`` sharer per
    donor.  The memory capacity admits a donor and a sharer only at half
    batch, so each sharer runs with gradient accumulation and its donor
    is reconfigured at the sharing point (and restored when the sharer
    finishes).  Returns (plan, names)."""
    from repro.core import ClusterState, Job, PerfParams, Simulator
    from repro.core.interference import paper_interference_model
    from repro.core.schedulers import SJF_BSBF
    from repro.launch.cluster import plan_from_sim
    gib = 2 ** 30

    def perf(beta):
        return PerfParams(alpha_comp=0.01, beta_comp=beta, alpha_comm=0.0,
                          beta_comm=0.0, msg_bytes=0.0, delta=2.0,
                          mem_base=4.0 * gib, mem_per_sample=0.25 * gib,
                          param_bytes=1e8, n_workers=1)
    pa, pb = perf(0.01), perf(0.008)
    t_a = pa.t_iter(batch)
    jobs, names = [], {}
    for i in range(n_servers):
        jobs.append(Job(jid=i, model=model_a, arrival=0.0, gpus=1,
                        iters=float(iters_a), batch=batch, perf=pa))
        names[i] = f"A{i}" if n_servers > 1 else "A"
    for i in range(n_servers):
        jid = n_servers + i
        jobs.append(Job(jid=jid, model=model_b, arrival=2 * t_a, gpus=1,
                        iters=float(iters_b), batch=batch, perf=pb))
        names[jid] = f"B{i}" if n_servers > 1 else "B"
    half = max(1, batch // 2)
    cap = pa.mem_bytes(half) + pb.mem_bytes(half) + 0.0625 * gib
    interference = paper_interference_model()
    sim = Simulator(ClusterState(n_servers=n_servers, gpus_per_server=1,
                                 gpu_capacity_bytes=cap),
                    jobs, SJF_BSBF(donor_reconfig=True),
                    interference=interference, reconfig_on_release=True)
    sim.run()
    plan = plan_from_sim(sim.log, sim.jobs, interference, cap, names=names)
    return plan, names


def _totals(plan) -> dict:
    out: dict = {}
    for phase in plan.phases:
        for name, q in phase.quotas:
            out[name] = out.get(name, 0) + q
    return out


def _solo_plan(plan, name: str):
    """The plan restricted to one job: same ops and step quotas, the job
    alone in its group."""
    from repro.launch.cluster import PlanPhase
    phases = []
    for ph in plan.phases:
        quotas = tuple((n, q) for n, q in ph.quotas if n == name)
        phases.append(PlanPhase(
            ops=tuple(op for op in ph.ops if op.job == name),
            quotas=quotas,
            groups=((name,),) if any(q > 0 for _, q in quotas) else ()))
    return phases


def cut_depth(cfg, layers: int):
    """Keep ``layers`` layers (whole pattern periods); widths unchanged."""
    unit = cfg.pattern_unit()
    return dataclasses.replace(cfg, n_layers=max(unit, layers // unit * unit))


def _update_gap(got, want, init) -> float:
    """Largest per-leaf ||got - want|| / ||want - init||: how far a
    member's final params are from the solo run's, as a share of the
    solo run's own update.  A leaf the solo run left unchanged counts 0
    if ``got`` left it unchanged too, else infinity."""
    import jax
    import numpy as np
    worst = 0.0
    for g, w, i in zip(*(jax.tree.leaves(t) for t in (got, want, init))):
        g, w, i = (np.asarray(a, np.float32) for a in (g, w, i))
        num = float(np.linalg.norm((g - w).ravel()))
        den = float(np.linalg.norm((w - i).ravel()))
        worst = max(worst, num / den if den > 0 else
                    (0.0 if num == 0 else float("inf")))
    return worst


@_recorded("sharing")
def sharing_phase(cfg_a, cfg_b, *, batch=2, seq=1024, iters_a=6,
                  iters_b=2, use_kernels=True, seed=0,
                  update_tol=UPDATE_TOL) -> dict:
    import jax
    import numpy as np

    from repro.data.synthetic import make_batch
    from repro.launch.cluster import JobSpec, ScheduleExecutor
    from repro.models import init_params
    from repro.train.optimizer import adamw_init
    failures: list = []
    at_start = _peak_bytes()[1] or 0
    say(f"sharing: bytes_in_use at phase start {at_start}")
    for cfg in (cfg_a, cfg_b):
        say(f"sharing: {cfg.name} d_model={cfg.d_model} heads="
            f"{cfg.n_heads}/{cfg.n_kv_heads} d_ff={cfg.d_ff} vocab="
            f"{cfg.vocab} {cfg.dtype}: depth cut to {cfg.n_layers} layers "
            f"({cfg.param_count():,} params); batch {batch} x seq {seq}")
    plan, _ = sharing_plan(cfg_a.name, cfg_b.name, batch=batch,
                           iters_a=iters_a, iters_b=iters_b)
    for i, ph in enumerate(plan.phases):
        say(f"sharing: plan phase {i}: ops "
            f"{[(o.kind, o.job, o.sub_batch) for o in ph.ops]} quotas "
            f"{list(ph.quotas)} groups {list(ph.groups)}")
    kinds = {op.kind for ph in plan.phases for op in ph.ops}
    if not {"start", "reconfig", "finish"} <= kinds:
        failures.append(f"sharing: plan lacks start/reconfig/finish: {kinds}")
    specs = {"A": JobSpec(cfg_a, batch=batch, seq=seq, seed=seed + 1,
                          use_kernels=use_kernels),
             "B": JobSpec(cfg_b, batch=batch, seq=seq, seed=seed + 2,
                          use_kernels=use_kernels)}
    totals = _totals(plan)
    programs: dict = {}
    t0 = time.perf_counter()
    with ScheduleExecutor(donate=True, program_cache=programs) as ex:
        for name, spec in specs.items():
            ex.submit(name, spec, totals[name])
        report = ex.execute(plan)
        group = {n: (jax.device_get(ex.runs[n].params),
                     list(ex.runs[n].losses), list(ex.runs[n].reconfigs))
                 for n in specs}
        compiles = ex.compiles
        ex.runs.clear()
    say(f"sharing: group run {time.perf_counter() - t0:.1f}s, "
        f"{compiles} programs compiled")
    accum = max(r["accum_steps"] for r in report.values())
    for n in specs:
        say(f"sharing: {n} ({specs[n].cfg.name}) steps {report[n]['steps']}"
            f" reconfigs {group[n][2]} loss per step "
            f"{[round(x, 5) for x in group[n][1]]}")
    # JAX has no reset of the device's peak counter, so this phase's own
    # peak is estimated: what it started with plus its largest program's
    # live set (arguments, temporaries, outputs not aliased to inputs)
    largest = 0
    for key, prog in programs.items():
        ma = prog.memory_analysis()
        live = (ma.argument_size_in_bytes + ma.temp_size_in_bytes
                + ma.output_size_in_bytes - ma.alias_size_in_bytes)
        largest = max(largest, live)
        say(f"sharing: program {'+'.join(m[0].name for m in key[1:])} "
            f"(accum {'+'.join(str(m[1]) for m in key[1:])}) memory: args "
            f"{ma.argument_size_in_bytes} out {ma.output_size_in_bytes} "
            f"alias {ma.alias_size_in_bytes} temp {ma.temp_size_in_bytes}")
    peak, _ = _peak_bytes()
    say(f"sharing: phase peak estimate {at_start + largest} (bytes_in_use "
        f"at start + largest program {largest}); process "
        f"peak_bytes_in_use {peak}")
    if not any(len(k) == 3 and k[1][1] >= 2 and k[2][1] >= 2
               for k in programs):
        failures.append("sharing: no fused group with accum_steps >= 2")

    def alone(name, spec, state=None):
        """``name`` through the executor alone, on the plan's own ops
        and quotas; ``state`` replaces the start op's fresh state."""
        phases = _solo_plan(plan, name)
        with ScheduleExecutor(donate=True, program_cache=programs) as solo:
            solo.submit(name, spec, totals[name])
            if state is not None:
                op = next(o for ph in phases for o in ph.ops
                          if o.kind == "start")
                solo.start(name, sub_batch=op.sub_batch, state=state)
                phases = [dataclasses.replace(ph, ops=tuple(
                    o for o in ph.ops if o.kind != "start"))
                    for ph in phases]
            solo.execute(phases)
            out = (jax.device_get(solo.runs[name].params),
                   list(solo.runs[name].losses))
            solo.runs.clear()
        return out

    bitwise, details = True, {}
    for n, spec in specs.items():
        mine, losses = alone(n, spec)
        init = jax.device_get(init_params(spec.cfg,
                                          jax.random.PRNGKey(spec.seed)))
        got = [np.asarray(a) for a in jax.tree.leaves(group[n][0])]
        want = [np.asarray(b) for b in jax.tree.leaves(mine)]
        n_diff = sum(int(np.sum(a != b)) for a, b in zip(got, want))
        maxabs = max(float(np.max(np.abs(a.astype(np.float32)
                                         - b.astype(np.float32))))
                     for a, b in zip(got, want))
        same = n_diff == 0 and losses == group[n][1]
        bitwise &= same
        loss_gap = max(abs(x - y) / max(1.0, abs(y))
                       for x, y in zip(group[n][1], losses))
        gap = _update_gap(group[n][0], mine, init)
        details[n] = {"bitwise": same, "elements_differing": n_diff,
                      "elements": sum(a.size for a in got),
                      "max_abs_diff": maxabs, "max_loss_gap": loss_gap,
                      "update_gap": gap, "solo_losses": losses}
        say(f"sharing: {n} final params vs solo run: bitwise {same} "
            f"({n_diff} of {details[n]['elements']} elements differ, max "
            f"|diff| {maxabs:.3g}); update gap {gap:.4g} (tol "
            f"{update_tol}); loss gap {loss_gap:.3g}; solo loss per step "
            f"{[round(x, 5) for x in losses]}")
        if not (np.all(np.isfinite(group[n][1]))
                and np.all(np.isfinite(losses))):
            failures.append(f"sharing: {n} loss not finite")
        # The fused and the solo program are separate XLA modules whose
        # fusions may round differently: bitwise identity is reported (it
        # holds on the CPU, where tests pin it).  Gates: the final params
        # (update gap, see UPDATE_TOL) and every step's loss, to 1e-3
        # (relative above 1).
        if not gap <= update_tol:
            failures.append(f"sharing: {n} final params off the solo run "
                            f"by {gap:.4g} of its update > {update_tol}")
        if not loss_gap <= 1e-3:
            failures.append(f"sharing: {n} loss gap to solo run "
                            f"{loss_gap:.3g} > 1e-3")
        if n == "B":
            # the control: B alone with its second accumulation
            # microbatch dropped (its first sample at sub-batch 1,
            # accumulation 1) must fail the update gate
            one = dataclasses.replace(spec, batch=1)
            data = jax.tree.map(lambda x: x[:1], make_batch(
                spec.cfg, spec.batch, spec.seq, seed=spec.seed))
            params0 = init_params(spec.cfg, jax.random.PRNGKey(spec.seed))
            dropped, _ = alone(n, one, (params0, adamw_init(params0), data))
            del params0, data
            ctl = _update_gap(dropped, mine, init)
            details[n]["dropped_microbatch_gap"] = ctl
            say(f"sharing: control: B with one accumulation microbatch "
                f"dropped, update gap to the solo run {ctl:.4g}")
            if not ctl > update_tol:
                failures.append(f"sharing: a dropped microbatch passes the "
                                f"update gate ({ctl:.4g} <= {update_tol})")
    say(f"sharing: group == solo bitwise for every member: {bitwise}")
    return {"accum_steps": accum, "bitwise": bitwise, "details": details,
            "peak_estimate": at_start + largest, "failures": failures}


# ---------------------------------------------------------------------- #
# fleet: agents, one per chip, against one executor
# ---------------------------------------------------------------------- #
def fleet_phase(cfg_a, cfg_b, *, n_agents, workdir, batch=2,
                seq=128, use_kernels=True, seed=0):
    """Replay a plan with ``n_agents`` concurrent sharing groups on as
    many agent processes.  Touches no JAX backend in this process: the
    agents hold the chips.  Returns (plan, specs, per-job report)."""
    from repro.launch.cluster import JobSpec
    from repro.launch.fleet import FleetConfig, FleetMaster
    plan, names = sharing_plan(cfg_a.name, cfg_b.name, n_servers=n_agents,
                               batch=batch)
    groups = max(len(ph.groups) for ph in plan.phases)
    say(f"fleet: {n_agents} agents, {len(names)} jobs, up "
        f"to {groups} concurrent groups; {cfg_a.name} + {cfg_b.name}")
    specs = {}
    for jid, name in sorted(names.items()):
        cfg = cfg_a if name.startswith("A") else cfg_b
        specs[name] = JobSpec(cfg, batch=batch, seq=seq, seed=seed + jid,
                              use_kernels=use_kernels)
    ckpt = os.path.join(workdir, "fleet")
    t0 = time.perf_counter()
    try:
        # a phase of these reduced jobs takes seconds: a stalled agent
        # fails the run in minutes rather than at the default deadline
        with FleetMaster(ckpt, config=FleetConfig(
                checkpoint_every=1, phase_timeout=300.0)) as m:
            m.start(n_agents=n_agents)
            report = m.run_plan(plan, specs)
    except Exception:
        for f in sorted(os.listdir(ckpt)):
            if f.endswith(".log"):
                with open(os.path.join(ckpt, f), errors="replace") as fh:
                    say(f"fleet: {f} tail:\n{fh.read()[-3000:]}")
        raise
    say(f"fleet: plan replayed in {time.perf_counter() - t0:.1f}s")
    return plan, specs, report, groups


def fleet_reference(plan, specs, report, workdir) -> list:
    """The same plan in one ScheduleExecutor; per-job checkpoint CRCs
    must equal the fleet's."""
    from repro.checkpoint import checkpoint_crc
    from repro.launch.cluster import ScheduleExecutor
    failures = []
    ref_dir = os.path.join(workdir, "reference")
    with ScheduleExecutor(donate=True, checkpoint_dir=ref_dir) as ex:
        totals = _totals(plan)
        for name, spec in specs.items():
            ex.submit(name, spec, totals[name])
        ref = ex.execute(plan)
        paths = {name: ex.checkpoint(name) for name in specs}
        ex.flush_checkpoints()
        ex.runs.clear()
    for name in specs:
        crc = checkpoint_crc(paths[name])
        ok = (report[name]["finished"] and report[name]["crc"] == crc
              and report[name]["steps"] == ref[name]["steps"])
        say(f"fleet: {name} steps {report[name]['steps']} crc fleet "
            f"{report[name]['crc']} single-executor {crc} match {ok}")
        if not ok:
            failures.append(f"fleet: job {name} diverged from the "
                            f"single-executor run")
    return failures


# ---------------------------------------------------------------------- #
def _count_cache_events() -> dict:
    import jax
    counts = {"hits": 0, "misses": 0}

    def listen(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            counts["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            counts["misses"] += 1
    jax.monitoring.register_event_listener(listen)
    return counts


def _finish(failures: list, info: dict) -> int:
    for f in failures:
        say(f"FAIL {f}")
    if failures:
        return 1
    print(json.dumps({"ok": True, "device": info}), flush=True)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--fleet4", action="store_true",
                    help="four fleet agents (one per chip) replaying a "
                         "plan, checked against one executor")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    if args.fleet4:
        return fleet4_main(args.seed)
    info = require_tpu()                  # before anything else
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.configs import get_config
    from repro.util.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    counts = _count_cache_events()
    say(f"device: {json.dumps(info)}; compile cache {cache_dir}")

    t0 = time.perf_counter()
    serve = serving_phase(get_config(SERVE_ARCH), seed=args.seed)
    gc.collect()
    say(f"serving: phase done in {time.perf_counter() - t0:.1f}s")
    t0 = time.perf_counter()
    cfg_a, cfg_b = (cut_depth(get_config(n), SHARE_LAYERS)
                    for n in SHARE_ARCHS)
    share = sharing_phase(cfg_a, cfg_b, seed=args.seed)
    say(f"sharing: phase done in {time.perf_counter() - t0:.1f}s")
    say(f"compile cache: {counts['hits']} hits, {counts['misses']} misses")
    return _finish(serve["failures"] + share["failures"], info)


def _devices_in_child() -> dict:
    """Device info from a child process that exits at once, so this
    process never holds a chip the agents need."""
    code = ("import jax, json; d = jax.devices(); print(json.dumps("
            "{'platform': d[0].platform, 'kind': d[0].device_kind, "
            "'count': len(d)}))")
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=600)
    if proc.returncode != 0:
        raise SystemExit(f"chip_smoke: device probe failed:\n"
                         f"{proc.stderr[-2000:]}")
    return json.loads(proc.stdout.strip().splitlines()[-1])


def fleet4_main(seed: int) -> int:
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.configs import get_config
    from repro.launch.fleet import local_tpu_chips
    probe = _devices_in_child()
    if probe["platform"] != "tpu" or probe["count"] < 4:
        raise SystemExit(f"chip_smoke --fleet4: needs 4 TPU chips, JAX "
                         f"found {probe}")
    say(f"device: {json.dumps(probe)} (device files show "
        f"{local_tpu_chips()} chips)")
    # reduced widths: the single-executor reference holds all eight
    # jobs' states on one chip at once
    cfg_a, cfg_b = (get_config(n).reduced() for n in SHARE_ARCHS)
    with tempfile.TemporaryDirectory() as work:
        plan, specs, report, groups = fleet_phase(
            cfg_a, cfg_b, n_agents=4, workdir=work, seed=seed)
        info = require_tpu()              # the agents have exited
        from repro.kernels import ops
        from repro.util.compile_cache import enable_compile_cache
        enable_compile_cache()
        with ops.recording() as routes:
            failures = fleet_reference(plan, specs, report, work)
    if groups < 4:
        failures.append(f"fleet: plan has only {groups} concurrent groups")
    _check_routes(routes, failures, "fleet reference")
    return _finish(failures, info)


if __name__ == "__main__":
    sys.exit(main())
