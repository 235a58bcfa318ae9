"""Training cells: ``ScheduleExecutor`` runs the traffic file's plan.

Set-up builds one executor, gives each job the benchmark's weights and
batches from the seed, runs the plan's ops and steps up to the group the
window times (for a shared plan: the donor alone, its reconfiguration to
a sub-batch, the sharer's start), then drives that group through
``checked_steps`` calls.  Those first steps of each job are what the
plain reference follows.  The window then calls ``step_group`` on the
same executor until time is up.

Checks, per job, against ``bench.reference`` in float32 from the same
seed (each the worst over steps or parameter leaves):

- ``loss_gap``: |loss - reference loss| / reference loss, every step;
- ``grad_gap``: the first gradient's norm per leaf, as the optimizer got
  it (its first moment after one step over 1 - beta1), against the
  reference's: |difference| / max(reference leaf, median leaf);
- ``change_gap``: the same for the norm of each leaf's change over the
  checked steps, leaving out leaves whose reference gradient is under a
  thousandth of the median leaf's.
"""
from __future__ import annotations

import gc
import time
from typing import Any, Dict, List

from bench import reference as ref
from bench.harness import memory_peak_bytes, say
from bench.programs import program_config, sharing_plan, solo_plan


def _gaps(got: Dict[str, Any], want: Dict[str, Any]) -> Dict[str, float]:
    import numpy as np
    lg = max(abs(a - b) / abs(b) for a, b in zip(got["losses"],
                                                 want["losses"]))

    def leaf_gap(p, r, keep):
        den = np.maximum(r, np.median(r))
        return float(np.max(np.abs(p - r)[keep] / den[keep]))
    g_ref = want["grad"]
    moved = g_ref >= 1e-3 * np.median(g_ref)
    return {"loss_gap": float(lg),
            "grad_gap": leaf_gap(got["grad"], g_ref, np.ones_like(moved)),
            "change_gap": leaf_gap(got["change"], want["change"], moved)}


def run(h) -> Dict[str, Any]:
    import jax
    import numpy as np
    from repro.launch.cluster import JobSpec, ScheduleExecutor
    from repro.models import init_params as program_init
    from repro.train import TrainConfig
    from repro.train.optimizer import adamw_init

    tr = h.cell.traffic
    batch, seq = tr["batch"], tr["seq"]
    opt = ref.Adam(**tr["optimizer"])
    tc = TrainConfig()
    if (tc.lr, tc.weight_decay) != (opt.lr, opt.weight_decay):
        raise SystemExit(f"bench: the program trains at lr {tc.lr}, "
                         f"weight decay {tc.weight_decay}; the traffic "
                         f"file says {opt}")
    jobs = [m["job"] for m in tr["members"]]
    conf = {m["job"]: h.cell.configs[m["config"]] for m in tr["members"]}
    dims = {j: ref.Dims.of(conf[j]) for j in jobs}
    cfgs = {j: program_config(conf[j]) for j in jobs}
    if tr["plan"] == "sjf_bsbf":
        a, b = tr["members"]
        plan = sharing_plan(a["config"], b["config"], batch=batch,
                            iters_a=a["iters"], iters_b=b["iters"])
    else:
        plan = solo_plan(jobs[0], batch)
    for i, ph in enumerate(plan.phases):
        say(f"train: plan phase {i}: ops "
            f"{[(o.kind, o.job, o.sub_batch) for o in ph.ops]} quotas "
            f"{list(ph.quotas)} groups {list(ph.groups)}")
    group = next(tuple(g) for ph in plan.phases for g in ph.groups
                 if set(g) == set(jobs))
    n_feed = tr["batches_per_job"]
    feed = {j: ref.train_batches(dims[j], h.seed, i, batch, seq, n_feed)
            for i, j in enumerate(jobs)}
    checked = tr["checked_steps"]

    ex = ScheduleExecutor(donate=True)
    for j in jobs:
        ex.submit(j, JobSpec(cfgs[j], batch=batch, seq=seq, use_kernels=True),
                  10 ** 9)
    first_grad: Dict[str, np.ndarray] = {}

    def call(names) -> Dict[str, Any]:
        for n in names:
            r = ex.runs[n]
            r.batch = feed[n][r.steps_done % n_feed]
        res = ex.step_group(list(names))
        for n in names:
            if ex.runs[n].steps_done == 1:
                first_grad[n] = ref.leaf_norms(ex.runs[n].opt.m) / (
                    1 - opt.b1)
        return res

    def start(job, sub_batch):
        params = ref.init_params(dims[job], h.seed)
        want = jax.eval_shape(lambda: program_init(
            cfgs[job], jax.random.PRNGKey(0)))
        got = jax.eval_shape(lambda: params)
        if jax.tree.structure(want) != jax.tree.structure(got) or any(
                (a.shape, a.dtype) != (b.shape, b.dtype) for a, b in zip(
                    jax.tree.leaves(want), jax.tree.leaves(got))):
            raise SystemExit(f"bench: {job}: the benchmark's weights do not "
                             f"match the program's parameter tree")
        ex.start(job, sub_batch=sub_batch,
                 state=(params, adamw_init(params), feed[job][0]))

    for ph in plan.phases:
        for op in ph.ops:
            if op.kind == "start":
                start(op.job, op.sub_batch)
            elif op.kind == "reconfig":
                ex.reconfigure(op.job, op.sub_batch)
        if group in [tuple(g) for g in ph.groups]:
            break
        quotas = dict(ph.quotas)
        for g in ph.groups:
            for _ in range(min(quotas.get(n, 0) for n in g)):
                call(g)
    accum = {j: ex.runs[j].accum_steps for j in jobs}
    for _ in range(checked):
        call(group)
    steps = {j: ex.runs[j].steps_done for j in jobs}
    got = {j: {"losses": list(ex.runs[j].losses), "grad": first_grad[j],
               "change": ref.change_norms(dims[j], h.seed,
                                          ex.runs[j].params)}
           for j in jobs}
    say(f"train: group {group} accumulation {accum}; checked steps "
        f"{steps}; losses "
        f"{ {j: [round(x, 6) for x in g['losses']] for j, g in got.items()} }")
    setup_s = h.setup_done()
    calls = 0
    walls: List[float] = []
    with h.window():
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < h.window_seconds:
            with h.span("bench.step_group"):
                res = call(group)
            walls.append(res["walltime"])
            calls += 1
    t0, t1 = h.window_span
    tokens = calls * batch * seq * len(group)
    peak = memory_peak_bytes()
    ex.runs.clear()
    del ex
    gc.collect()

    checks: Dict[str, Dict[str, float]] = {}
    calibration: Dict[str, Any] = {}
    limits = h.cell.workload["limits"]
    worst = {k: 0.0 for k in limits}
    for i, j in enumerate(jobs):
        used = [feed[j][s % n_feed] for s in range(steps[j])]
        want = ref.run_reference(dims[j], h.seed, used, opt)
        gaps = _gaps(got[j], want)
        say(f"train: {j} reference losses "
            f"{[round(x, 6) for x in want['losses']]}; gaps {gaps}")
        for k in worst:
            worst[k] = max(worst[k], gaps[k])
        if h.calibrate:
            ctl = _gaps(ref.run_reference(dims[j], h.seed, used, opt,
                                          precision="fp8"), want)
            half = _gaps(ref.run_reference(dims[j], h.seed, used, opt,
                                           keep_rows=batch // 2), want)
            calibration[f"{j}.control_fp8"] = ctl
            calibration[f"{j}.half_batch"] = half
        del want
        gc.collect()
    for k, v in worst.items():
        checks[k] = {"value": v, "limit": limits[k]}
    finite = all(np.isfinite(got[j]["losses"]).all() for j in jobs)
    return {"setup_s": setup_s,
            "end_to_end": {"train_tokens_per_s": tokens / (t1 - t0)},
            "attempted": calls, "failed": 0, "correct": finite,
            "checks": checks, "calibration": calibration,
            "memory_peak_bytes": peak,
            "counters": {"calls": calls, "step_group_s": walls,
                         "group": list(group), "batch": batch, "seq": seq,
                         "accum": accum, "tokens": tokens}}

