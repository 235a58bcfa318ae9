"""granite-4.0-h-micro, the hybrid of Mamba2 and attention layers with an
MLP after every mixer, against the benchmark's float32 reference
(``bench/reference_hybrid.py``), at a tiny size on seeded random
weights: the forward's logits, the loss and the gradients on the jnp
path and on the kernel path (SSD and flash attention interpreted), the
parts of the architecture that the match depends on, and a hybrid job
sharing an accelerator with a dense one under SJF-BSBF."""
import dataclasses
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import reference_hybrid as R  # noqa: E402
from bench.drivers.train_hybrid import program_config  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.launch.cluster import (JobSpec, ScheduleExecutor,  # noqa: E402
                                  plan_from_sim)
from repro.models import forward  # noqa: E402
from repro.train.train_step import loss_fn  # noqa: E402

B, S, SEED = 2, 32, 11
# The same widths' relations as the published model (GQA 2:1, attention
# multiplier 1/head_dim, Mamba2 d_inner = 2 x hidden), cut to a few dozen.
TINY = dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
            intermediate_size=128, shared_intermediate_size=128,
            vocab_size=256, mamba_n_heads=8, mamba_d_head=16,
            mamba_d_state=16, attention_multiplier=1 / 16,
            torch_dtype="float32")


@pytest.fixture(scope="module")
def tiny():
    with open(os.path.join(ROOT, "bench", "configs",
                           "granite-4.0-h-micro-10l.json")) as f:
        conf = json.load(f)
    conf.update(TINY, reduced=["num_hidden_layers", *TINY])
    dm = R.Dims.of(conf)
    params = R.init_params(dm, SEED)
    # queries and keys 20x the init's scale, so that the tiny attention
    # layer's softmax is sharp and its positions matter
    attn = params["units"]["sub5"]["attn"]
    for w in ("wq", "wk"):
        attn[w]["w"] = attn[w]["w"] * 20.0
    batch = R.train_batches(dm, SEED, 0, B, S, 1)[0]
    mm = R.make_mm("float32")
    with jax.default_matmul_precision("highest"):
        logits = jax.jit(lambda p: R.forward(dm, mm, p, batch["tokens"]))(
            params)
        loss, grads = jax.jit(jax.value_and_grad(
            lambda p: R.head_loss(dm, mm, p, _ref_trunk(dm, mm, p, batch),
                                  batch["labels"])))(params)
    return {"cfg": program_config(conf), "params": params, "batch": batch,
            "logits": logits, "loss": float(loss), "grads": grads}


def _ref_trunk(dm, mm, params, batch):
    x = params["embed"]["table"][batch["tokens"]] * dm.emb_mult
    for l, kind in enumerate(dm.kinds):
        w = jax.tree.map(lambda a: a[l // dm.period],
                         params["units"][f"sub{l % dm.period}"])
        x = R.layer_forward(dm, mm, kind, w, x)
    return x


def _logits(cfg, params, tokens, use_kernels=False):
    return jax.jit(lambda p: forward(cfg, p, {"tokens": tokens}, remat=False,
                                     use_kernels=use_kernels)[0])(params)


def _logits_gap(cfg, params, tiny):
    got = _logits(cfg, params, tiny["batch"]["tokens"])
    want = tiny["logits"]
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


@pytest.mark.parametrize("use_kernels", [False, True],
                         ids=["jnp", "kernels"])
def test_program_matches_reference(tiny, use_kernels):
    """Both in float32.  The program runs Mamba2 as the chunked SSD (or
    its kernel) and the reference as the position-by-position
    recurrence: the same terms summed in another order, a few ulps apart
    (2e-6 of the gradients' norms measured); the tolerances leave
    about 10x."""
    cfg, params, batch = tiny["cfg"], tiny["params"], tiny["batch"]
    logits = _logits(cfg, params, batch["tokens"], use_kernels)
    want = tiny["logits"]
    assert float(jnp.max(jnp.abs(logits - want))) <= 1e-5 * float(
        jnp.max(jnp.abs(want)))
    (loss, _), grads = jax.jit(jax.value_and_grad(
        lambda p: loss_fn(cfg, p, batch, use_kernels=use_kernels),
        has_aux=True))(params)
    assert abs(float(loss) - tiny["loss"]) <= 1e-6 * tiny["loss"]
    for (path, g), r in zip(jax.tree_util.tree_leaves_with_path(grads),
                            jax.tree.leaves(tiny["grads"])):
        gap = float(jnp.linalg.norm(g - r))
        assert gap <= 3e-5 * float(jnp.linalg.norm(r)) + 1e-9, \
            jax.tree_util.keystr(path)


def _swap_layers(cfg, params, i, j):
    """The same layers run in another order: layers i and j trade places."""
    pattern = list(cfg.layer_pattern)
    pattern[i], pattern[j] = pattern[j], pattern[i]
    units = dict(params["units"])
    units[f"sub{i}"], units[f"sub{j}"] = units[f"sub{j}"], units[f"sub{i}"]
    return (dataclasses.replace(cfg, layer_pattern=tuple(pattern)),
            dict(params, units=units))


@pytest.mark.parametrize("change", [
    "layer_order", "rope", "attention_scale", "embedding_multiplier",
    "residual_multiplier", "logits_scaling"])
def test_each_part_of_the_architecture_matters(tiny, change):
    """The match above depends on every part the configuration names:
    with any one of them removed from the program (attention moved one
    layer earlier, RoPE where the model has none, the default softmax
    scale, or a multiplier at one) the logits leave the reference's by
    far more than the tolerance of 1e-5."""
    cfg, params = tiny["cfg"], tiny["params"]
    if change == "layer_order":
        cfg, params = _swap_layers(cfg, params, 4, 5)
    elif change == "rope":
        cfg = dataclasses.replace(cfg, rope=True)
    elif change == "attention_scale":
        cfg = dataclasses.replace(cfg, attention_multiplier=0.0)
    else:
        cfg = dataclasses.replace(cfg, **{change: 1.0})
    assert _logits_gap(cfg, params, tiny) > 1e-3


def test_reduced_keeps_the_period():
    cfg = get_config("granite-4.0-h-micro")
    small = cfg.reduced()
    assert cfg.layer_pattern == ("mamba",) * 5 + ("attention",) + (
        "mamba",) * 4
    assert small.layer_pattern == cfg.layer_pattern
    assert small.n_layers == 10 and small.n_units == 1
    assert (small.embedding_multiplier, small.residual_multiplier,
            small.attention_multiplier, small.logits_scaling) == (
        12.0, 0.22, 0.015625, 8.0)
    assert not small.rope and cfg.q_scale == 0.125


def _sharing_plan():
    """SJF-BSBF on one one-GPU server whose memory admits the two jobs
    only at half batch: the hybrid donor "A" from t=0, reconfigured to
    sub-batch 1 (accumulation 2) when the dense sharer "B" arrives."""
    from repro.core import ClusterState, Job, PerfParams, Simulator
    from repro.core.interference import paper_interference_model
    from repro.core.schedulers import SJF_BSBF
    gib = 2 ** 30

    def perf(beta):
        return PerfParams(alpha_comp=0.01, beta_comp=beta, alpha_comm=0.0,
                          beta_comm=0.0, msg_bytes=0.0, delta=2.0,
                          mem_base=4.0 * gib, mem_per_sample=0.25 * gib,
                          param_bytes=1e8, n_workers=1)
    pa, pb = perf(0.01), perf(0.008)
    jobs = [Job(jid=0, model="hybrid", arrival=0.0, gpus=1, iters=6.0,
                batch=2, perf=pa),
            Job(jid=1, model="dense", arrival=2 * pa.t_iter(2), gpus=1,
                iters=2.0, batch=2, perf=pb)]
    interference = paper_interference_model()
    cap = pa.mem_bytes(1) + pb.mem_bytes(1) + 0.0625 * gib
    sim = Simulator(ClusterState(n_servers=1, gpus_per_server=1,
                                 gpu_capacity_bytes=cap),
                    jobs, SJF_BSBF(donor_reconfig=True),
                    interference=interference, reconfig_on_release=True)
    sim.run()
    return plan_from_sim(sim.log, sim.jobs, interference, cap,
                         names={0: "A", 1: "B"})


def test_hybrid_job_shares_with_a_dense_job():
    """The paper's mechanism with a hybrid member: one fused program
    advances both jobs after the donor's reconfiguration to
    accumulation 2, and each job's losses match its solo run at its full
    batch within the executor's accumulation tolerance."""
    specs = {
        "A": JobSpec(dataclasses.replace(get_config(
            "granite-4.0-h-micro").reduced(), dtype="float32"),
            batch=2, seq=32),
        "B": JobSpec(dataclasses.replace(get_config(
            "minicpm-2b").reduced(), dtype="float32"), batch=2, seq=32,
            seed=1)}
    plan = _sharing_plan()
    ops = [(op.kind, op.job, op.sub_batch) for ph in plan.phases
           for op in ph.ops]
    assert ("reconfig", "A", 1) in ops and ("start", "B", 1) in ops
    ex = ScheduleExecutor(donate=True)
    for name, spec in specs.items():
        ex.submit(name, spec, sum(q for ph in plan.phases
                                  for n, q in ph.quotas if n == name))
    ex.execute(plan)
    fused = [g for ph in plan.phases for g in ph.groups if len(g) == 2]
    assert fused and ex.compiles >= 2
    for name, spec in specs.items():
        run = ex.runs[name]
        assert run.reconfigs or name == "B"
        solo = ScheduleExecutor(donate=True)
        solo.submit(name, spec, 10 ** 6)
        solo.start(name)
        for _ in range(run.steps_done):
            solo.step_group([name])
        np.testing.assert_allclose(run.losses, solo.runs[name].losses,
                                   rtol=2e-3, atol=2e-5)
