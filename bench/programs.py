"""How the benchmark builds what it drives: the program's configuration
for a configuration file, and the schedule plans of the training cells.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict

# configuration-file key -> the program's ArchConfig field
WIDTHS = {"hidden_size": "d_model", "num_attention_heads": "n_heads",
          "num_key_value_heads": "n_kv_heads", "head_dim": "head_dim",
          "intermediate_size": "d_ff", "vocab_size": "vocab",
          "num_hidden_layers": "n_layers", "rms_norm_eps": "norm_eps",
          "rope_theta": "rope_theta", "tie_word_embeddings": "tie_embeddings",
          "torch_dtype": "dtype", "vision_tokens": "vision_tokens"}


def program_config(config: Dict[str, Any]):
    """The program's ArchConfig for ``config["arch"]``, with every size the
    file states put in its place: the file is the configuration as run."""
    from repro.configs import get_config
    fields = {f: config[k] for k, f in WIDTHS.items() if k in config}
    mrope = config.get("rope_scaling", {}).get("mrope_section")
    if mrope is not None:
        fields["mrope_sections"] = tuple(mrope)
    return dataclasses.replace(get_config(config["arch"]), **fields)


def sharing_plan(model_a: str, model_b: str, *, batch: int, iters_a: int,
                 iters_b: int):
    """SJF-BSBF on one one-GPU server: a donor of ``model_a`` from t=0 and
    a sharer of ``model_b`` that arrives after two donor iterations.  The
    memory capacity admits the two only at half batch, so the sharer runs
    with gradient accumulation and the donor is reconfigured at the
    sharing point.  Returns the executor's plan."""
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
    jobs = [Job(jid=0, model=model_a, arrival=0.0, gpus=1,
                iters=float(iters_a), batch=batch, perf=pa),
            Job(jid=1, model=model_b, arrival=2 * t_a, gpus=1,
                iters=float(iters_b), batch=batch, perf=pb)]
    half = max(1, batch // 2)
    cap = pa.mem_bytes(half) + pb.mem_bytes(half) + 0.0625 * gib
    interference = paper_interference_model()
    sim = Simulator(ClusterState(n_servers=1, gpus_per_server=1,
                                 gpu_capacity_bytes=cap),
                    jobs, SJF_BSBF(donor_reconfig=True),
                    interference=interference, reconfig_on_release=True)
    sim.run()
    return plan_from_sim(sim.log, sim.jobs, interference, cap,
                         names={0: "A", 1: "B"})


def solo_plan(job: str, batch: int):
    """One job alone at its full batch: no accumulation, no fusion."""
    from repro.launch.cluster import PlanOp, PlanPhase, SchedulePlan
    return SchedulePlan(phases=[PlanPhase(
        ops=(PlanOp("start", job, batch),), quotas=((job, 1),),
        groups=((job,),))], predicted={})
