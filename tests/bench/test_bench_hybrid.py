"""The hybrid training cell (``train_solo.granite-4.0-h-micro-10l``) on a
tiny copy on the CPU: a sound run is correct, faults planted under the
timed path and the float8 control are not, the program's configuration
is checked key by key against the file, and the three per-layer metrics
read what a trace of the cell holds."""
from __future__ import annotations

import json
import os

import pytest

import bench_tiny
from bench import harness, program_trace
from bench import reference_hybrid as ref
from bench.costs import hybrid, ssd
from bench.drivers.train_hybrid import program_config
from test_bench_faults import _half_batch, _unchanged

CELL = "train_solo.granite-4.0-h-micro-10l"
CONFIG = os.path.join(harness.BENCH, "configs", "granite-4.0-h-micro-10l.json")
# the published relations of the widths, cut to a few dozen
SMALL = dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
             intermediate_size=128, shared_intermediate_size=128,
             vocab_size=512, mamba_n_heads=8, mamba_d_head=16,
             mamba_d_state=16, attention_multiplier=1 / 16)


def _edit(path, **kw):
    c = harness.load_json(path)
    c.update(kw)
    with open(path, "w") as f:
        json.dump(c, f)


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    b = bench_tiny.build(str(tmp_path_factory.mktemp("tiny")))
    _edit(os.path.join(b, "configs", "granite-4.0-h-micro-10l.json"),
          reduced=["num_hidden_layers", *SMALL], **SMALL)
    _edit(os.path.join(b, "traffic", "plan_solo_granite.json"), seq=64)
    return b


def test_sound_hybrid_run_is_correct(bench):
    rc, line = bench_tiny.run(bench, CELL, seconds=1.0)
    assert rc == 0 and line["correct"], line["checks"]
    assert line["metrics"]["train_tokens_per_s"]["value"] > 0


@pytest.mark.parametrize("fault", [_unchanged, _half_batch],
                         ids=["state_unchanged", "half_batch"])
def test_hybrid_fault_is_not_correct(bench, fault, monkeypatch):
    from repro.launch import cluster
    monkeypatch.setattr(cluster, "make_train_step",
                        fault(cluster.make_train_step))
    rc, line = bench_tiny.run(bench, CELL, seconds=1.0)
    assert rc == 0 and not line["correct"], line["checks"]


def test_hybrid_control_fails_a_limit(bench):
    """The reference with every product in float8, against the float32
    reference: at least one number passes its limit."""
    from bench.drivers.train import _gaps
    cell = harness.load_cell(CELL, bench=bench)
    tr = cell.traffic
    dm = ref.Dims.of(cell.config)
    opt = ref.Adam(**tr["optimizer"])
    batches = ref.train_batches(dm, 5, 0, tr["batch"], tr["seq"], 3)
    gaps = _gaps(ref.run_reference(dm, 5, batches, opt, precision="fp8"),
                 ref.run_reference(dm, 5, batches, opt))
    limits = cell.workload["limits"]
    assert any(gaps[k] > limits[k] for k in limits), gaps


@pytest.mark.parametrize("change", [
    {"rope_scaling_factor": 2.0},                        # a key nobody reads
    {"position_embedding_type": "rope"},
    {"mamba_chunk_size": 128},
    {"layer_types": ["attention"] + ["mamba"] * 9},
    {"mamba_d_head": 32},
    {"intermediate_size": 4096, "shared_intermediate_size": 4096},
    {"embedding_multiplier": 1.0}])
def test_program_config_checks_every_key(change):
    conf = harness.load_json(CONFIG)
    cfg = program_config(conf)
    assert (cfg.n_layers, cfg.d_model, cfg.ssm_state, cfg.n_ssm_heads,
            cfg.head_dim, cfg.q_scale, cfg.layer_pattern[5]) == (
        10, 2048, 128, 64, 64, 0.125, "attention")
    conf.update(change)
    with pytest.raises(SystemExit):
        program_config(conf)


def test_required_work_of_the_cell():
    """About 5.8 GFLOP a token for a step, and the SSD's products at its
    chunk of 256 well inside it."""
    dm = ref.Dims.of(harness.load_json(CONFIG))
    per_token = hybrid.train_step_flops(dm, 2, 2048) / 4096
    assert 5.7e9 < per_token < 5.9e9
    fwd, fwd_bytes = ssd.forward(dm, 2, 2048)
    bwd, _ = ssd.backward(dm, 2, 2048)
    assert bwd == 2 * fwd and fwd_bytes < fwd
    assert 9 * 3 * fwd < 0.1 * per_token * 4096


# ---------------------------------------------------------------------- #
# the three readers
# ---------------------------------------------------------------------- #
MS = 1_000_000
PEAKS = {"bf16_flop_per_s": 197e12, "hbm_byte_per_s": 819e9}
OPS = {"ssd_fwd.1": "jit(group_step)/member0/jvp(forward)/mamba/ssd_fwd/"
                    "pallas_call",
       "ssd_bwd.2": "jit(group_step)/member0/transpose(jvp(forward))/"
                    "member0/jvp(forward)/checkpoint/rematted_computation/"
                    "mamba/ssd_bwd/pallas_call",
       "fusion.3": "jit(group_step)/member0/jvp(forward)/mamba/mul",
       "fusion.4": "jit(group_step)/member0/jvp(forward)/mlp/dot_general"}


def _kernel(name):
    return f'%{name} = f32[2] custom-call(), custom_call_target=' \
        '"tpu_custom_call"'


def _events(names=True):
    dev = [(0, 30 * MS, _kernel("ssd_fwd.1")),
           (30 * MS, 50 * MS, _kernel("ssd_bwd.2")),
           (80 * MS, 20 * MS, "%fusion.3 = f32[] fusion()"),
           (100 * MS, 40 * MS, "%fusion.4 = f32[] fusion()")]
    return {"device": {"/device:TPU:0": dev},
            "host": [(0, 1000 * MS, "bench.window")],
            "modules": {"/device:TPU:0": [(0, 140 * MS,
                                           "jit_group_step(1)")]},
            "op_names": {"jit_group_step(1)": OPS} if names else {}}


def _run(counters, traced=True):
    return harness.Run(cell=harness.load_cell(CELL), counters=counters,
                       spans=[], window_s=1.0,
                       trace={"busy_s": 1.0} if traced else None,
                       peaks=PEAKS)


def test_hybrid_readers(monkeypatch):
    held = {"events": _events()}
    monkeypatch.setattr(program_trace, "load_events",
                        lambda d: held["events"])
    counters = {"calls": 4, "step_group_s": [0.2] * 4, "batch": 2,
                "seq": 2048}
    dm = ref.Dims.of(harness.load_json(CONFIG))
    mfu = harness.reader_for("step_mfu.hybrid").read(_run(counters))
    assert mfu == pytest.approx(100 * hybrid.train_step_flops(dm, 2, 2048)
                                / 0.2 / PEAKS["bf16_flop_per_s"])
    (ff, fb), (bf, bb) = ssd.forward(dm, 2, 2048), ssd.backward(dm, 2, 2048)
    least = 9 * (max(ff / 197e12, fb / 819e9) + max(bf / 197e12, bb / 819e9))
    roof = harness.reader_for("ssd_roofline.train").read(_run(counters))
    assert roof == pytest.approx(100 * least * 4 / 0.080)
    assert 0 < roof < 100
    mamba = harness.reader_for("mamba_ms.train").read(_run(counters))
    assert mamba == pytest.approx((30 + 50 + 20) / 4)
    # no kernels or scopes of these names, or no trace: nothing to read
    held["events"] = _events(names=False)
    run = _run(counters)
    assert harness.reader_for("mamba_ms.train").read(run) is None
    held["events"]["device"]["/device:TPU:0"] = [
        (0, MS, "%fusion.3 = f32[] fusion()")]
    assert harness.reader_for("ssd_roofline.train").read(_run(counters)) \
        is None
    assert harness.reader_for("ssd_roofline.train").read(
        _run(counters, traced=False)) is None
