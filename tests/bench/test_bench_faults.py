"""``correct`` against faults planted under the timed path, and the
lower-precision control, on tiny copies of the cells on the CPU.

Each fault breaks the program underneath an otherwise unchanged run, and
the run's result must come out not correct: a train step that returns
its state unchanged, a step that leaves out half of its batch, a served
token altered where the engine produces it.  (The cells run on one chip:
there is no exchange between chips to leave out.)"""
from __future__ import annotations

import os

import jax
import numpy as np
import pytest

import bench_tiny
from bench import harness
from bench import reference as ref


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    return bench_tiny.build(str(tmp_path_factory.mktemp("tiny")))


def _unchanged(make):
    def patched(cfg, tc=None):
        step = make(cfg, tc) if tc is not None else make(cfg)

        def same(params, opt, batch):
            _, _, metrics = step(params, opt, batch)
            return params, opt, metrics
        return same
    return patched


def _half_batch(make):
    def patched(cfg, tc=None):
        step = make(cfg, tc) if tc is not None else make(cfg)

        def half(params, opt, batch):
            rows = jax.tree.leaves(batch)[0].shape[0] // 2
            return step(params, opt, jax.tree.map(lambda x: x[:rows], batch))
        return half
    return patched


def _altered(make):
    def patched(cfg, steps, greedy, use_kernels):
        run = make(cfg, steps, greedy, use_kernels)

        def altered(params, cache, tok, key):
            toks, cache, tok, key = run(params, cache, tok, key)
            return (toks + 1) % cfg.vocab, cache, tok, key
        return altered
    return patched


TRAIN = ["train_solo.qwen2-vl-2b-2l", "train_shared.minicpm-2b-2l"]


@pytest.mark.parametrize("workload", TRAIN)
def test_sound_training_run_is_correct(bench, workload):
    rc, line = bench_tiny.run(bench, workload, seconds=1.0)
    assert rc == 0 and line["correct"], line["checks"]


@pytest.mark.parametrize("fault", [_unchanged, _half_batch],
                         ids=["state_unchanged", "half_batch"])
@pytest.mark.parametrize("workload", TRAIN)
def test_training_fault_is_not_correct(bench, workload, fault, monkeypatch):
    from repro.launch import cluster
    monkeypatch.setattr(cluster, "make_train_step",
                        fault(cluster.make_train_step))
    rc, line = bench_tiny.run(bench, workload, seconds=1.0)
    assert rc == 0 and not line["correct"], line["checks"]


@pytest.mark.parametrize("workload", ["serve_sessions.minicpm-2b"])
def test_altered_token_is_not_correct(bench, workload, monkeypatch):
    from repro.launch import engine
    monkeypatch.setattr(engine, "_make_scan_generate",
                        _altered(engine._make_scan_generate))
    rc, line = bench_tiny.run(bench, workload, seconds=2.0)
    assert rc == 0 and not line["correct"], line["checks"]


def test_training_control_fails_a_limit(bench):
    """The reference in float8 put in the program's place, against the
    float32 reference: at least one number passes its limit."""
    cell = harness.load_cell("train_solo.qwen2-vl-2b-2l", bench=bench)
    tr = cell.traffic
    dm = ref.Dims.of(cell.config)
    opt = ref.Adam(**tr["optimizer"])
    batches = ref.train_batches(dm, 5, 0, tr["batch"], tr["seq"], 3)
    want = ref.run_reference(dm, 5, batches, opt)
    got = ref.run_reference(dm, 5, batches, opt, precision="fp8")
    from bench.drivers.train import _gaps
    gaps = _gaps(got, want)
    limits = cell.workload["limits"]
    assert any(gaps[k] > limits[k] for k in limits), gaps


def test_serving_control_fails_the_limit():
    """Served tokens chosen by the float8 reference, read by the float32
    reference, lie below its best by more than the limit (an 8-layer copy
    at minicpm-2b's widths with an 8,192-token vocabulary; the error
    grows with depth, and reads 1.6-1.7 at all 40 layers on the chip)."""
    conf = harness.load_json(os.path.join(harness.BENCH, "configs",
                                          "minicpm-2b.json"))
    limit = harness.load_json(os.path.join(
        harness.BENCH, "workloads",
        "serve_sessions.minicpm-2b.json"))["limits"]["max_regret"]
    conf.update(num_hidden_layers=8, vocab_size=8192)
    dm = ref.Dims.of(conf)
    rng = np.random.default_rng(0)
    seqs = [list(rng.integers(0, dm.vocab, 112)) for _ in range(3)]
    out = ref.served_logits(dm, 3, seqs, [32] * 3, 128, ["float32", "fp8"])
    gap = max(float(np.max(r["best"] - r["fp8_argmax_logit"]))
              for r in out["float32"])
    assert gap > limit
