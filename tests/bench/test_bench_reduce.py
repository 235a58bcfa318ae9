"""The benchmark's yardstick: trace reduction, required-work counts and
the table of peaks."""
from __future__ import annotations

import glob
import gzip
import json
import os
import re

import pytest

import bench_tiny  # noqa: F401  (puts the checkout on sys.path)
from bench import harness
from bench.costs import dense, flash_attention, flash_decode_paged
from bench.reference import Dims
from bench.tracing import reduce_events

DATA = os.path.join(os.path.dirname(__file__), "data")
KERNELS = harness.load_json(os.path.join(harness.BENCH, "kernels.json"))


def test_reduce_synthetic_events():
    events = {
        "device": {"/device:TPU:0": [(10, 10, "fusion.1"), (15, 15, "fusion.2"),
                                     (40, 10, "custom-call.3")]},
        "host": [(0, 100, "bench.window"), (0, 25, "bench.step_segment"),
                 (22, 13, "bench.submit")],
    }
    kmap = {"mine": ["custom-call"]}
    tr = reduce_events(events, kmap)
    assert tr["window_s"] == pytest.approx(100e-9)
    assert tr["busy_s"] == pytest.approx(30e-9)       # [10, 30) and [40, 50)
    assert tr["kernel_seconds"] == {"mine": pytest.approx(10e-9)}
    gaps = dict(tr["idle_gaps"])
    # [0, 10): inside step_segment only; [30, 40): midpoint 35 is past
    # submit's end; [50, 100): no span
    assert gaps["bench.step_segment"] == pytest.approx(10e-9)
    assert gaps["bench.none"] == pytest.approx(60e-9)
    ops = dict(tr["device_ops"])
    assert ops["fusion"] == pytest.approx(25e-9)
    assert ops["mine"] == pytest.approx(10e-9)


def _naive(events, kmap):
    """Busy time and kernel time by a plain loop over nanoseconds' edges."""
    (w0, wd), = [(s, d) for s, d, n in events["host"] if n == "bench.window"]
    w1 = w0 + wd
    busy, kern = [], {}
    for evs in events["device"].values():
        ivs = sorted((max(s, w0), min(s + d, w1)) for s, d, _ in evs
                     if min(s + d, w1) > max(s, w0))
        total, end = 0, None
        for a, b in ivs:
            if end is None or a > end:
                total += b - a
                end = b
            elif b > end:
                total += b - end
                end = b
        busy.append(total)
        for s, d, name in evs:
            a, b = max(s, w0), min(s + d, w1)
            for kernel, pats in kmap.items():
                if not kernel.startswith("_") and any(
                        re.search(p, name) for p in pats):
                    if b > a:
                        kern[kernel] = kern.get(kernel, 0) + b - a
                    break                   # the first kernel that matches
    n = len(events["device"])
    return sum(busy) / n / 1e9, {k: v / n / 1e9 for k, v in kern.items()}


@pytest.mark.parametrize("path", sorted(glob.glob(os.path.join(
    DATA, "trace_*.json.gz"))))
def test_reduce_recorded_chip_trace(path):
    with gzip.open(path, "rt") as f:
        events = json.load(f)
    tr = reduce_events(events, KERNELS)
    busy, kern = _naive(events, KERNELS)
    assert 0 < tr["busy_s"] <= tr["window_s"]
    assert tr["busy_s"] == pytest.approx(busy, rel=1e-9)
    assert tr["kernel_seconds"] == pytest.approx(kern, rel=1e-9)
    assert kern, "no kernel of kernels.json found in the recorded trace"
    idle = sum(v for _, v in tr["idle_gaps"])
    assert idle == pytest.approx(tr["window_s"] - tr["busy_s"], rel=1e-6)


def test_recorded_traces_exist():
    assert glob.glob(os.path.join(DATA, "trace_*.json.gz"))


def _dims(**kw):
    base = dict(d=4, layers=3, heads=2, kv_heads=1, head_dim=2, ffn=8,
                vocab=10, eps=1e-5, theta=1e4, tied=True)
    base.update(kw)
    return Dims(**base)


def test_dense_costs_by_hand():
    d = _dims()
    # q 4x4, k 4x2, v 4x2, o 4x4, gate/up 4x8, down 8x4
    assert dense.layer_params(d) == 16 + 8 + 8 + 16 + 96
    # 3 queries at positions 0..2 see 1 + 2 + 3 keys
    assert dense.attention_pairs(0, 3) == 6
    assert dense.attention_pairs(2, 4) == 3 + 4
    # batch 2 x seq 3: 6 tokens x 2 x 144 per layer x 3 layers,
    # attention 4 x 2 seqs x 6 pairs x 2 heads x 2 dims x 3 layers,
    # head 2 x 6 tokens x 4 x 10
    fwd = 2 * 6 * 144 * 3 + 4 * 2 * 6 * 2 * 2 * 3 + 2 * 6 * 4 * 10
    assert dense.forward_flops(d, 2, 3) == fwd
    assert dense.train_step_flops(d, 2, 3) == 3 * fwd
    # rows 2, 3 of a prompt: 2 rows of products, pairs 3 + 4, one head row
    assert dense.prefill_flops(d, 2, 4) == (2 * 2 * 144 * 3
                                            + 4 * 7 * 2 * 2 * 3 + 2 * 4 * 10)
    assert dense.decode_flops(d, 5) == 2 * 144 * 3 + 4 * 5 * 4 * 3 + 80


def test_kernel_costs_by_hand():
    d = _dims()
    f, b = flash_attention.forward(d, 2, 3)
    assert f == 4 * 2 * 6 * 2 * 2
    assert b == 2 * (2 * 2 * 3 * 4 + 2 * 2 * 3 * 2)   # q, o at 4; k, v at 2
    f, b = flash_attention.backward(d, 2, 3)
    assert f == 2 * 4 * 2 * 6 * 2 * 2
    assert b == 2 * (4 * 2 * 3 * 4 + 4 * 2 * 3 * 2)
    f, b = flash_decode_paged.step(d, 7)
    assert f == 4 * 7 * 2 * 2
    assert b == 2 * (2 * 7 * 1 * 2 + 2 * 2 * 2)


def test_peaks_by_device_kind():
    v5e = harness.peaks_for("TPU v5 lite")
    assert v5e["bf16_flop_per_s"] == 197e12
    assert v5e["hbm_byte_per_s"] == 819e9
    assert "source" in v5e
    with pytest.raises(KeyError):
        harness.peaks_for("TPU v9 imaginary")
