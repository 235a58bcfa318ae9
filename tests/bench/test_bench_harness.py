"""The benchmark's harness: traffic, latency timing, the chip check, and
cells, configurations and metrics added as files only."""
from __future__ import annotations

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import bench_tiny
from bench import harness, traffic
from bench.drivers import serve

TRAFFIC = os.path.join(harness.BENCH, "traffic")
SERVING = ["sessions", "offline"]
SEEDS = [1, 2 ** 31 + 5, 3_000_000_017]


def _params(name):
    return harness.load_json(os.path.join(TRAFFIC, name + ".json"))


@pytest.mark.parametrize("name", SERVING)
def test_generator_is_deterministic_per_seed(name):
    p = _params(name)
    a = traffic.generate(p, SEEDS[1], 122753, 50)
    b = traffic.generate(p, SEEDS[1], 122753, 50)
    c = traffic.generate(p, SEEDS[2], 122753, 50)
    assert [r.arrival for r in a] == [r.arrival for r in b]
    assert all(np.array_equal(x.tokens, y.tokens) for x, y in zip(a, b))
    assert [r.new_tokens for r in a] == [r.new_tokens for r in b]
    assert not all(np.array_equal(x.tokens, y.tokens) for x, y in zip(a, c))
    # every seed asks for the same sizes and gaps, in its own order
    for key in ("new_tokens", "shared"):
        assert sorted(getattr(r, key) for r in a) == sorted(
            getattr(r, key) for r in c)
    assert sorted(len(r.tokens) for r in a) == sorted(len(r.tokens)
                                                      for r in c)
    assert [r.arrival for r in a] == [r.arrival for r in c]


@pytest.mark.parametrize("name", SERVING)
@pytest.mark.parametrize("seed", SEEDS)
def test_every_prompt_shape_is_warmed(name, seed):
    p = _params(name)
    shapes = traffic.prompt_shapes(p)
    reqs = traffic.generate(p, seed, 122753, 50)
    firsts = set()
    for r in reqs:
        assert len(r.tokens) in shapes["miss"]
        if r.shared:
            assert (r.shared, len(r.tokens) - r.shared) in shapes["hit"]
        own = int(r.tokens[r.shared])
        assert own not in firsts         # no request's text starts alike
        firsts.add(own)
    systems = {tuple(r.tokens[:r.shared]) for r in reqs if r.shared}
    assert len({s[0] for s in systems}) == len(systems)


def test_open_loop_latency_runs_from_scheduled_arrival():
    arrival = np.array([0.0, 1.0, 2.0])
    first = np.array([1.5, 1.8, np.inf])     # request 0 submitted late
    last = np.array([3.5, 9.0, np.inf])
    new = np.array([5, 3, 4])
    due = np.array([True, True, True])
    ttft, tpot = serve.latencies(arrival, first, last, new, due, window=4.0)
    assert ttft[:2] == pytest.approx([1.5, 0.8])
    assert ttft[2] == float("inf")           # never came: counts as missing
    assert tpot == pytest.approx([(3.5 - 1.5) / 4])   # 1 completed in window
    assert harness.percentile([1.0, 2.0, float("inf")], 90) == float("inf")


def test_run_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(bench_tiny.ROOT, "bench", "run.py"),
         "--workload", "train_solo.qwen2-vl-2b-2l", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=bench_tiny.ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert proc.returncode != 0
    assert "correct" not in proc.stdout


def test_manifest_and_files_agree():
    spec = harness.load_json(os.path.join(bench_tiny.ROOT, "BENCHMARK.json"))
    for w in spec["workloads"]:
        cell = harness.load_cell(w["name"])
        assert any(m["name"] == "setup_s" for m in cell.end_to_end)
        assert len(cell.end_to_end) >= 2 and cell.per_layer
        for m in cell.per_layer:
            assert m["moves"] in {e["name"] for e in cell.end_to_end}
            harness.reader_for(m["name"])
    for c in spec["configs"]:
        conf = harness.load_json(os.path.join(bench_tiny.ROOT, c["file"]))
        assert conf["name"] == c["name"] and conf["source"] == c["source"]
        assert sorted(conf["reduced"]) == sorted(c["reduced"])


EXTRA_READER = '''
"""Tokens per segment: a metric that exists only as this file."""


def read(run):
    segs = run.counters.get("segment_s")
    if not segs:
        return None
    return run.counters["tokens"] / len(segs)
'''


def test_cell_config_and_metric_added_as_files_only(tmp_path):
    bench = bench_tiny.build(str(tmp_path))
    # a new configuration, traffic mix, cell and per-layer metric
    conf = harness.load_json(os.path.join(bench, "configs", "minicpm-2b.json"))
    conf.update(name="extra-1l", num_hidden_layers=1)
    json.dump(conf, open(os.path.join(bench, "configs", "extra-1l.json"),
                         "w"))
    mix = harness.load_json(os.path.join(bench, "traffic", "offline.json"))
    mix["prompt"] = {"lengths": [32], "weights": [1]}
    json.dump(mix, open(os.path.join(bench, "traffic", "extra_mix.json"),
                        "w"))
    wl = harness.load_json(os.path.join(
        bench, "workloads", "serve_offline.minicpm-2b.json"))
    wl.update(config="extra-1l", traffic="extra_mix")
    json.dump(wl, open(os.path.join(bench, "workloads",
                                    "extra.extra-1l.json"), "w"))
    with open(os.path.join(bench, "metrics", "tokens_per_segment.py"),
              "w") as f:
        f.write(EXTRA_READER)
    manifest = os.path.join(str(tmp_path), "BENCHMARK.json")
    spec = harness.load_json(manifest)
    spec["configs"].append({"name": "extra-1l", "source": conf["source"],
                            "file": "bench/configs/extra-1l.json",
                            "reduced": [], "why": "a test"})
    spec["workloads"].append({"name": "extra.extra-1l", "config": "extra-1l",
                              "traffic": "extra_mix", "chips": 1,
                              "why": "a test"})
    spec["end_to_end"].insert(0, {
        "name": "serve_tokens_per_s", "unit": "tokens/s", "better": "higher",
        "bound": 0.05, "source": "host_clock",
        "workloads": ["extra.extra-1l"]})
    spec["per_layer"].append({"name": "tokens_per_segment", "unit": "tokens",
                              "better": "higher", "source": "program_span",
                              "layer": "serving loop",
                              "moves": "serve_tokens_per_s",
                              "workloads": ["extra.extra-1l"]})
    json.dump(spec, open(manifest, "w"))

    cell = harness.load_cell("extra.extra-1l", bench=bench)
    assert cell.config["num_hidden_layers"] == 1
    assert [m["name"] for m in cell.per_layer if
            m["name"] == "tokens_per_segment"]
    rc, line = bench_tiny.run(bench, "extra.extra-1l", seconds=2.0)
    assert rc == 0 and line["correct"], line
    assert line["metrics"]["serve_tokens_per_s"]["value"] > 0
    assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    reader = harness.reader_for("tokens_per_segment", bench)
    run = harness.Run(cell=cell, counters={"segment_s": [0.1, 0.1],
                                           "tokens": 30},
                      spans=[], window_s=1.0, trace=None, peaks={})
    assert reader.read(run) == 15
