"""A tiny copy of the benchmark for CPU tests: the repository's bench files
with every configuration, traffic mix and engine cut to a few dozen
widths, so that a whole run takes seconds with the kernels interpreted."""
from __future__ import annotations

import json
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

SMALL = dict(hidden_size=128, intermediate_size=256, vocab_size=512)


def _edit(path: str, **kw) -> None:
    with open(path) as f:
        c = json.load(f)
    c.update(kw)
    with open(path, "w") as f:
        json.dump(c, f, indent=1)


def build(dst: str) -> str:
    """Copy the benchmark under ``dst`` and cut it down; returns the copy's
    bench directory."""
    bench = os.path.join(dst, "bench")
    shutil.copytree(os.path.join(ROOT, "bench"), bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), dst)

    def at(rel):
        return os.path.join(bench, rel)
    _edit(at("configs/minicpm-2b.json"), num_attention_heads=4,
          num_key_value_heads=4, head_dim=32, num_hidden_layers=2, **SMALL)
    _edit(at("configs/minicpm-2b-2l.json"), num_attention_heads=4,
          num_key_value_heads=4, head_dim=32, **SMALL)
    _edit(at("configs/qwen2-vl-2b-2l.json"), num_attention_heads=4,
          num_key_value_heads=2, head_dim=32, vision_tokens=64,
          rope_scaling={"type": "mrope", "mrope_section": [4, 6, 6]},
          **SMALL)
    for t in ("plan_shared", "plan_solo"):
        _edit(at(f"traffic/{t}.json"), seq=64)
    _edit(at("traffic/sessions.json"),
          tenants={"prompt_lengths": [64, 32, 32, 16], "zipf": 1.0},
          suffix={"lengths": [16, 32], "weights": [0.6, 0.4]},
          output={"median": 20, "sigma": 0.5, "min": 17, "max": 40},
          arrivals={"rate": 4.0, "cv": 2.0})
    _edit(at("traffic/offline.json"), requests=40,
          prompt={"lengths": [16, 32, 64], "weights": [1, 1, 1]},
          output={"median": 20, "sigma": 0.5, "min": 17, "max": 40})
    for w in ("serve_sessions", "serve_offline"):
        _edit(at(f"workloads/{w}.minicpm-2b.json"),
              engine={"n_slots": 4, "max_len": 256, "segment": 8,
                      "page_size": 16, "n_pages": 64},
              check={"tokens": 60, "max_requests": 3})
    return bench


def run(bench: str, workload: str, seed: int = 3_000_000_001,
        seconds: float = 3.0, calibrate: int = 0):
    """One run of a tiny cell on the CPU, the chip check skipped; returns
    (exit code, the result line)."""
    import contextlib
    import io

    import jax
    from jax.experimental.compilation_cache import compilation_cache

    from bench.harness import main
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes",
            "jax_compilation_cache_max_size")
    saved = {k: getattr(jax.config, k) for k in keys}
    out = io.StringIO()
    try:
        with contextlib.redirect_stdout(out):
            rc = main(["--workload", workload, "--seed", str(seed),
                       "--seconds", str(seconds), "--trace", "0",
                       "--calibrate", str(calibrate)],
                      bench=bench, require_chip=False)
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()
    return rc, json.loads(out.getvalue().strip().splitlines()[-1])
