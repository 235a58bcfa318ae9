"""The benchmark's harness: finds a cell's files by name, checks for the
chip, keeps the compile cache, times the window, reads the trace, and
prints the result line.

Everything that belongs to one cell, configuration, traffic mix or
per-layer metric is a file of its own, found by the name that
``BENCHMARK.json`` gives:

    bench/workloads/<cell>.json     driver, configuration, traffic, limits
    bench/configs/<config>.json     the model as it is run, and its source
    bench/traffic/<traffic>.json    parameters the driver's generator reads
    bench/drivers/<driver>.py       ``run(h) -> dict``: one way of driving
                                    the program
    bench/metrics/<metric>.py       ``read(run) -> float | None``; a metric
                                    ``a.b`` falls back to ``a.py``

A later cell, configuration or metric is added as new files only.
"""
from __future__ import annotations

import contextlib
import importlib.util
import json
import os
import shutil
import statistics
import sys
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

BENCH = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(BENCH)
TRACE_SECONDS = 20.0        # longest traced window; a run's own may be shorter


def load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def say(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------------- #
# specs
# ---------------------------------------------------------------------- #
@dataclass
class Cell:
    """One cell as its files state it."""

    name: str
    workload: Dict[str, Any]
    traffic: Dict[str, Any]
    configs: Dict[str, Dict[str, Any]]      # every configuration it names
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]
    bench: str = BENCH

    @property
    def config(self) -> Dict[str, Any]:
        return self.configs[self.workload["config"]]


def _applies(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, *, bench: str = BENCH,
              manifest: Optional[str] = None) -> Cell:
    """Read a cell's files.  ``manifest`` is ``BENCHMARK.json``; its
    metric entries say which metrics the cell reports."""
    manifest = manifest or os.path.join(os.path.dirname(bench),
                                        "BENCHMARK.json")
    spec = load_json(manifest)
    entry = next((w for w in spec["workloads"] if w["name"] == name), None)
    if entry is None:
        raise SystemExit(f"bench: no workload {name!r} in {manifest}")
    workload = load_json(os.path.join(bench, "workloads", name + ".json"))
    for key in ("config", "traffic", "chips"):
        if workload[key] != entry[key]:
            raise SystemExit(f"bench: {name}: {key} {workload[key]!r} in "
                             f"its file, {entry[key]!r} in {manifest}")
    traffic = load_json(os.path.join(bench, "traffic",
                                     workload["traffic"] + ".json"))
    names = [workload["config"]] + list(traffic.get("configs", []))
    configs = {c: load_json(os.path.join(bench, "configs", c + ".json"))
               for c in names}
    return Cell(name=name, workload=workload, traffic=traffic,
                configs=configs,
                end_to_end=[m for m in spec["end_to_end"]
                            if _applies(m, name)],
                per_layer=[m for m in spec["per_layer"]
                           if _applies(m, name)],
                bench=bench)


def reader_for(metric: str, bench: str = BENCH):
    """The reader module of a per-layer metric: ``metrics/<name>.py``,
    else ``metrics/<name before the first dot>.py``."""
    for stem in (metric, metric.split(".")[0]):
        path = os.path.join(bench, "metrics", stem + ".py")
        if os.path.exists(path):
            return load_module(path, "bench_metric_" + stem.replace(".", "_"))
    raise SystemExit(f"bench: no reader for metric {metric!r}")


def peaks_for(kind: str, bench: str = BENCH) -> Dict[str, Any]:
    """Published peaks of one chip of ``kind``; an unknown kind raises."""
    table = load_json(os.path.join(bench, "peaks.json"))
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


# ---------------------------------------------------------------------- #
# device and compile cache
# ---------------------------------------------------------------------- #
def require_chips(chips: int) -> Dict[str, Any]:
    """Device info; exits non-zero unless JAX finds ``chips`` TPU chips."""
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if info["platform"] != "tpu" or info["count"] < chips:
        say(f"bench: needs {chips} TPU chip(s), JAX found {info}")
        raise SystemExit(3)
    return info


def compile_cache(checkout: str = CHECKOUT) -> str:
    """JAX's persistent compilation cache at its fixed place
    (``$JAX_COMPILATION_CACHE_DIR``, else ``<checkout>/.jax_cache``),
    keeping every program so that only a cell's first run compiles."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        checkout, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # no eviction: a cell's programs, evicted, compile again in every run
    jax.config.update("jax_compilation_cache_max_size", -1)
    return path


class CompileCounter:
    """Counts XLA compilations and persistent-cache loads, through JAX's
    monitoring events."""

    def __init__(self):
        import jax
        self.compiles = 0
        self.cache_hits = 0
        self.cache_misses = 0

        def event(name, **_):
            if name == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1
            elif name == "/jax/compilation_cache/cache_misses":
                self.cache_misses += 1

        def duration(name, secs, **_):
            if name == "/jax/core/compile/backend_compile_duration":
                self.compiles += 1
        jax.monitoring.register_event_listener(event)
        jax.monitoring.register_event_duration_secs_listener(duration)

    def snapshot(self) -> Dict[str, int]:
        return {"compiles": self.compiles, "cache_hits": self.cache_hits,
                "cache_misses": self.cache_misses}


def memory_peak_bytes() -> Optional[int]:
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.local_devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


# ---------------------------------------------------------------------- #
# the run
# ---------------------------------------------------------------------- #
@dataclass
class Harness:
    """What a driver is handed: the cell, the run's arguments, and the
    means to time its window and record host spans."""

    cell: Cell
    seed: int
    seconds: float
    trace: bool
    calibrate: bool = False
    workdir: str = ""
    t_start: float = field(default_factory=time.perf_counter)
    spans: List[tuple] = field(default_factory=list)
    window_span: Optional[tuple] = None
    window_compiles: Optional[Dict[str, int]] = None
    counter: Optional[CompileCounter] = None

    @property
    def window_seconds(self) -> float:
        return min(self.seconds, TRACE_SECONDS) if self.trace else self.seconds

    @contextlib.contextmanager
    def span(self, name: str):
        """A host span: kept in memory, and written into the profiler's
        trace while it records."""
        import jax
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(name):
            yield
        self.spans.append((name, t0, time.perf_counter()))

    @contextlib.contextmanager
    def window(self):
        """The measured window.  With ``trace`` the profiler records it;
        compilations inside it are counted."""
        import jax
        before = self.counter.snapshot() if self.counter else None
        if self.trace:
            jax.profiler.start_trace(self.trace_dir)
        t0 = time.perf_counter()
        try:
            with self.span("bench.window"):
                yield
        finally:
            t1 = time.perf_counter()
            if self.trace:
                jax.profiler.stop_trace()
            self.window_span = (t0, t1)
            if before is not None:
                after = self.counter.snapshot()
                self.window_compiles = {k: after[k] - before[k]
                                        for k in after}

    @property
    def trace_dir(self) -> str:
        return os.path.join(self.workdir, "trace")

    def setup_done(self) -> float:
        """Seconds from the process's start of set-up to now."""
        return time.perf_counter() - self.t_start


@dataclass
class Run:
    """What a per-layer metric reader reads."""

    cell: Cell
    counters: Dict[str, Any]
    spans: List[tuple]
    window_s: float
    trace: Optional[Dict[str, Any]]
    peaks: Dict[str, Any]


def percentile(values: List[float], q: float) -> float:
    """The ``q``-th percentile (0-100), by linear interpolation between
    order statistics; ``inf`` entries count as the largest values."""
    xs = sorted(values)
    if not xs:
        return float("nan")
    pos = (len(xs) - 1) * q / 100.0
    lo, hi = int(pos), min(int(pos) + 1, len(xs) - 1)
    if xs[hi] == float("inf"):
        return float("inf") if pos > lo or xs[lo] == float("inf") else xs[lo]
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def spread(values: List[float]) -> float:
    """Interquartile distance as a share of the median."""
    q1, med, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / med


def main(argv=None, *, bench: str = BENCH, require_chip: bool = True) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--calibrate", type=int, choices=(0, 1), default=0,
                    help="also read the lower-precision control and the "
                         "planted faults against the reference (used to "
                         "set the limits; never by a measured run)")
    args = ap.parse_args(argv)
    t_start = time.perf_counter()
    cell = load_cell(args.workload, bench=bench)
    sys.path.insert(0, os.path.join(os.path.dirname(bench), "src"))
    if require_chip:
        info = require_chips(cell.workload["chips"])
    else:
        import jax
        d = jax.devices()
        info = {"platform": d[0].platform, "kind": d[0].device_kind,
                "count": len(d)}
    checkout = os.path.dirname(bench)
    cache = compile_cache(checkout)
    say(f"bench: device {json.dumps(info)}; compile cache {cache}")
    workdir = os.path.join(checkout, ".bench_run")
    shutil.rmtree(workdir, ignore_errors=True)
    os.makedirs(workdir)
    h = Harness(cell=cell, seed=args.seed, seconds=args.seconds,
                trace=bool(args.trace), calibrate=bool(args.calibrate),
                workdir=workdir, t_start=t_start, counter=CompileCounter())
    driver = load_module(os.path.join(bench, "drivers",
                                      cell.workload["driver"] + ".py"),
                         "bench_driver_" + cell.workload["driver"])
    try:
        out = driver.run(h)
        return finish(h, out, info, bench)
    finally:
        shutil.rmtree(workdir, ignore_errors=True)


def finish(h: Harness, out: Dict[str, Any], info: Dict[str, Any],
           bench: str) -> int:
    """Reduce the run to its metrics and print the result line."""
    cell = h.cell
    t0, t1 = h.window_span
    say(f"bench: window {t1 - t0:.3f} s; compiles inside it "
        f"{json.dumps(h.window_compiles)}")
    for k, v in sorted(out.get("counters", {}).items()):
        if not isinstance(v, (list, dict)):
            say(f"bench: counter {k} = {v}")
    device = dict(info, memory_peak_bytes=out["memory_peak_bytes"])
    metrics: Dict[str, Any] = {}
    breakdown = None
    if h.trace:
        from bench.tracing import reduce_trace
        kernel_map = load_json(os.path.join(bench, "kernels.json"))
        tr = reduce_trace(h.trace_dir, kernel_map)
        device["busy_s"], device["window_s"] = tr["busy_s"], tr["window_s"]
        breakdown = {"device_ops": tr["device_ops"][:10],
                     "idle_gaps": tr["idle_gaps"][:10]}
        run = Run(cell=cell, counters=out.get("counters", {}),
                  spans=h.spans, window_s=t1 - t0, trace=tr,
                  peaks=peaks_for(info["kind"], bench))
        for m in cell.per_layer:
            value = reader_for(m["name"], bench).read(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        say(f"bench: trace busy {tr['busy_s']:.4f} s of "
            f"{tr['window_s']:.4f} s; kernels "
            f"{json.dumps(tr['kernel_seconds'])}")
    else:
        for m in cell.end_to_end:
            if m["name"] == "setup_s":
                value = out["setup_s"]
            else:
                value = out["end_to_end"][m["name"]]
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    checks = out["checks"]
    correct = bool(out["correct"]) and all(
        c["value"] <= c["limit"] for c in checks.values())
    line = {"correct": correct, "attempted": out["attempted"],
            "failed": out["failed"], "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = checks
    for k, v in out.get("calibration", {}).items():
        say(f"bench: calibration {k} = {v}")
    for k, c in checks.items():
        say(f"bench: check {k} = {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(line), flush=True)
    return 0
