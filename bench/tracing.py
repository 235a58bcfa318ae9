"""Reduction of a profiler trace to device busy time, kernel time and idle
gaps.

``load_events`` reads the ``.xplane.pb`` that ``jax.profiler`` writes into
plain event lists on the profiler's one clock; ``reduce_events`` does the
arithmetic, so that a recorded trace can be checked without the chip:

- busy: the union of the intervals in which an XLA op ran on a chip,
  inside the harness's ``bench.window`` span, averaged over the chips;
- kernel seconds: the summed device durations of the ops whose names
  match a kernel's patterns in ``kernels.json``;
- idle gaps: the complement of busy inside the window, each gap named by
  the innermost harness span open on the host at its midpoint.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from typing import Any, Dict, List, Tuple

import numpy as np

WINDOW = "bench.window"
DEVICE_LINE = "XLA Ops"
# ops whose events span the ops of their bodies, which the trace also
# records: counted in busy time, left out of the breakdown
CONTAINERS = {"while", "conditional", "call"}


def load_events(trace_dir: str) -> Dict[str, Any]:
    """{"device": {plane: [(start_ns, dur_ns, name)]}, "host": [...]} from
    the newest trace under ``trace_dir``.  Host events are the harness's
    own spans (names starting ``bench.``)."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    device: Dict[str, List[Tuple[int, int, str]]] = {}
    host: List[Tuple[int, int, str]] = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:") and "SparseCore" not in \
                plane.name:
            for line in plane.lines:
                if line.name == DEVICE_LINE:
                    device[plane.name] = [
                        (int(e.start_ns), int(e.duration_ns), e.name)
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        host.append((int(e.start_ns), int(e.duration_ns),
                                     e.name))
    return {"device": device, "host": host}


def _union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[List[int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def kernel_of(name: str, kernel_map: Dict[str, Any]) -> str | None:
    """The first kernel (in the map's order) one of whose patterns is
    found in the op's name, which on a TPU is its HLO instruction."""
    for kernel, patterns in kernel_map.items():
        if kernel.startswith("_"):
            continue
        if any(re.search(p, name) for p in patterns):
            return kernel
    return None


def op_family(name: str) -> str:
    """A short label for an op: its HLO instruction's name without the
    numeric suffix (``%fusion.12 = ...`` -> ``fusion``), with a fusion's
    kind, so the breakdown groups instances of one op."""
    head = name.split(" = ", 1)[0].lstrip("%")
    label = re.sub(r"[.\-_]?\d+$", "", head) or head
    kind = re.search(r"kind=(k\w+)", name)
    if kind:
        label += f"[{kind.group(1)}]"
    if "tpu_custom_call" in name:
        label = f"custom-call[{label}]"
    return label


def reduce_events(events: Dict[str, Any], kernel_map: Dict[str, Any]
                  ) -> Dict[str, Any]:
    host = events["host"]
    win = [(s, s + d) for s, d, n in host if n == WINDOW]
    dev_all = [iv for evs in events["device"].values() for iv in evs]
    if not dev_all:
        raise ValueError("the trace holds no device op")
    if win:
        w0, w1 = win[0]
    else:
        w0 = min(s for s, _, _ in dev_all)
        w1 = max(s + d for s, d, _ in dev_all)
    window_ns = w1 - w0
    busy_ns, kernel_ns = [], defaultdict(float)
    by_op = defaultdict(float)
    gaps: Dict[str, float] = defaultdict(float)
    # innermost (shortest) host span first
    spans = sorted(((s, s + d, n) for s, d, n in host if n != WINDOW),
                   key=lambda t: t[1] - t[0])
    names: Dict[str, Tuple[str, str]] = {}    # op -> (kernel, breakdown)
    for evs in events["device"].values():
        ivs = []
        for s, d, name in evs:
            a, b = max(s, w0), min(s + d, w1)
            if b <= a:
                continue
            ivs.append((a, b))
            if name not in names:
                k = kernel_of(name, kernel_map)
                names[name] = (k or "", k or op_family(name))
            k, fam = names[name]
            kernel_ns[k] += b - a
            if fam not in CONTAINERS:
                by_op[fam] += b - a
        merged = _union(ivs)
        busy_ns.append(sum(b - a for a, b in merged))
        edges = np.array([w0] + [x for iv in merged for x in iv] + [w1],
                         np.float64)
        lo, hi = edges[0::2], edges[1::2]
        keep = hi > lo
        lo, hi = lo[keep], hi[keep]
        mid = (lo + hi) / 2
        order = np.argsort(mid)
        mid, length = mid[order], (hi - lo)[order]
        owner = np.full(len(mid), -1)
        for i, (s, e, _) in enumerate(spans):
            a, b = np.searchsorted(mid, [s, e])
            free = owner[a:b] < 0
            owner[a:b][free] = i
        for i in np.unique(owner):
            name = spans[i][2] if i >= 0 else "bench.none"
            gaps[name] += float(length[owner == i].sum())
    n = len(events["device"])
    kernel_ns.pop("", None)
    return {
        "busy_s": sum(busy_ns) / n / 1e9,
        "window_s": window_ns / 1e9,
        "kernel_seconds": {k: v / n / 1e9 for k, v in sorted(
            kernel_ns.items())},
        "device_ops": [[k, v / n / 1e9] for k, v in sorted(
            by_op.items(), key=lambda kv: -kv[1])],
        "idle_gaps": [[k, v / n / 1e9] for k, v in sorted(
            gaps.items(), key=lambda kv: -kv[1])],
    }


def reduce_trace(trace_dir: str, kernel_map: Dict[str, Any]
                 ) -> Dict[str, Any]:
    return reduce_events(load_events(trace_dir), kernel_map)
