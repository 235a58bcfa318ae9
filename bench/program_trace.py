"""The program's own names in a traced run, read from the profiler trace
that the harness recorded: its host spans (``engine.*``, ``executor.*``,
from ``repro.util.spans``), its jitted programs, its Pallas kernels and
its named scopes.

``of(run)`` reads the trace once per run and gives, beside what
``bench.tracing`` gives:

- ``host``: the host spans, ``(start_ns, dur_ns, name)`` on the
  profiler's clock, the harness's ``bench.*`` and the program's own;
- ``idle_gaps``: idle time named by the innermost of those spans open at
  a gap's midpoint, so that a gap inside a program call takes the
  program's name (``bench.tracing``'s own breakdown knows ``bench.*``
  alone);
- ``program_seconds``: device time per jitted program, from the device
  plane's ``XLA Modules`` line (``jit_decode_segment(<id>)`` ->
  ``decode_segment``);
- ``kernel_seconds_by_name``: device time per Pallas kernel, whose HLO
  instruction takes the kernel's ``name`` (``%flash_attention_dq.3 = ...
  custom_call_target="tpu_custom_call"`` -> ``flash_attention_dq``);
- ``scope_seconds``: device time per named scope.  The trace's ops carry
  no metadata, so an op's ``op_name`` is looked up in the HLO of its
  program (the module whose interval holds it), which the profiler keeps
  as an ``Hlo Proto`` stat in the ``/host:metadata`` plane.

A program without these names reads empty tables, so that the metrics
that need them read nothing.  ``kernels.json`` is read as it is.
"""
from __future__ import annotations

import bisect
import glob
import json
import os
import re
from collections import defaultdict
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from bench import tracing
from bench.harness import load_json, say

MODULE_LINE = "XLA Modules"
METADATA_PLANE = "/host:metadata"
HOST_SPANS = ("bench.", "engine.", "executor.")
# the path parts that JAX itself puts into an ``op_name`` (control flow,
# calls, remat, custom rules); every other plain name on the path, other
# than the primitive at its end, a program (``jit(...)``) or a Pallas
# kernel (the part before ``pallas_call``), is a ``jax.named_scope``
JAX_PARTS = re.compile(r"while|body|cond|branch_\d+_fun|closed_call|core_call"
                       r"|checkpoint|remat\d*|rematted_computation|scan"
                       r"|custom_jvp_call|custom_vjp_call(?:_jaxpr)?"
                       r"|shard_map|pmap|xla_pmap|xla_call|pallas_call")
# a path part: a plain name, perhaps inside transforms (``transpose(jvp(
# forward))``); einsum equations and qualified function names are not
WRAPPED = re.compile(r"((?:\w+\()*)([A-Za-z_]\w*)\)*")

Host = List[Tuple[int, int, str]]


def trace_dir(run) -> str:
    """Where the harness keeps a traced run's profile until its readers
    have run (``Harness.trace_dir`` under ``<checkout>/.bench_run``)."""
    return os.path.join(os.path.dirname(run.cell.bench), ".bench_run",
                        "trace")


def load_events(trace_dir: str) -> Optional[Dict[str, Any]]:
    """{"device": {plane: [(start_ns, dur_ns, name)]}, "host": [...],
    "modules": {plane: [...]}, "op_names": {module: {op: op_name}}} from
    the newest trace under ``trace_dir``, or None where there is none."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        return None
    data = ProfileData.from_file(paths[-1])
    device: Dict[str, Host] = {}
    modules: Dict[str, Host] = {}
    host: Host = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:") and "SparseCore" not in \
                plane.name:
            for line in plane.lines:
                into = {tracing.DEVICE_LINE: device,
                        MODULE_LINE: modules}.get(line.name)
                if into is not None:
                    into[plane.name] = [
                        (int(e.start_ns), int(e.duration_ns), e.name)
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(HOST_SPANS):
                        host.append((int(e.start_ns), int(e.duration_ns),
                                     e.name))
    with open(paths[-1], "rb") as f:
        op_names = hlo_op_names(f.read())
    return {"device": device, "host": host, "modules": modules,
            "op_names": op_names}


# ---------------------------------------------------------------------- #
# the HLO the profiler keeps: a protobuf walk over the few fields needed
# ---------------------------------------------------------------------- #
def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    x = shift = 0
    while True:
        b = buf[i]
        i += 1
        x |= (b & 0x7F) << shift
        if b < 0x80:
            return x, i
        shift += 7


def _fields(buf: bytes, lo: int = 0, hi: int | None = None):
    """(field number, value) of one protobuf message in ``buf[lo:hi]``; a
    length-delimited value is its (start, end) in ``buf``."""
    i, hi = lo, len(buf) if hi is None else hi
    while i < hi:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            v, i = (i, i + n), i + n
        elif wire in (1, 5):
            n = 8 if wire == 1 else 4
            v, i = None, i + n
        else:
            raise ValueError(f"protobuf wire type {wire} at {i}")
        yield key >> 3, v


def _text(buf: bytes, span: Tuple[int, int]) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def _hlo_op_names(buf: bytes, lo: int, hi: int) -> Dict[str, str]:
    """{instruction: op_name} of a serialized ``HloProto``: hlo_module (1)
    > computations (3) > instructions (2) > name (1) and metadata (7) >
    op_name (2)."""
    ops = {}
    for f, mod in _fields(buf, lo, hi):
        if f != 1:
            continue
        for g, v in _fields(buf, *mod):
            if g == 3:
                for h, ins in _fields(buf, *v):
                    if h != 2:
                        continue
                    iname = op = None
                    for k, w in _fields(buf, *ins):
                        if k == 1:
                            iname = _text(buf, w)
                        elif k == 7:
                            op = next((_text(buf, x) for m, x in
                                       _fields(buf, *w) if m == 2), None)
                    if iname and op:
                        ops[iname] = op
    return ops


def hlo_op_names(xspace: bytes) -> Dict[str, Dict[str, str]]:
    """{module: {HLO instruction: op_name}} from a serialized ``XSpace``:
    every ``Hlo Proto`` stat of the ``/host:metadata`` plane (XSpace
    planes (1) > XPlane name (2), event_metadata (4) > stats (5) >
    bytes_value (6); stat_metadata (5) names the stat).  A module is
    keyed by the event metadata's name (2), which is the name the
    device's ``XLA Modules`` line gives it, program id included
    (``jit_prefill(123)``), so two compilations of one function keep
    their own tables.  {} where the trace keeps no HLO."""
    out: Dict[str, Dict[str, str]] = {}
    for f, plane in _fields(xspace):
        if f != 1:
            continue
        fields = list(_fields(xspace, *plane))
        if not any(g == 2 and _text(xspace, v) == METADATA_PLANE
                   for g, v in fields):
            continue
        entries = {g: [dict(_fields(xspace, *v)) for h, v in fields
                       if h == g] for g in (4, 5)}   # map entries: key 1, value 2
        hlo_stat = {e[1] for e in entries[5] if _text(xspace, dict(
            _fields(xspace, *e[2])).get(2, (0, 0))) == "Hlo Proto"}
        for e in entries[4]:
            meta = list(_fields(xspace, *e[2]))
            for h, stat in meta:
                if h != 5:
                    continue
                st = dict(_fields(xspace, *stat))
                if st.get(1) in hlo_stat and 6 in st:
                    key = next((_text(xspace, v) for g, v in meta
                                if g == 2), "")
                    out[key] = _hlo_op_names(xspace, *st[6])
    return out


# ---------------------------------------------------------------------- #
# names
# ---------------------------------------------------------------------- #
def program_stem(module: str) -> str:
    """``jit_decode_segment(123)`` -> ``decode_segment``."""
    return re.sub(r"^jit_|\(.*$", "", module)


def scope_of(op_name: str | None) -> str:
    """The program's named scopes on an op's ``op_name`` path, joined by
    ``/``: the path's plain names less the primitive at its end, programs
    (``jit(...)``), Pallas kernels and ``JAX_PARTS``.  A scope under
    ``transpose(...)`` (autodiff's backward pass) reads ``backward`` for
    ``forward`` and ``<scope>.backward`` for any other.  ``none`` where
    the op has none."""
    parts = (op_name or "").split(";")[0].split("/")
    out = []
    for i, part in enumerate(parts[:-1]):
        m = WRAPPED.fullmatch(part)
        if (not m or m.group(1).startswith(("jit(", "pjit("))
                or JAX_PARTS.fullmatch(m.group(2))
                or parts[i + 1] == "pallas_call"):
            continue
        name = m.group(2)
        if m.group(1).startswith("transpose("):
            name = "backward" if name == "forward" else f"{name}.backward"
        out.append(name)
    return "/".join(out) or "none"


def kernel_name(name: str) -> str | None:
    """The Pallas kernel an op runs (its HLO instruction's name without
    the numeric suffix), or None for an op that is not a Pallas kernel."""
    if 'custom_call_target="tpu_custom_call"' not in name:
        return None
    return re.sub(r"\.\d+$", "", name.split(" = ", 1)[0].lstrip("%"))


# ---------------------------------------------------------------------- #
# the reduction
# ---------------------------------------------------------------------- #
def reduce_events(events: Dict[str, Any], kernel_map: Dict[str, Any]
                  ) -> Dict[str, Any]:
    """The tables of the module docstring from ``load_events``' events."""
    host = events["host"]
    win = [(s, s + d) for s, d, n in host if n == tracing.WINDOW]
    dev_all = [iv for evs in events["device"].values() for iv in evs]
    if win:
        w0, w1 = win[0]
    else:
        w0 = min(s for s, _, _ in dev_all)
        w1 = max(s + d for s, d, _ in dev_all)
    by_program, by_kernel, by_scope = (defaultdict(float) for _ in range(3))
    op_names = events.get("op_names", {})
    scopes: Dict[Tuple[str, str], str] = {}   # (module, op) -> scope
    for plane, evs in events["device"].items():
        mods = sorted(events.get("modules", {}).get(plane, []))
        m_lo = np.array([s for s, _, _ in mods], np.int64)
        m_hi = np.array([s + d for s, d, _ in mods], np.int64)
        for s, d, name in mods:
            a, b = max(s, w0), min(s + d, w1)
            if b > a:
                by_program[program_stem(name)] += b - a
        for s, d, name in evs:
            a, b = max(s, w0), min(s + d, w1)
            if b <= a or tracing.op_family(name) in tracing.CONTAINERS:
                continue
            kn = kernel_name(name)
            if kn:
                by_kernel[kn] += b - a
            if op_names:
                j = int(np.searchsorted(m_lo, s, "right")) - 1
                mod = mods[j][2] if j >= 0 and s < m_hi[j] else ""
                if (mod, name) not in scopes:
                    op = name.split(" = ", 1)[0].lstrip("%")
                    scopes[mod, name] = scope_of(
                        op_names.get(mod, {}).get(op))
                by_scope[scopes[mod, name]] += b - a
    n = len(events["device"])

    def per_chip(d):
        return {k: v / n / 1e9 for k, v in sorted(d.items(),
                                                   key=lambda kv: -kv[1])}
    return {"host": host,
            "idle_gaps": tracing.reduce_events(events,
                                               kernel_map)["idle_gaps"],
            "program_seconds": per_chip(by_program),
            "kernel_seconds_by_name": per_chip(by_kernel),
            "scope_seconds": per_chip(by_scope)}


def mean_less_child_ms(host: Host, parent: str, children) -> float | None:
    """Mean over the host spans called ``parent`` of their time less that
    of the spans called ``children`` (a name or a tuple of names) that
    they hold, in ms; None where no span is called ``parent``."""
    if isinstance(children, str):
        children = (children,)
    kids = sorted((s, s + d) for s, d, n in host if n in children)
    starts = [a for a, _ in kids]
    left = []
    for s, d, n in host:
        if n != parent:
            continue
        own = d
        i = bisect.bisect_left(starts, s)
        while i < len(kids) and kids[i][0] < s + d:
            if kids[i][1] <= s + d:
                own -= kids[i][1] - kids[i][0]
            i += 1
        left.append(own)
    return 1e-6 * sum(left) / len(left) if left else None


def span_table(host: Host) -> Dict[str, List[float]]:
    """{span name: [count, mean ms, total s, self s]}; self time is what
    the spans it holds directly leave uncovered."""
    out: Dict[str, List[float]] = {}
    stack: List[Tuple[int, List[float]]] = []     # (end, parent's row)
    for s, d, n in sorted(host, key=lambda e: (e[0], -e[1])):
        while stack and stack[-1][0] <= s:
            stack.pop()
        if stack:
            stack[-1][1][3] -= d / 1e9
        row = out.setdefault(n, [0, 0.0, 0.0, 0.0])
        row[0] += 1
        row[2] += d / 1e9
        row[3] += d / 1e9
        stack.append((s + d, row))
    for row in out.values():
        row[1] = 1e3 * row[2] / row[0]
    return out


_last: List[Any] = [None, None]       # (the run's trace table, of(run))


def of(run) -> Optional[Dict[str, Any]]:
    """The program's names in a traced run (module docstring), read once
    per run and written to stderr; None for an untraced run."""
    if run.trace is None:
        return None
    if _last[0] is run.trace:
        return _last[1]
    events = load_events(trace_dir(run))
    out = None
    if events is not None:
        out = reduce_events(events, load_json(os.path.join(
            run.cell.bench, "kernels.json")))
        for key in ("idle_gaps", "program_seconds", "kernel_seconds_by_name",
                    "scope_seconds"):
            say(f"bench: program trace {key} {json.dumps(out[key])}")
        say(f"bench: program spans {json.dumps(span_table(out['host']))}")
    _last[:] = [run.trace, out]
    return out

