"""Kernel autotuner: per-shape-class block/chunk selection with an XLA
fallback, persisted as a versioned artifact (DESIGN.md §15).

The Pallas kernels' tile sizes (``block_q``/``block_k`` for flash
attention, ``block_k`` for flash decode, ``chunk`` for the SSD scan) were
hard-coded; ``BENCH_kernels.json`` shows the kernels losing to the
compiled XLA reference at small shapes under those defaults.  This module
sweeps a candidate grid per *shape class* — (sequence-length bucket ×
head/state dim × dtype) — times every candidate against the XLA
reference path, and records the winner.  When the best Pallas candidate
still trails the reference, the entry records ``backend: "ref"`` and the
wrappers in :mod:`ops` route that shape class to the reference
implementation instead — the tuned-or-fallback choice is never slower
than the hard-coded default, because the default candidate is always in
the measured set.

The winners persist in ``artifacts/bench/autotune.json`` (versioned, like
PR 5's ``calibration.json``).  :mod:`ops` consults the table lazily at
trace time whenever a call site does not pass explicit block sizes, so
every kernel call site (train step, coschedule, serve) picks up tuned
choices with zero API change; with no artifact present the hard-coded
defaults apply unchanged.  Entries are honored only when the table was
tuned on the current jax backend — a CPU-tuned table never disables
kernels on TPU.

Environment override: ``REPRO_AUTOTUNE=/path/to/table.json`` points the
lazy load elsewhere; ``REPRO_AUTOTUNE=0`` (or ``off``) disables the table
entirely (the test suite does this for hermeticity).

Artifact schema (version 2 — version 1 lacked the ``flash_decode_paged``
kind, whose shape classes key on the exact page size rather than a
sequence bucket, so stale tables are invalidated)::

    {"version": 2, "created": ...,
     "meta": {"backend": "cpu"|"tpu", "interpret": bool, "smoke": bool,
              "iters": n},
     "entries": {"<kind>|s<bucket>|d<dim>|<dtype>":
                 {"backend": "kernel"|"ref", <block fields>,
                  "t_best": s, "t_ref": s, "t_default": s,
                  "speedup_vs_default": x}}}
"""
from __future__ import annotations

import json
import os
import time
from typing import Dict, List, Optional, Tuple

from ..util.errors import ArtifactVersionError

AUTOTUNE_VERSION = 2
DEFAULT_PATH = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                            "artifacts", "bench", "autotune.json")

# the hard-coded choices the table replaces (and falls back to).  The
# paged decode kernel has no block knobs — tuning it is a pure
# kernel-vs-reference routing decision per (page_size, head_dim, dtype).
DEFAULTS = {
    "flash_attention": {"block_q": 128, "block_k": 128},
    "flash_decode": {"block_k": 128},
    "flash_decode_paged": {},
    "ssd": {"chunk": 256},
}


# ---------------------------------------------------------------------- #
# shape classes
# ---------------------------------------------------------------------- #
def seq_bucket(s: int) -> int:
    """Next power of two >= s, floored at 64 (one class per octave)."""
    b = 64
    while b < s:
        b *= 2
    return b


def shape_key(kind: str, s: int, d: int, dtype) -> str:
    import numpy as np
    name = np.dtype(dtype).name
    # paged decode keys on the exact page size: page sizes (8/16/32...)
    # sit below the 64-floor sequence bucket and would all collide
    b = int(s) if kind == "flash_decode_paged" else seq_bucket(int(s))
    return f"{kind}|s{b}|d{int(d)}|{name}"


# ---------------------------------------------------------------------- #
# artifact I/O
# ---------------------------------------------------------------------- #
def save_artifact(payload: Dict, path: Optional[str] = None) -> str:
    if payload.get("version") != AUTOTUNE_VERSION:
        raise ValueError(f"refusing to save autotune artifact with version "
                         f"{payload.get('version')!r}")
    path = path or DEFAULT_PATH
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with open(path, "w") as f:
        json.dump(payload, f, indent=1)
    return path


def load_artifact(path: Optional[str] = None) -> Dict:
    path = path or DEFAULT_PATH
    with open(path) as f:
        payload = json.load(f)
    version = payload.get("version")
    if version != AUTOTUNE_VERSION:
        raise ArtifactVersionError(path, version, AUTOTUNE_VERSION,
                                   kind="autotune artifact",
                                   detail="re-run benchmarks/autotune.py "
                                          "to regenerate")
    return payload


class AutotuneTable:
    """In-memory view of the artifact, consulted by :mod:`ops`."""

    def __init__(self, payload: Dict):
        version = payload.get("version")
        if version != AUTOTUNE_VERSION:
            raise ArtifactVersionError("<payload>", version,
                                       AUTOTUNE_VERSION,
                                       kind="autotune artifact")
        for field in ("entries", "meta"):
            if field not in payload:
                raise ArtifactVersionError(
                    "<payload>", version, AUTOTUNE_VERSION,
                    kind="autotune artifact",
                    detail=f"schema missing {field!r}")
        if "backend" not in payload["meta"]:
            raise ArtifactVersionError(
                "<payload>", version, AUTOTUNE_VERSION,
                kind="autotune artifact",
                detail="schema missing meta['backend']")
        self.payload = payload
        self.entries: Dict[str, Dict] = payload["entries"]
        self.backend: str = payload["meta"]["backend"]

    def lookup(self, kind: str, s: int, d: int, dtype) -> Optional[Dict]:
        """Tuned entry for this shape class, or None (caller uses the
        hard-coded defaults).  Entries tuned on a different jax backend
        are ignored: the timings do not transfer."""
        import jax
        if self.backend != jax.default_backend():
            return None
        return self.entries.get(shape_key(kind, s, d, dtype))


# module-level table: lazily loaded from DEFAULT_PATH (or REPRO_AUTOTUNE)
# on first lookup; absent/stale artifacts fall back to None gracefully —
# serving must never fail because a tuning artifact is missing.
_UNSET = object()
_TABLE = _UNSET


def set_table(table: Optional[AutotuneTable]) -> None:
    """Install a table explicitly (None disables all tuned routing)."""
    global _TABLE
    _TABLE = table


def reset_table() -> None:
    """Forget the cached table; next lookup lazily re-reads the env/disk."""
    global _TABLE
    _TABLE = _UNSET


def get_table() -> Optional[AutotuneTable]:
    global _TABLE
    if _TABLE is _UNSET:
        env = os.environ.get("REPRO_AUTOTUNE")
        if env is not None and env.strip().lower() in ("", "0", "off"):
            _TABLE = None
        else:
            path = env or DEFAULT_PATH
            try:
                _TABLE = AutotuneTable(load_artifact(path))
            except (FileNotFoundError, ValueError, KeyError):
                _TABLE = None
    return _TABLE


def lookup(kind: str, s: int, d: int, dtype) -> Optional[Dict]:
    table = get_table()
    return None if table is None else table.lookup(kind, s, d, dtype)


# ---------------------------------------------------------------------- #
# sweep machinery
# ---------------------------------------------------------------------- #
# candidate grids; the DEFAULTS entry is always included so the chosen
# config is >= 1.0x the default by construction (same measurement set)
CANDIDATES = {
    "flash_attention": [(64, 64), (64, 128), (128, 64), (128, 128),
                        (128, 256), (256, 128), (256, 256)],
    "flash_decode": [32, 64, 128, 256],
    "flash_decode_paged": [None],       # no knobs: kernel-vs-ref only
    "ssd": [64, 128, 256],
}
SMOKE_CANDIDATES = {
    "flash_attention": [(64, 64), (128, 128)],
    "flash_decode": [64, 128],
    "flash_decode_paged": [None],
    "ssd": [128, 256],
}

# (s, d) shape classes per kernel; smoke keeps CI fast (interpret mode).
# For paged decode the "s" is the PAGE SIZE (keyed exactly, no bucket).
ATTN_CLASSES = [(256, 32), (256, 64), (512, 64), (1024, 64)]
DECODE_CLASSES = [(128, 32), (256, 64), (512, 64), (1024, 64)]
PAGED_DECODE_CLASSES = [(8, 32), (16, 64), (32, 64), (16, 128)]
SSD_CLASSES = [(256, 16), (512, 32), (1024, 32)]
SMOKE_ATTN_CLASSES = [(128, 32), (256, 32)]
SMOKE_DECODE_CLASSES = [(128, 32)]
SMOKE_PAGED_DECODE_CLASSES = [(8, 32)]
SMOKE_SSD_CLASSES = [(256, 16)]


def _time(fn, args, iters: int, warmup: int = 1) -> float:
    import jax
    for _ in range(warmup):
        out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def _vjp_fn(f):
    import jax

    def run(*args):
        out, pull = jax.vjp(f, *args[:-1])
        return pull(args[-1])
    return run


def _pick(rows: List[Dict], default_cfg: Dict, score_field: str) -> Dict:
    """Winner = argmin score over all measured rows (candidates + ref).
    The returned entry carries the winner's config plus the timing
    triple used by the acceptance check."""
    best = min(rows, key=lambda r: r[score_field])
    t_ref = next(r[score_field] for r in rows if r["backend"] == "ref")
    t_default = next(
        r[score_field] for r in rows
        if r["backend"] == "kernel"
        and all(r[k] == v for k, v in default_cfg.items()))
    entry = {k: v for k, v in best.items() if k not in ("t_fwd",)}
    entry["t_best"] = best[score_field]
    entry["t_ref"] = t_ref
    entry["t_default"] = t_default
    entry["speedup_vs_default"] = t_default / best[score_field]
    return entry


def _tune_flash_attention(classes, candidates, iters: int, interpret: bool):
    import jax
    import jax.numpy as jnp

    from repro.models.attention import full_attention

    from . import flash_attention as _flash

    entries, sweep = {}, {}
    for (s, d) in classes:
        b, h = 1, 4
        ks = jax.random.split(jax.random.PRNGKey(0), 4)
        q, k, v, do = (jax.random.normal(kk, (b, s, h, d)) for kk in ks)

        def kern(bq, bk):
            def f(q, k, v):
                return _flash.flash_attention(
                    q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                    v.transpose(0, 2, 1, 3), causal=True, window=0,
                    block_q=bq, block_k=bk,
                    interpret=interpret).transpose(0, 2, 1, 3)
            return f

        rows = []
        for (bq, bk) in candidates:
            f = kern(bq, bk)
            rows.append({
                "backend": "kernel", "block_q": bq, "block_k": bk,
                "t_fwd": _time(jax.jit(f), (q, k, v), iters),
                "t_fwd_bwd": _time(jax.jit(_vjp_fn(f)), (q, k, v, do),
                                   iters),
            })
        ref = lambda q, k, v: full_attention(q, k, v, causal=True)  # noqa
        rows.append({
            "backend": "ref",
            "t_fwd": _time(jax.jit(ref), (q, k, v), iters),
            "t_fwd_bwd": _time(jax.jit(_vjp_fn(ref)), (q, k, v, do), iters),
        })
        key = shape_key("flash_attention", s, d, jnp.float32)
        # scored on fwd+bwd: training dominates; prefill rides the winner
        entries[key] = _pick(rows, DEFAULTS["flash_attention"], "t_fwd_bwd")
        sweep[key] = {"shape": {"b": b, "s": s, "h": h, "d": d},
                      "rows": rows}
    return entries, sweep


def _tune_flash_decode(classes, candidates, iters: int, interpret: bool):
    import jax
    import jax.numpy as jnp

    from . import flash_decode as _decode
    from . import ref as _ref

    entries, sweep = {}, {}
    for (s, d) in classes:
        b, h = 8, 4
        ks = jax.random.split(jax.random.PRNGKey(1), 3)
        q = jax.random.normal(ks[0], (b, 1, h, d))
        k = jax.random.normal(ks[1], (b, s, h, d))
        v = jax.random.normal(ks[2], (b, s, h, d))
        lengths = jnp.linspace(1, s, b).astype(jnp.int32)

        rows = []
        for bk in candidates:
            def f(q, k, v, lengths, bk=bk):
                return _decode.flash_decode(q, k, v, lengths, block_k=bk,
                                            interpret=interpret)
            rows.append({"backend": "kernel", "block_k": bk,
                         "t": _time(jax.jit(f), (q, k, v, lengths), iters)})
        rows.append({"backend": "ref",
                     "t": _time(jax.jit(_ref.flash_decode_ref),
                                (q, k, v, lengths), iters)})
        key = shape_key("flash_decode", s, d, jnp.float32)
        entries[key] = _pick(rows, DEFAULTS["flash_decode"], "t")
        sweep[key] = {"shape": {"b": b, "s": s, "h": h, "d": d},
                      "rows": rows}
    return entries, sweep


def _tune_flash_decode_paged(classes, candidates, iters: int,
                             interpret: bool):
    """No block knobs to sweep — the decision is purely whether the
    Pallas paged kernel beats the XLA gather+softmax reference at this
    (page_size, head_dim) class."""
    del candidates
    import jax
    import jax.numpy as jnp

    from . import flash_decode as _decode
    from . import ref as _ref

    entries, sweep = {}, {}
    for (ps, d) in classes:
        b, h, h_kv, p_tab = 8, 4, 2, 4
        n_pages = b * p_tab
        ks = jax.random.split(jax.random.PRNGKey(3), 3)
        q = jax.random.normal(ks[0], (b, 1, h, d))
        k_pool = jax.random.normal(ks[1], (n_pages, ps, h_kv * d))
        v_pool = jax.random.normal(ks[2], (n_pages, ps, h_kv * d))
        pages = jnp.arange(n_pages, dtype=jnp.int32).reshape(b, p_tab)
        lengths = jnp.linspace(1, p_tab * ps, b).astype(jnp.int32)

        def kern(q, k_pool, v_pool, pages, lengths):
            return _decode.flash_decode_paged(q, k_pool, v_pool, pages,
                                              lengths, interpret=interpret)

        rows = [
            {"backend": "kernel",
             "t": _time(jax.jit(kern), (q, k_pool, v_pool, pages, lengths),
                        iters)},
            {"backend": "ref",
             "t": _time(jax.jit(_ref.flash_decode_paged_ref),
                        (q, k_pool, v_pool, pages, lengths), iters)},
        ]
        key = shape_key("flash_decode_paged", ps, d, jnp.float32)
        entries[key] = _pick(rows, DEFAULTS["flash_decode_paged"], "t")
        sweep[key] = {"shape": {"b": b, "page_size": ps, "h": h,
                                "h_kv": h_kv, "d": d, "n_pages": n_pages},
                      "rows": rows}
    return entries, sweep


def _tune_ssd(classes, candidates, iters: int, interpret: bool):
    import jax
    import jax.numpy as jnp

    from repro.models.ssm import ssd_chunked

    from . import mamba2_scan as _ssd

    entries, sweep = {}, {}
    for (s, p) in classes:
        b, h, n = 1, 2, p
        ks = jax.random.split(jax.random.PRNGKey(2), 6)
        x = jax.random.normal(ks[0], (b, s, h, p))
        dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)) - 1.0)
        A = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.5)
        Bm = jax.random.normal(ks[3], (b, s, n))
        Cm = jax.random.normal(ks[4], (b, s, n))
        dy = jax.random.normal(ks[5], (b, s, h, p))

        rows = []
        for chunk in candidates:
            def f(x, dt, A, Bm, Cm, chunk=chunk):
                return _ssd.ssd(x, dt, A, Bm, Cm, chunk=chunk,
                                interpret=interpret)
            rows.append({
                "backend": "kernel", "chunk": chunk,
                "t_fwd": _time(jax.jit(f), (x, dt, A, Bm, Cm), iters),
                "t_fwd_bwd": _time(jax.jit(_vjp_fn(f)),
                                   (x, dt, A, Bm, Cm, dy), iters),
            })
        ref = lambda *a: ssd_chunked(*a)  # noqa: E731 — model default chunk
        rows.append({
            "backend": "ref",
            "t_fwd": _time(jax.jit(ref), (x, dt, A, Bm, Cm), iters),
            "t_fwd_bwd": _time(jax.jit(_vjp_fn(ref)),
                               (x, dt, A, Bm, Cm, dy), iters),
        })
        key = shape_key("ssd", s, p, jnp.float32)
        entries[key] = _pick(rows, DEFAULTS["ssd"], "t_fwd_bwd")
        sweep[key] = {"shape": {"b": b, "s": s, "h": h, "p": p, "n": n},
                      "rows": rows}
    return entries, sweep


def run_autotune(smoke: bool = False, iters: Optional[int] = None
                 ) -> Tuple[Dict, Dict]:
    """Sweep every kernel's candidate grid over its shape classes.

    Returns (table_payload, bench_payload): the first is the versioned
    artifact :mod:`ops` consults; the second is the full sweep record for
    ``BENCH_autotune.json`` (every candidate's walltime, the chosen
    config, and its speedup vs the hard-coded default)."""
    import jax

    interpret = jax.default_backend() != "tpu"
    iters = iters if iters is not None else (2 if smoke else 5)
    cands = SMOKE_CANDIDATES if smoke else CANDIDATES
    attn_classes = SMOKE_ATTN_CLASSES if smoke else ATTN_CLASSES
    dec_classes = SMOKE_DECODE_CLASSES if smoke else DECODE_CLASSES
    paged_classes = (SMOKE_PAGED_DECODE_CLASSES if smoke
                     else PAGED_DECODE_CLASSES)
    ssd_classes = SMOKE_SSD_CLASSES if smoke else SSD_CLASSES

    entries: Dict[str, Dict] = {}
    sweep: Dict[str, Dict] = {}
    for tune, classes, cand in (
            (_tune_flash_attention, attn_classes, cands["flash_attention"]),
            (_tune_flash_decode, dec_classes, cands["flash_decode"]),
            (_tune_flash_decode_paged, paged_classes,
             cands["flash_decode_paged"]),
            (_tune_ssd, ssd_classes, cands["ssd"])):
        e, s = tune(classes, cand, iters, interpret)
        entries.update(e)
        sweep.update(s)

    meta = {"backend": jax.default_backend(), "interpret": interpret,
            "smoke": smoke, "iters": iters}
    table_payload = {"version": AUTOTUNE_VERSION, "created": time.time(),
                     "meta": meta, "entries": entries}
    bench_payload = {"meta": meta, "defaults": DEFAULTS, "sweep": sweep,
                     "entries": entries}
    return table_payload, bench_payload
