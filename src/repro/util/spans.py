"""Host spans and counters on the profiler's clock.

``span(name, **ids)`` marks a layer boundary of the program: it always
enters ``jax.profiler.TraceAnnotation(name, **ids)``, so that a profiler
trace shows the span on the same clock as the device's ops, with ``ids``
as the event's stats.  Inside ``recording()`` it also keeps the span in
memory, and ``count`` keeps counters:

    with spans.recording() as rec:
        ...                         # code that opens spans
    rec.spans      # [Span(name, t0, t1, parent, ids)], in order of entry
    rec.counters   # {name: int}

``t0`` and ``t1`` are ``time.perf_counter`` seconds; ``parent`` is the
index in ``rec.spans`` of the span that was open when this one began, or
None.  Outside ``recording()`` a span costs one ``TraceAnnotation`` and
nothing else, and ``count`` costs a global read.  One
recording is open at a time, and spans nest on one thread: the parent
stack is the recording's, not the thread's.

``named`` gives a function the name that the device trace shows for its
jitted program (the XLA module is ``jit_<name>``).
"""
from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass, field
from typing import (Any, Callable, ContextManager, Dict, Iterator, List,
                    NamedTuple, Optional)

import jax


class Span(NamedTuple):
    name: str
    t0: float
    t1: float
    parent: Optional[int]
    ids: Dict[str, Any]

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


@dataclass
class Recording:
    """What was recorded between entering and leaving ``recording()``."""

    spans: List[Span] = field(default_factory=list)
    counters: Dict[str, Any] = field(default_factory=dict)
    _open: List[int] = field(default_factory=list)


_active: Optional[Recording] = None


@contextlib.contextmanager
def recording() -> Iterator[Recording]:
    """Record spans and counters for the block; yields the record."""
    global _active
    if _active is not None:
        raise RuntimeError("a recording is already open")
    rec = _active = Recording()
    try:
        yield rec
    finally:
        _active = None


def span(name: str, **ids) -> ContextManager[None]:
    """A host span: a ``TraceAnnotation``, and a record while recording.
    Outside a recording it is the ``TraceAnnotation`` itself, so that a
    span allocates no generator."""
    rec = _active
    if rec is None:
        return jax.profiler.TraceAnnotation(name, **ids)
    return _recorded(rec, name, ids)


@contextlib.contextmanager
def _recorded(rec: Recording, name: str, ids: Dict[str, Any]
              ) -> Iterator[None]:
    i = len(rec.spans)
    rec.spans.append(None)
    parent = rec._open[-1] if rec._open else None
    rec._open.append(i)
    t0 = time.perf_counter()
    try:
        with jax.profiler.TraceAnnotation(name, **ids):
            yield
    finally:
        rec.spans[i] = Span(name, t0, time.perf_counter(), parent, ids)
        rec._open.pop()


def count(name: str, n: int = 1) -> None:
    """Add ``n`` to counter ``name`` while recording."""
    rec = _active
    if rec is not None:
        rec.counters[name] = rec.counters.get(name, 0) + n


def self_seconds(spans: List[Span]) -> List[float]:
    """Each span's time not covered by its direct children."""
    out = [s.seconds for s in spans]
    for s in spans:
        if s.parent is not None:
            out[s.parent] -= s.seconds
    return out


def named(name: str, fn: Callable) -> Callable:
    """``fn`` renamed ``name``, so that ``jax.jit(fn)`` compiles to the
    XLA module ``jit_<name>``."""
    fn.__name__ = fn.__qualname__ = name
    return fn
