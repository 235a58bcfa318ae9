"""The program's span recorder (``repro.util.spans``) and the spans the
executor and the serving engine emit at their layer boundaries."""
import dataclasses
import time

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.launch.cluster import JobSpec, ScheduleExecutor
from repro.launch.engine import DecodeEngine
from repro.models import init_params
from repro.util import spans


# ---------------------------------------------------------------------- #
# the recorder
# ---------------------------------------------------------------------- #
def test_recording_off_records_nothing():
    with spans.span("outside", rid=1):
        spans.count("program_builds")
    assert spans._active is None
    with spans.recording() as rec:
        pass
    assert rec.spans == [] and rec.counters == {}


def test_nesting_parents_and_ids():
    with spans.recording() as rec:
        with spans.span("a"):
            with spans.span("b", rid=7, plen=12):
                pass
            with spans.span("c"):
                with spans.span("d"):
                    pass
        with spans.span("e"):
            pass
    names = [s.name for s in rec.spans]
    assert names == ["a", "b", "c", "d", "e"]         # order of entry
    parents = [s.parent for s in rec.spans]
    assert parents == [None, 0, 0, 2, None]
    assert rec.spans[1].ids == {"rid": 7, "plen": 12}
    assert rec.spans[0].ids == {}
    for s in rec.spans:
        assert s.t1 >= s.t0
        if s.parent is not None:
            p = rec.spans[s.parent]
            assert p.t0 <= s.t0 and s.t1 <= p.t1
    assert spans._active is None


def test_self_seconds_leave_out_children():
    with spans.recording() as rec:
        with spans.span("parent"):
            time.sleep(0.01)
            with spans.span("child"):
                time.sleep(0.03)
            with spans.span("child"):
                with spans.span("grandchild"):
                    time.sleep(0.02)
    own = spans.self_seconds(rec.spans)
    parent, c1, c2, g = rec.spans
    assert own[0] == pytest.approx(parent.seconds - c1.seconds - c2.seconds)
    assert own[2] == pytest.approx(c2.seconds - g.seconds)
    assert own[3] == pytest.approx(g.seconds)
    assert 0.009 <= own[0] < parent.seconds - 0.05
    assert sum(own) == pytest.approx(parent.seconds)


def test_counters_and_notes_while_recording():
    with spans.recording() as rec:
        spans.count("program_builds")
        spans.count("program_builds", 2)
        spans.count("other")
    assert rec.counters == {"program_builds": 3, "other": 1}


def test_a_span_that_raises_is_recorded_and_closed():
    with spans.recording() as rec:
        with pytest.raises(ValueError):
            with spans.span("outer"):
                with spans.span("inner"):
                    raise ValueError("boom")
        with spans.span("after"):
            pass
    assert [(s.name, s.parent) for s in rec.spans] == [
        ("outer", None), ("inner", 0), ("after", None)]


def test_one_recording_at_a_time():
    with spans.recording():
        with pytest.raises(RuntimeError):
            with spans.recording():
                pass
    assert spans._active is None


def test_named_names_the_xla_module():
    fn = jax.jit(spans.named("decode_segment", lambda x: x + 1))
    assert "jit_decode_segment" in fn.lower(np.float32(1)).as_text()


# ---------------------------------------------------------------------- #
# the program's spans
# ---------------------------------------------------------------------- #
def _cfg():
    return dataclasses.replace(get_config("minicpm-2b").reduced(),
                               dtype="float32")


def _children(rec, i):
    return [s for s in rec.spans if s.parent == i]


def _covered(rec, i, slack=0.1):
    """The direct children of span ``i`` lie inside it and leave little of
    it uncovered."""
    p = rec.spans[i]
    kids = _children(rec, i)
    assert all(p.t0 <= k.t0 and k.t1 <= p.t1 for k in kids)
    ends = [(k.t0, k.t1) for k in kids]
    assert all(a[1] <= b[0] for a, b in zip(ends, ends[1:])), "overlap"
    gap = p.seconds - sum(k.seconds for k in kids)
    assert gap <= max(slack * p.seconds, 2e-3), (p.name, gap, p.seconds)


def test_executor_spans_cover_the_call_and_count_builds():
    cfg = _cfg()
    ex = ScheduleExecutor(donate=True)
    ex.submit("A", JobSpec(cfg, batch=2, seq=16), 100)
    ex.submit("B", JobSpec(cfg, batch=2, seq=16, seed=1), 100)
    ex.start("A")
    ex.start("B", sub_batch=1)
    with spans.recording() as rec:
        for _ in range(3):
            ex.step_group(["A", "B"])
        ex.step_group(["A"])                  # a new composition
    tops = [i for i, s in enumerate(rec.spans)
            if s.name == "executor.step_group"]
    assert len(tops) == 4
    assert [rec.spans[i].ids for i in tops] == [
        {"call": 0, "group": "A+B"}, {"call": 1, "group": "A+B"},
        {"call": 2, "group": "A+B"}, {"call": 3, "group": "A"}]
    for i in tops:
        kids = [k.name for k in _children(rec, i)]
        assert kids == ["executor.prepare", "executor.dispatch",
                        "executor.wait", "executor.readback",
                        "executor.bookkeep"]
    # steady calls: the children cover the call
    for i in tops[1:3]:
        _covered(rec, i)
    builds = [s for s in rec.spans if s.name == "executor.build"]
    assert [rec.spans[b.parent].name for b in builds] == [
        "executor.prepare"] * 2
    assert [b.ids["group"] for b in builds] == ["A+B", "A"]
    assert rec.counters["program_builds"] == 2
    assert ex.compiles == 2


def test_engine_spans_cover_the_segment_and_name_admissions():
    cfg = _cfg()
    params = init_params(cfg, jax.random.PRNGKey(0))
    eng = DecodeEngine(cfg, params, n_slots=2, max_len=64, segment=4,
                       paged=True, page_size=8, prefix_share=True)
    rng = np.random.default_rng(0)
    system = rng.integers(0, cfg.vocab, 16).astype(np.int32)

    def prompt(n):
        return np.concatenate([system, rng.integers(0, cfg.vocab, n)
                               ]).astype(np.int32)
    eng.submit(prompt(4), 6)                  # a miss: whole prefill
    eng.run()
    with spans.recording() as rec:
        rids = [eng.submit(prompt(4), 6), eng.submit(prompt(4), 6)]
        eng.run()                             # hits: gather + extend
        eng.submit(prompt(4), 6)
        eng.run()                             # same shapes again
    tops = [i for i, s in enumerate(rec.spans)
            if s.name == "engine.step_segment"]
    assert [rec.spans[i].ids["segment"] for i in tops] == list(
        range(2, 2 + len(tops)))
    order = ["engine.shed", "engine.admit", "engine.grow",
             "engine.dispatch", "engine.wait", "engine.bookkeep"]
    for i in tops:
        kids = [k.name for k in _children(rec, i)]
        assert [n for n in order if n in kids] == sorted(
            set(kids), key=order.index)
        assert kids.count("engine.dispatch") == kids.count(
            "engine.wait") == 1
    admits = [s for s in rec.spans if s.name == "engine.admit"]
    assert [a.ids["rid"] for a in admits] == rids + [rids[-1] + 1]
    assert all(a.ids["plen"] == 20 for a in admits)
    # each matches the system prompt's two pages, and no own token
    assert all(a.ids["matched"] == 16 for a in admits)
    # the first hit builds its gather, extend and one-page scatter once;
    # the rest of the window builds nothing
    builds = [(s.ids["kind"], s.ids["shape"]) for s in rec.spans
              if s.name == "engine.build"]
    assert sorted(builds) == [("gather_prefix", "2"),
                              ("prefill_extend", "(16, 4)"),
                              ("scatter_pages", "1")]
    assert rec.counters["program_builds"] == 3
    assert all(rec.spans[s.parent].name == "engine.admit"
               for s in rec.spans if s.name == "engine.build")
    # a segment that builds nothing is covered by its children
    for i in tops[-2:]:
        _covered(rec, i)


class _Late:
    """Tokens that reach the host ``delay`` seconds after the dispatch
    returned, as a slow device's do."""

    def __init__(self, toks, delay):
        self.toks, self.delay = toks, delay

    def __array__(self, dtype=None, copy=None):
        time.sleep(self.delay)
        return np.asarray(self.toks, dtype)


def test_segment_time_runs_from_dispatch_to_tokens():
    cfg = _cfg()
    params = init_params(cfg, jax.random.PRNGKey(0))
    eng = DecodeEngine(cfg, params, n_slots=1, max_len=32, segment=4)
    eng.submit(np.arange(4, dtype=np.int32), 4)
    eng.run()                                 # compiled
    seg = eng._segment_fn

    def slow(params, cache, tok):
        toks, cache, tok = seg(params, cache, tok)
        return _Late(toks, 0.25), cache, tok
    eng._segment_fn = slow
    eng._seg_ewma = 0.0
    eng.submit(np.arange(4, dtype=np.int32), 8)
    eng.run()
    assert eng._seg_ewma >= 0.25
    # a shed request's back-off hint reads the slow segment
    eng.submit(np.arange(4, dtype=np.int32), 4, deadline=-1.0)
    eng.step_segment()
    (hint,) = eng.retry_after.values()
    assert hint >= 0.25
