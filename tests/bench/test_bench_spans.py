"""The program's names in a traced run (``bench/program_trace.py``:
programs, Pallas kernels, named scopes, the program's own host spans) and
the four per-layer metrics that read them."""
from __future__ import annotations

import glob
import gzip
import json
import os

import pytest

import bench_tiny  # noqa: F401  (puts the checkout on sys.path)
from bench import harness, program_trace, tracing
from bench.program_trace import (hlo_op_names, kernel_name,
                                 mean_less_child_ms, program_stem,
                                 reduce_events, scope_of, span_table)

DATA = os.path.join(os.path.dirname(__file__), "data")
KERNELS = harness.load_json(os.path.join(harness.BENCH, "kernels.json"))

FWD = ('%flash_attention_fwd.36 = (bf16[8,256,64]) custom-call(bf16[8,256,'
       '64] %bitcast.993), custom_call_target="tpu_custom_call", '
       'operand_layout_constraints={bf16[8,256,64]{2,1,0}}')
DQ = ('%flash_attention_dq.2 = bf16[8,256,64] custom-call(bf16[8,256,64] '
      '%bitcast.986), custom_call_target="tpu_custom_call"')


def test_names_read_from_hlo_and_modules():
    assert program_stem("jit_decode_segment(12421778962411245467)") == \
        "decode_segment"
    assert program_stem("jit_group_step") == "group_step"
    assert kernel_name(FWD) == "flash_attention_fwd"
    assert kernel_name(DQ) == "flash_attention_dq"
    assert kernel_name("%copy.38 = bf16[512,16,2304] copy(%p)") is None
    assert kernel_name('%custom-call.25 = bf16[4] custom-call(), '
                       'custom_call_target="AllocateBuffer"') is None


@pytest.mark.parametrize("op_name, scope", [
    ("jit(group_step)/member0/jvp(forward)/while/body/dot_general",
     "member0/forward"),
    ("jit(group_step)/member1/transpose(jvp(forward))/while/body/"
     "closed_call/checkpoint/rematted_computation/flash_attention_fwd",
     "member1/backward"),
    ("jit(group_step)/member0/while/body/accumulate/add", "member0/accumulate"),
    ("jit(group_step)/member1/optimizer/mul", "member1/optimizer"),
    ("jit(decode_segment)/while/body/closed_call/attention/kv_write/scatter",
     "attention/kv_write"),
    ("mlp/dot_general;jit(decode_segment)/attention/add", "mlp"),
    ("jit(decode_segment)/head/dot_general", "head"),
    ("jit(prefill)/while/body/dot_general", "none"),
    ("", "none"),
    (None, "none"),
    # JAX's own parts, einsum equations, function names, jnp helpers and
    # Pallas kernels are not scopes; any other name on the path is
    ("jit(decode_segment)/while/body/closed_call/attention/bgjd,jk->bgjkd/"
     "dot_general", "attention"),
    ("jit(decode_segment)/while/body/closed_call/_make_scan_generate."
     "<locals>.decode_segment.<locals>.body/reduce", "none"),
    ("jit(decode_segment)/while/body/closed_call/attention/"
     "flash_decode_paged/pallas_call", "attention"),
    ("jit(group_step)/member0/cond/branch_1_fun/jit(silu)/logistic",
     "member0"),
    ("jit(group_step)/member0/router/topk/sort", "member0/router/topk"),
    ("jit(group_step)/transpose(jvp(router))/mul", "router.backward"),
])
def test_scope_of_op_name(op_name, scope):
    assert scope_of(op_name) == scope


def _varint(n):
    out = bytearray()
    while True:
        b, n = n & 0x7F, n >> 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _field(num, value):
    if isinstance(value, int):
        return _varint(num << 3) + _varint(value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(num << 3 | 2) + _varint(len(value)) + value


def _msg(*fields):
    return b"".join(_field(n, v) for n, v in fields)


def _xspace(modules):
    """A serialized XSpace whose metadata plane holds one ``Hlo Proto`` per
    module: [(module name, {instruction: op_name or None})], its event
    metadata named ``<module>(<id>)``, as the profiler names it."""
    events = []
    for k, (mod, instrs) in enumerate(modules):
        ins = [_msg((1, name), (2, "fusion"),
                    *([(7, _msg((1, "add"), (2, op)))] if op else []))
               for name, op in instrs.items()]
        comp = _msg((1, "main"), *[(2, i) for i in ins])
        hlo = _msg((1, _msg((1, mod), (3, comp))))
        stat = _msg((1, 7), (6, hlo))
        em = _msg((1, k + 1), (2, f"{mod}({k + 1})"), (5, stat))
        events.append(_msg((1, k + 1), (2, em)))
    stat_meta = _msg((1, 7), (2, _msg((1, 7), (2, "Hlo Proto"))))
    metadata = _msg((1, 3), (2, "/host:metadata"),
                    *[(4, e) for e in events], (5, stat_meta))
    other = _msg((1, 4), (2, "/device:TPU:0"), (3, _msg((1, 1))))
    return _msg((1, other), (1, metadata))


def test_hlo_op_names_walk_a_serialized_xspace():
    mods = [("jit_decode_segment", {"fusion.1": "attention/dot",
                                    "copy.2": None}),
            ("jit_group_step", {"fusion.1": "member0/optimizer/add"}),
            ("jit_prefill", {"fusion.1": "mlp/dot"}),
            ("jit_prefill", {"fusion.1": "head/dot"})]
    want = {"jit_decode_segment(1)": {"fusion.1": "attention/dot"},
            "jit_group_step(2)": {"fusion.1": "member0/optimizer/add"},
            # two compilations of one function keep their own tables
            "jit_prefill(3)": {"fusion.1": "mlp/dot"},
            "jit_prefill(4)": {"fusion.1": "head/dot"}}
    assert hlo_op_names(_xspace(mods)) == want
    assert hlo_op_names(_msg((1, _msg((2, "/host:CPU"))))) == {}


def _events():
    """A window [0, 1000) with two programs on the device, a Pallas
    kernel, ops with and without metadata, and program spans on the
    host."""
    dev = [(100, 50, FWD), (150, 50, DQ), (200, 100, "%fusion.1 = f32[] "
                                                     "fusion(), kind=kLoop"),
           (600, 100, "%copy.3 = bf16[] copy(%p)"),
           (700, 100, "%fusion.1 = f32[] fusion(), kind=kLoop")]
    mods = [(100, 200, "jit_group_step(99)"),
            (600, 200, "jit_decode_segment(42)")]
    host = [(0, 1000, "bench.window"), (0, 400, "executor.step_group"),
            (0, 100, "executor.prepare"), (100, 250, "executor.wait"),
            (350, 40, "executor.readback"), (500, 400, "engine.step_segment"),
            (520, 60, "engine.admit"), (950, 30, "bench.step_segment")]
    ops = {"jit_group_step(99)": {
        "flash_attention_fwd.36": "member0/jvp(forward)/x",
        "flash_attention_dq.2": "member0/transpose(jvp(forward))/x",
        "fusion.1": "member0/optimizer/add"},
        "jit_decode_segment(42)": {"fusion.1": "head/dot"}}
    return {"device": {"/device:TPU:0": dev}, "host": host,
            "modules": {"/device:TPU:0": mods}, "op_names": ops}


def test_reduce_names_programs_kernels_scopes_and_gaps():
    tr = reduce_events(_events(), KERNELS)
    ns = 1e-9
    assert tr["program_seconds"] == {"group_step": pytest.approx(200 * ns),
                                     "decode_segment":
                                     pytest.approx(200 * ns)}
    assert tr["kernel_seconds_by_name"] == {
        "flash_attention_fwd": pytest.approx(50 * ns),
        "flash_attention_dq": pytest.approx(50 * ns)}
    # the same instruction name reads its own program's metadata
    assert tr["scope_seconds"] == {
        "member0/optimizer": pytest.approx(100 * ns),
        "head": pytest.approx(100 * ns),
        "none": pytest.approx(100 * ns),
        "member0/forward": pytest.approx(50 * ns),
        "member0/backward": pytest.approx(50 * ns)}
    gaps = dict(tr["idle_gaps"])
    # [0, 100): prepare; [300, 600): midpoint 450 in no program span
    # but past step_group's end at 400 -> bench.none; [800, 1000): the
    # midpoint 900 is at engine.step_segment's end -> none of them
    assert gaps["executor.prepare"] == pytest.approx(100 * ns)
    assert gaps["bench.none"] == pytest.approx(500 * ns)
    assert sum(gaps.values()) == pytest.approx(600 * ns)
    assert tr["host"] == _events()["host"]


def test_reduce_keeps_compilations_of_one_function_apart():
    ev = _events()
    ev["modules"]["/device:TPU:0"] = [(100, 200, "jit_prefill(1)"),
                                      (600, 200, "jit_prefill(2)")]
    ev["op_names"] = {"jit_prefill(1)": {"fusion.1": "mlp/add"},
                      "jit_prefill(2)": {"fusion.1": "head/add"}}
    tr = reduce_events(ev, KERNELS)
    assert tr["program_seconds"] == {"prefill": pytest.approx(400e-9)}
    scopes = tr["scope_seconds"]
    assert scopes["mlp"] == pytest.approx(100e-9)
    assert scopes["head"] == pytest.approx(100e-9)
    assert scopes["none"] == pytest.approx(200e-9)


def test_reduce_without_program_names_reads_as_before():
    """A program without names (modules ``jit_run``, no HLO metadata, no
    program spans) reads empty tables, and ``bench.tracing`` reads the
    same whatever host spans the program adds, but for the gaps' names."""
    ev = _events()
    del ev["modules"], ev["op_names"]
    tr = reduce_events(ev, KERNELS)
    assert tr["program_seconds"] == {} and tr["scope_seconds"] == {}
    assert set(tr["kernel_seconds_by_name"]) == {"flash_attention_fwd",
                                                 "flash_attention_dq"}
    bench_only = dict(ev, host=[h for h in ev["host"]
                                if h[2].startswith("bench.")])
    full, old = (tracing.reduce_events(e, KERNELS) for e in (ev, bench_only))
    assert {k: v for k, v in full.items() if k != "idle_gaps"} == \
        {k: v for k, v in old.items() if k != "idle_gaps"}
    assert dict(old["idle_gaps"]) == {"bench.none": pytest.approx(600e-9)}


FIXTURES = sorted(glob.glob(os.path.join(DATA, "trace_*.spans.json.gz")))


@pytest.mark.parametrize("path", FIXTURES)
def test_recorded_chip_trace_with_program_names(path):
    with gzip.open(path, "rt") as f:
        events = json.load(f)
    tr = reduce_events(events, KERNELS)
    base = tracing.reduce_events(events, KERNELS)
    busy = base["busy_s"]
    assert 0 < busy <= base["window_s"]
    # every op's time goes to one scope, and the module line holds the ops
    assert sum(tr["scope_seconds"].values()) == pytest.approx(
        sum(v for _, v in base["device_ops"]), rel=1e-9)
    assert sum(tr["program_seconds"].values()) >= busy * (1 - 1e-6)
    # the Pallas kernels read by name are the ones kernels.json finds
    assert sum(tr["kernel_seconds_by_name"].values()) == pytest.approx(
        sum(base["kernel_seconds"].values()), rel=1e-9)
    # the idle time is the same, and nearly all of it takes a program
    # span's name
    gaps = dict(tr["idle_gaps"])
    assert sum(gaps.values()) == pytest.approx(
        sum(v for _, v in base["idle_gaps"]), rel=1e-9)
    named = sum(v for k, v in gaps.items() if k.startswith(
        ("engine.", "executor.")))
    assert named >= 0.9 * sum(gaps.values())
    if "serve" in os.path.basename(path):
        assert "decode_segment" in tr["program_seconds"]
        assert "flash_decode_paged" in tr["kernel_seconds_by_name"]
        assert {"attention", "attention/kv_write", "mlp", "head"} <= set(
            tr["scope_seconds"])
    else:
        assert "group_step" in tr["program_seconds"]
        assert {"flash_attention_fwd", "flash_attention_dq",
                "flash_attention_dkv"} <= set(tr["kernel_seconds_by_name"])
        assert {"member0/forward", "member0/backward",
                "member0/optimizer"} <= set(tr["scope_seconds"])


def test_recorded_chip_traces_with_program_names_exist():
    assert len(FIXTURES) >= 2




# ---------------------------------------------------------------------- #
# the host spans
# ---------------------------------------------------------------------- #
STEP = [(0, 10_000_000, "bench.step_group"),
        (0, 10_000_000, "executor.step_group"),
        (0, 2_000_000, "executor.prepare"),
        (2_000_000, 7_000_000, "executor.wait"),
        (10_000_000, 6_000_000, "executor.step_group"),
        (11_000_000, 4_000_000, "executor.wait"),
        (20_000_000, 10_000_000, "executor.wait")]       # not inside one


def test_mean_less_child():
    assert mean_less_child_ms(STEP, "executor.step_group",
                              "executor.wait") == pytest.approx(2.5)
    assert mean_less_child_ms(STEP, "executor.step_group",
                              ("executor.prepare", "executor.wait")
                              ) == pytest.approx(1.5)
    assert mean_less_child_ms(STEP, "engine.step_segment",
                              "engine.wait") is None


def test_span_table_counts_means_and_self_time():
    t = span_table(STEP)
    assert t["executor.step_group"] == pytest.approx([2, 8.0, 0.016, 0.003])
    assert t["bench.step_group"] == pytest.approx([1, 10.0, 0.010, 0.0])
    assert t["executor.wait"] == pytest.approx([3, 7.0, 0.021, 0.021])


def test_load_events_reads_program_spans_from_a_profile(tmp_path):
    """A profile that ``jax.profiler`` wrote holds the program's spans on
    the host, by name, beside the harness's."""
    import jax
    import jax.numpy as jnp
    from repro.util import spans
    f = jax.jit(spans.named("decode_segment", lambda x: x * 2))
    f(jnp.ones(4)).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            with spans.span("engine.step_segment", segment=0):
                with spans.span("engine.wait"):
                    f(jnp.ones(4)).block_until_ready()
            with spans.span("other.span"):
                pass
    finally:
        jax.profiler.stop_trace()
    ev = program_trace.load_events(str(tmp_path))
    names = [n for _, _, n in ev["host"]]
    assert {"bench.window", "engine.step_segment", "engine.wait"} <= \
        set(names)
    assert "other.span" not in names
    assert program_trace.load_events(str(tmp_path / "none")) is None


# ---------------------------------------------------------------------- #
# the four readers
# ---------------------------------------------------------------------- #
def _run(cell, counters=None, traced=True):
    return harness.Run(cell=harness.load_cell(cell), counters=counters or {},
                       spans=[], window_s=1.0,
                       trace={"busy_s": 1.0} if traced else None, peaks={})


@pytest.fixture
def profile(monkeypatch):
    """``profile(events)``: the events the next traced run's profile
    holds; None for a run whose profile the harness kept nowhere."""
    held = {}
    monkeypatch.setattr(program_trace, "load_events",
                        lambda d: held.get("events"))

    def put(events):
        held["events"] = events
    return put


def test_host_readers(profile):
    execu = harness.reader_for("executor_host_ms.train")
    eng = harness.reader_for("engine_host_ms.sessions")
    ms = 1_000_000
    profile({"device": {"/device:TPU:0": [(0, ms, "%fusion.1 = f32[] "
                                                  "fusion()")]},
             "host": [(0, 1000 * ms, "bench.window"),
                      (0, 100 * ms, "bench.step_group"),
                      (0, 100 * ms, "executor.step_group"),
                      (20 * ms, 10 * ms, "executor.dispatch"),
                      (30 * ms, 65 * ms, "executor.wait"),
                      (100 * ms, 650 * ms, "engine.step_segment"),
                      (100 * ms, 10 * ms, "engine.admit"),
                      (120 * ms, 620 * ms, "engine.wait")]})
    assert execu.read(_run("train_shared.minicpm-2b-2l")) == \
        pytest.approx(25.0)
    assert eng.read(_run("serve_sessions.minicpm-2b")) == \
        pytest.approx(30.0)
    # a program without the spans, an untraced run, no profile kept:
    # nothing to read
    profile({"device": {"/device:TPU:0": [(0, ms, "%copy.1 = copy()")]},
             "host": [(0, 1000 * ms, "bench.window"),
                      (0, 100 * ms, "bench.step_group")]})
    assert execu.read(_run("train_solo.qwen2-vl-2b-2l")) is None
    assert eng.read(_run("serve_sessions.minicpm-2b")) is None
    assert execu.read(_run("train_solo.qwen2-vl-2b-2l", traced=False)) \
        is None
    profile(None)
    assert eng.read(_run("serve_sessions.minicpm-2b")) is None


def _serving(modules):
    dev = [(s, d, "%fusion.1 = f32[] fusion()") for s, d, _ in modules]
    return {"device": {"/device:TPU:0": dev},
            "host": [(0, 10 ** 10, "bench.window")],
            "modules": {"/device:TPU:0": modules}, "op_names": {}}


def test_device_readers(profile):
    pre = harness.reader_for("prefill_device_ms.sessions")
    dec = harness.reader_for("decode_step_ms.sessions")
    segs = {"segment_s": [0.65] * 4}
    ms = 1_000_000
    profile(_serving([(0, 2400 * ms, "jit_decode_segment(1)"),
                      (2400 * ms, 10 * ms, "jit_prefill(2)"),
                      (2410 * ms, 20 * ms, "jit_prefill_extend(3)"),
                      (2430 * ms, 3 * ms, "jit_gather_prefix(4)"),
                      (2433 * ms, 5 * ms, "jit_scatter_pages(5)"),
                      (2438 * ms, 2 * ms, "jit_fork_page(6)"),
                      (2440 * ms, 500 * ms,
                       "jit_convert_element_type(7)")]))
    run = _run("serve_sessions.minicpm-2b", counters=segs)
    assert pre.read(run) == pytest.approx(40.0 / 4)
    # 4 segments of the cell's 16 steps
    assert dec.read(run) == pytest.approx(2400.0 / 64)
    # programs without these names: nothing to read
    profile(_serving([(0, 2400 * ms, "jit_seg(1)"),
                      (2400 * ms, 40 * ms, "jit_run(2)")]))
    old = _run("serve_sessions.minicpm-2b", counters=segs)
    assert pre.read(old) is None and dec.read(old) is None
    assert dec.read(_run("serve_sessions.minicpm-2b", counters=segs,
                         traced=False)) is None


def test_the_profile_is_read_once_per_run(profile, monkeypatch):
    loads = []
    events = _serving([(0, 10, "jit_decode_segment(1)")])
    monkeypatch.setattr(program_trace, "load_events",
                        lambda d: loads.append(d) or events)
    run = _run("serve_sessions.minicpm-2b", counters={"segment_s": [1.0]})
    for name in ("prefill_device_ms.sessions", "decode_step_ms.sessions",
                 "engine_host_ms.sessions"):
        harness.reader_for(name).read(run)
    assert loads == [program_trace.trace_dir(run)]
    assert loads[0].endswith(os.path.join(".bench_run", "trace"))
    program_trace.of(_run("serve_sessions.minicpm-2b"))
    assert len(loads) == 2
