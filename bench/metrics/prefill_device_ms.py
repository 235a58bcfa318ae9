"""Device time of admission per serving segment, in ms: the programs that
admit a request (whole prefill, suffix extend, prefix gather, page
scatter, copy-on-write fork), by their XLA modules in the traced window,
over the window's segments."""
from bench import program_trace

PROGRAMS = ("prefill", "prefill_extend", "gather_prefix", "scatter_pages",
            "fork_page")


def read(run):
    pt = program_trace.of(run)
    progs = (pt or {}).get("program_seconds", {})
    segs = run.counters.get("segment_s")
    if not segs or not any(p in progs for p in PROGRAMS):
        return None
    return 1e3 * sum(progs.get(p, 0.0) for p in PROGRAMS) / len(segs)
