"""Device time of one decode step, in ms: the ``decode_segment`` program's
device time in the traced window over the decode steps it ran (the
window's segments times the engine's segment length)."""
from bench import program_trace


def read(run):
    pt = program_trace.of(run)
    t = (pt or {}).get("program_seconds", {}).get("decode_segment")
    segs = run.counters.get("segment_s")
    if not t or not segs:
        return None
    return 1e3 * t / (len(segs) * run.cell.workload["engine"]["segment"])
