"""Mean walltime of ``DecodeEngine.step_segment`` in the window, in ms:
admissions, bookkeeping and one fused decode segment, which ends in a
host sync on its tokens."""


def read(run):
    segs = run.counters.get("segment_s")
    if not segs:
        return None
    return 1e3 * sum(segs) / len(segs)
