"""Host time inside each serving segment, in ms: the mean, over the
traced window's ``engine.step_segment`` spans, of their time less the
``engine.wait`` span they hold (until the segment's tokens are on the
host).  What is left is shedding, admission (prefills dispatched), page
growth, the decode dispatch and bookkeeping.  The spans are the
program's own, read from the profiler trace."""
from bench import program_trace


def read(run):
    pt = program_trace.of(run)
    if pt is None:
        return None
    return program_trace.mean_less_child_ms(
        pt["host"], "engine.step_segment", "engine.wait")
