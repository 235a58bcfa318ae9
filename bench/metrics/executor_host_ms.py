"""Host time inside each fused call, in ms: the mean, over the traced
window's ``executor.step_group`` spans, of their time less the
``executor.dispatch`` and ``executor.wait`` spans they hold (from the
dispatch to ``block_until_ready``: the call's walltime).  What is left is
program lookup, argument flattening, the loss read-back and bookkeeping.
The spans are the program's own, read from the profiler trace."""
from bench import program_trace


def read(run):
    pt = program_trace.of(run)
    if pt is None:
        return None
    return program_trace.mean_less_child_ms(
        pt["host"], "executor.step_group",
        ("executor.dispatch", "executor.wait"))
