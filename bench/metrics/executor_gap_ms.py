"""Host time between fused calls: (window - sum of ``step_group``
walltimes) / calls, in ms.  The walltimes are the executor's own."""


def read(run):
    c = run.counters
    if not c.get("calls"):
        return None
    return 1e3 * (run.window_s - sum(c["step_group_s"])) / c["calls"]
