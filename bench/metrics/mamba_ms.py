"""Device time of the Mamba2 mixers per fused call, in ms: the traced
window's device time under the ``mamba`` scope (forward, its recompute
and its backward, ``mamba.backward``, with every scope inside them),
over the calls in the window."""
from bench import program_trace

SCOPES = ("mamba", "mamba.backward")


def read(run):
    scopes = (program_trace.of(run) or {}).get("scope_seconds", {})
    t = sum(v for k, v in scopes.items()
            if any(part in SCOPES for part in k.split("/")))
    calls = run.counters.get("calls")
    if not t or not calls:
        return None
    return 1e3 * t / calls
