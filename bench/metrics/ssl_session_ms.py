"""ssl_session_ms: device time per protocol call in the local-SSL session
programs (engine/local_ssl.py: the vmapped whole-session scan, or the
per-step program of the per-party loop), matched by program name. Moves
protocol_s."""
from bench import trace_reduce
from bench.metrics import _common

#: program names of the SSL sessions (jit of engine/local_ssl.py's
#: ``one_party`` under vmap, and of its per-step ``step``)
PROGRAMS = ("one_party", "jit_step")


def read(ctx):
    if ctx["counters"].get("kind") != "protocol":
        return None
    calls = _common.spans(ctx, "call")
    t = sum(sum(p.dur_ns for p in _common.inside(
        trace_reduce.program_events(ctx["trace"], d, PROGRAMS), calls))
        for d in ctx["devices"]) / len(ctx["devices"])
    if not calls or t <= 0:
        return None
    return t * 1e-6 / len(calls), "ms"
