"""programs_per_run: device program executions per protocol call, from the
trace (first chip). Moves protocol_s."""
from bench.metrics import _common


def read(ctx):
    if ctx["counters"].get("kind") != "protocol":
        return None
    calls = _common.spans(ctx, "call")
    progs = ctx["trace"].programs.get(ctx["devices"][0], [])
    if not calls or not progs:
        return None
    return len(_common.inside(progs, calls)) / len(calls), "count"
