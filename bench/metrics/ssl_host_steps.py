"""ssl_host_steps: SSL steps the per-party host loop dispatched one by one
(engine/local_ssl.py::train_party_ssl) per traced call, from the
``ssl_host_steps`` delta the program attaches to its vfl.run span (re-read
from the profile); 0 while every session runs scanned. Read in every cell
whose vfl.run spans carry the counter. Moves protocol_s."""

from bench import harness
from bench.metrics import _common, _spans


def read(ctx):
    calls = _common.spans(ctx, "call")
    if not calls:
        return None
    try:
        runs = _spans.run_metadata(harness.TRACE_DIR)
    except FileNotFoundError:
        return None
    steps = [stats["ssl_host_steps"] for ev, stats in runs
             if "ssl_host_steps" in stats and _common.inside([ev], calls)]
    if not steps:
        return None
    return sum(steps) / len(calls), "count"
