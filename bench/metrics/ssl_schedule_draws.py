"""ssl_schedule_draws: host generator calls the SSL schedule builds made
(engine/local_ssl.py::build_schedules: 2 per session entry and epoch, the
labeled shuffle and the unlabeled draw) per traced call, from the
``ssl_schedule_draws`` delta the program attaches to its vfl.run span
(re-read from the profile). Read in every cell whose vfl.run spans carry the
counter; a program without it reads nothing. Moves protocol_s."""

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
    draws = [stats["ssl_schedule_draws"] for ev, stats in runs
             if "ssl_schedule_draws" in stats and _common.inside([ev], calls)]
    if not draws:
        return None
    return sum(draws) / len(calls), "count"
