"""mfu.train: model operations of one protocol call (bench/flops.py:
SSL steps, extraction, k-means, server fits, evaluation) over the traced
time per call, the chips and the chip's peak. Moves protocol_s."""
from bench.metrics import _common


def read(ctx):
    if ctx["counters"].get("kind") != "protocol":
        return None
    calls = _common.spans(ctx, "call")
    if not calls:
        return None
    per_call = sum(c.dur_ns for c in calls) * 1e-9 / len(calls)
    ops = sum(ctx["counters"]["model_flops_per_call"].values())
    return (100.0 * ops / (per_call * ctx["chips"]
                           * ctx["peak"]["flops_per_s"]), "%")
