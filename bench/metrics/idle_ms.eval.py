"""idle_ms.eval: device idle per traced call in the test-set evaluation
(span vfl.eval). Moves protocol_s."""

from bench.metrics import _spans

SPANS = ("eval",)


def read(ctx):
    return _spans.idle_ms(ctx, SPANS)
