"""idle_ms.unspanned: device idle per traced call that no phase span of the
program covers (bench.call against every vfl.* phase span): what the spans
leave unnamed. Moves protocol_s."""

from bench.metrics import _spans


def read(ctx):
    return _spans.unspanned_ms(ctx)
