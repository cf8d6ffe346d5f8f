"""idle_ms.extract: device idle per traced call while the host extracts the
parties' representations, steps ① and ⑤ (and few-shot ①'), ledger included
(spans vfl.p1.extract, vfl.p5.extract, vfl.f1.extract). Moves protocol_s."""

from bench.metrics import _spans

SPANS = ("p1.extract", "p5.extract", "f1.extract")


def read(ctx):
    return _spans.idle_ms(ctx, SPANS)
