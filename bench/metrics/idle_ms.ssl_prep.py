"""idle_ms.ssl_prep: device idle per traced call in step ④ (and few-shot
⑤') outside the session program: task building with the cluster-purity
sync, schedule building, stacking and the metrics readback (spans
vfl.p4.ssl, vfl.f5.ssl). Moves protocol_s."""

from bench.metrics import _spans

SPANS = ("p4.ssl", "f5.ssl")


def read(ctx):
    return _spans.idle_ms(ctx, SPANS)
