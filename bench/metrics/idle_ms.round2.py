"""idle_ms.round2: device idle per traced call in few-shot's second round
outside its SSL sessions: the private reps' extraction, the auxiliary fits,
step 3' (Eq. 10 and the gate), p̂'s download and the final fit (spans
vfl.f1.extract, vfl.f2.aux, vfl.f3.sdpa, vfl.f4.probs, vfl.f6.fit).
vfl.f1.extract is also one of idle_ms.extract's spans, so the two overlap
by its idle. Moves protocol_s."""

from bench.metrics import _spans

SPANS = ("f1.extract", "f2.aux", "f3.sdpa", "f4.probs", "f6.fit")


def read(ctx):
    return _spans.idle_ms(ctx, SPANS)
