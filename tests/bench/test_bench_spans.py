"""The readers of the program's phase spans (``bench/metrics/_spans.py`` and
the ``idle_ms.*`` and ``compiles_in_window`` metrics) on hand-made traces:
two protocol calls of 1,000 ms, each with the eight one-shot phase spans and
five device operations, so every reading can be worked out by hand."""

import os
import random
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import harness, trace_reduce  # noqa: E402
from bench.metrics import _spans  # noqa: E402
from bench.trace_reduce import Event, Trace  # noqa: E402

MS = 1e6
#: (phase, start, end) in ms from the call's start, and the device idle
#: inside each (the operations below cover the rest)
PHASES = [
    ("init", 10, 100, 40),
    ("p1.extract", 100, 300, 150),
    ("p2.grads", 300, 350, 30),
    ("p3.kmeans", 350, 400, 50),
    ("p4.ssl", 400, 700, 100),
    ("p5.extract", 700, 800, 80),
    ("p6.fit", 800, 900, 100),
    ("eval", 900, 990, 50),
]
OPS = [(50, 150), (320, 340), (450, 650), (760, 780), (950, 1000)]
CALL_STARTS = (0, 2000)
#: idle no phase span covers: [0, 10] of each call ([990, 1000] is busy)
UNSPANNED = 10
CALL_IDLE = 610


def _ev(name, start, end):
    return Event(name, start * MS, (end - start) * MS)


def _trace(phases=True, busy_second_device=False):
    calls = [_ev("bench.call", t, t + 1000) for t in CALL_STARTS]
    host = list(calls)
    ops = []
    for t in CALL_STARTS:
        ops += [_ev("fusion", t + a, t + b) for a, b in OPS]
        if phases:
            host.append(_ev("vfl.run", t + 5, t + 995))
            host += [_ev("vfl." + n, t + a, t + b) for n, a, b, _ in PHASES]
            # the engine's spans nest inside step 4 and are no phase
            host += [_ev("vfl.ssl.session", t + 420, t + 430)]
    tr = Trace(ops={"tpu:0": ops}, annotations=calls)
    tr.host = sorted(host, key=lambda e: e.start_ns)
    if busy_second_device:
        tr.ops["tpu:1"] = [_ev("fusion", t, t + 1000) for t in CALL_STARTS]
    return tr


def _ctx(tr, kind="protocol"):
    return {"trace": tr, "devices": sorted(tr.ops), "counters": {"kind": kind}}


@pytest.mark.parametrize(
    "metric, want",
    [
        ("idle_ms.extract", 150 + 80),
        ("idle_ms.ssl_prep", 100),
        ("idle_ms.eval", 50),
        ("idle_ms.unspanned", UNSPANNED),
    ],
)
@pytest.mark.parametrize("second_device", [False, True])
def test_idle_readers_on_hand_made_trace(metric, want, second_device):
    ctx = _ctx(_trace(busy_second_device=second_device))
    value, unit = harness.metric_readers()[metric](ctx)
    assert unit == "ms"
    # a second device busy throughout the calls halves the chips' average
    assert value == pytest.approx(want / (2 if second_device else 1))


def test_idle_in_phases_and_unspanned_add_up():
    ctx = _ctx(_trace())
    per_phase = [_spans.idle_ms(ctx, (n,))[0] for n, *_ in PHASES]
    assert per_phase == pytest.approx([idle for *_, idle in PHASES])
    assert sum(per_phase) + _spans.unspanned_ms(ctx)[0] == pytest.approx(CALL_IDLE)
    tr = ctx["trace"]
    total = sum(
        c.dur_ns - trace_reduce.busy_ns(tr.ops["tpu:0"], c.start_ns, c.end_ns)
        for c in tr.annotations
    )
    assert total / len(tr.annotations) / MS == pytest.approx(CALL_IDLE)


@pytest.mark.parametrize("seed", range(4))
def test_idle_matches_the_trace_reduction(seed):
    rng = random.Random(seed)
    ops = sorted(
        (Event("op", s, rng.uniform(0, 40)) for s in (rng.uniform(0, 1000) for _ in range(60))),
        key=lambda e: e.start_ns,
    )
    busy = trace_reduce.union(ops, 0, 1000)
    for _ in range(20):
        a = rng.uniform(0, 1000)
        b = rng.uniform(a, 1000)
        want = (b - a) - trace_reduce.busy_ns(ops, a, b)
        assert _spans.idle_ns(busy, [Event("w", a, b - a)]) == pytest.approx(want, abs=1e-9)


def test_readers_find_nothing_without_program_spans(monkeypatch, tmp_path):
    monkeypatch.setattr(harness, "TRACE_DIR", str(tmp_path))
    readers = harness.metric_readers()
    names = ["idle_ms.extract", "idle_ms.ssl_prep", "idle_ms.eval", "idle_ms.unspanned"]
    for ctx in (_ctx(_trace(phases=False)), _ctx(_trace(), kind="serve")):
        for name in names + ["compiles_in_window"]:
            assert readers[name](ctx) is None, name


def test_compiles_counts_the_runs_inside_the_calls():
    calls = [_ev("bench.call", t, t + 1000) for t in CALL_STARTS]
    runs = [
        (_ev("vfl.run", 5, 995), {"run": 1, "compiles": 2}),
        (_ev("vfl.run", 2005, 2995), {"run": 2, "compiles": 0}),
        (_ev("vfl.run", 4005, 4995), {"run": 3, "compiles": 7}),
    ]
    assert _spans.compiles(calls, runs) == 2.0
    assert _spans.compiles(calls[1:], runs) == 0.0
    assert _spans.compiles(calls, [(e, {"run": 1}) for e, _ in runs]) is None
