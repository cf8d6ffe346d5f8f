"""The program's own spans and counters (``repro.obs``): free while the
profiler is off, one ``vfl.run`` per protocol call holding its phase spans in
order and without overlap, the compile counter, and the benchmark's reading
of them on a trace recorded here on the CPU (tiny-img scale: one-shot twice,
then few-shot)."""

import os
import sys

import jax
import jax.numpy as jnp
import pytest
from jax._src.lib import _profiler

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from bench import harness, trace_reduce  # noqa: E402
from bench.metrics import _spans  # noqa: E402
from repro import obs, scenarios  # noqa: E402
from repro.core import runners  # noqa: E402
from repro.core.protocol import ProtocolConfig, run_scenarios_seeds  # noqa: E402

DATA = os.path.join(ROOT, "tests", "bench", "data")
ONE_SHOT = [
    "init",
    "p1.extract",
    "p2.grads",
    "p3.kmeans",
    "p4.ssl",
    "p5.extract",
    "p6.fit",
    "eval",
]
FEW_SHOT = ONE_SHOT + ["f1.extract", "f2.aux", "f3.sdpa", "f4.probs", "f5.ssl", "f6.fit", "eval"]
CALLS = (("one_shot", 0), ("one_shot", 1), ("few_shot", 2))


@pytest.fixture(scope="module")
def tiny():
    config = harness.load_config("tiny-img", os.path.join(DATA, "configs"))
    bundle = scenarios.build(harness.scenario_spec(config), seed=3)
    return bundle, ProtocolConfig(**config["protocol"])


def _call(tiny, method, seed):
    bundle, cfg = tiny
    grid = run_scenarios_seeds(
        runners.get(method).runner,
        [[jax.random.PRNGKey(seed)]],
        [[bundle.split]],
        [[bundle.extractors]],
        [[bundle.ssl_cfgs]],
        cfg,
    )
    res = grid[0][0]
    jax.block_until_ready((res.clients[0].params, res.server.params))
    return res


@pytest.fixture(scope="module")
def recorded(tiny, tmp_path_factory):
    """The profile of CALLS, each inside a ``bench.call`` annotation (the
    Python tracer off: its frames would outnumber the rest a hundredfold)."""
    out = str(tmp_path_factory.mktemp("profile"))
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(out, profiler_options=options)
    try:
        for method, seed in CALLS:
            with harness.annotate("call"):
                _call(tiny, method, seed)
    finally:
        jax.profiler.stop_trace()
    return out


def _vfl_events(directory):
    """Every ``vfl.*`` event of the profile with its stats, by start."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(trace_reduce.find_xplane(directory))
    out = []
    for plane in pd.planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("vfl."):
                    ev = trace_reduce.Event(e.name[4:], e.start_ns, e.duration_ns)
                    out.append((ev, dict(e.stats)))
    return sorted(out, key=lambda p: p[0].start_ns)


def test_span_off_constructs_no_annotation(tiny, monkeypatch):
    def refuse(*args, **kwargs):
        raise AssertionError("TraceAnnotation built while the profiler is off")

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", refuse)
    assert not _profiler.TraceMe.is_enabled()
    with obs.span("run", runner="x", S=1):
        pass
    res = _call(tiny, "one_shot", 5)
    assert res.ledger.comm_times(0) == 3


def test_compile_counter_counts_fresh_and_not_cached():
    x = jnp.arange(7.0)
    f = jax.jit(lambda v: v * 3.0 + 1.0)
    before = obs.counters()
    f(x).block_until_ready()
    mid = obs.counters()
    f(x).block_until_ready()
    after = obs.counters()
    assert mid["compiles"] - before["compiles"] == 1
    assert after["compiles"] == mid["compiles"]
    assert {"persistent_cache_hits", "session_hits", "session_misses"} <= set(after)


def test_phase_spans_in_order_and_disjoint(recorded):
    events = _vfl_events(recorded)
    runs = [(e, st) for e, st in events if e.name == "run"]
    assert [st["runner"] for _, st in runs] == [m for m, _ in CALLS]
    assert len({st["run"] for _, st in runs}) == len(CALLS)
    for (run, run_st), want in zip(runs, (ONE_SHOT, ONE_SHOT, FEW_SHOT)):
        assert (run_st["S"], run_st["C"], run_st["K"]) == (1, 1, 2)
        inner = [
            (e, st)
            for e, st in events
            if e.name != "run" and run.start_ns <= e.start_ns < run.end_ns
        ]
        assert all(st["run"] == run_st["run"] for _, st in inner)
        assert all(e.end_ns <= run.end_ns for e, _ in inner)
        phases = [e for e, _ in inner if e.name in _spans.PHASES]
        assert [e.name for e in phases] == want
        for a, b in zip(phases, phases[1:]):
            assert a.end_ns <= b.start_ns
        # the engine's spans sit inside step ④ (and ⑤'), in order: one
        # group (both parties share a signature) around its session
        for ssl in (p for p in phases if p.name in ("p4.ssl", "f5.ssl")):
            names = [
                e.name
                for e, _ in inner
                if e.name.startswith("ssl.") and ssl.start_ns <= e.start_ns < ssl.end_ns
            ]
            assert names == ["ssl.group", "ssl.schedule", "ssl.session",
                             "ssl.readback"]


def test_second_call_compiles_nothing(recorded):
    runs = sorted(_spans.run_metadata(recorded), key=lambda r: r[0].start_ns)
    assert len(runs) == len(CALLS)
    assert runs[1][1]["compiles"] == 0
    assert runs[1][1]["persistent_cache_hits"] == 0


def test_idle_in_phases_and_unspanned_add_up(recorded, monkeypatch):
    tr = trace_reduce.load(recorded)
    ctx = {"trace": tr, "devices": tr.devices[:1], "counters": {"kind": "protocol"}}
    calls = [a for a in tr.annotations if a.name == "bench.call"]
    assert len(calls) == len(CALLS)
    ops = tr.ops[tr.devices[0]]
    idle = [c.dur_ns - trace_reduce.busy_ns(ops, c.start_ns, c.end_ns) for c in calls]
    total = sum(idle) * 1e-6 / len(calls)
    phases = _spans.program_spans(ctx, _spans.PHASES)
    assert len(phases) == 2 * len(ONE_SHOT) + len(FEW_SHOT)
    readings = [_spans.idle_ms(ctx, (name,)) for name in _spans.PHASES]
    in_phases = sum(r[0] for r in readings if r is not None)
    unspanned = _spans.unspanned_ms(ctx)[0]
    assert 0 <= unspanned < total
    assert in_phases + unspanned == pytest.approx(total, rel=1e-12)
    monkeypatch.setattr(harness, "TRACE_DIR", recorded)
    counts = [st["compiles"] for _, st in _spans.run_metadata(recorded)]
    assert _spans.compiles_in_window(ctx) == (float(sum(counts)), "count")
