"""The trace reduction the benchmark's per-layer metrics read, pinned on a
small trace recorded on the CPU backend (``data/cpu_trace.xplane.pb``: two
``bench.call`` spans, each running ``jit_bench_prog``, sleeping 10 ms, then
running ``jit_other_prog``)."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
sys.path.insert(0, ROOT)

from bench import trace_reduce  # noqa: E402
from bench.trace_reduce import Event  # noqa: E402

TRACE = os.path.join(os.path.dirname(__file__), "data", "cpu_trace.xplane.pb")


@pytest.fixture(scope="module")
def tr():
    return trace_reduce.load(TRACE)


def test_window_is_the_span_of_the_annotations(tr):
    assert [a.name for a in tr.annotations] == ["bench.call", "bench.call"]
    assert tr.window() == (122505.0, 27109881.0)


def test_busy_union_and_idle_share(tr):
    start, end = tr.window()
    ops = tr.ops["cpu:0"]
    busy = trace_reduce.busy_ns(ops, start, end)
    assert busy == 843282.0
    gaps = trace_reduce.idle_gaps(ops, start, end)
    assert len(gaps) == 15
    assert sum(g for _, g in gaps) + busy == pytest.approx(end - start)
    idle = 1 - busy / (end - start)
    assert idle == pytest.approx(0.9687527, abs=1e-6)


def test_programs_are_named_and_matched(tr):
    progs = [p.name for p in tr.programs["cpu:0"]]
    assert progs == ["jit_bench_prog", "jit_other_prog"] * 2
    assert {e.program for e in tr.ops["cpu:0"]} == {"jit_bench_prog",
                                                    "jit_other_prog"}
    hits = trace_reduce.program_events(tr, "cpu:0", ("bench_prog",))
    assert [p.dur_ns for p in hits] == [339391.0, 367207.0]
    kernel = trace_reduce.kernel_events(tr, "cpu:0", ("dot_general",))
    assert [e.dur_ns for e in kernel] == [286828.0, 316405.0]
    assert trace_reduce.kernel_events(tr, "cpu:0", ("no_such_kernel",)) == []


def test_breakdown_names_gaps_by_host_activity(tr):
    bd = trace_reduce.breakdown(tr)
    assert [n for n, _ in bd["device_ops"]] == ["jit_bench_prog",
                                                "jit_other_prog"]
    assert bd["device_ops"][0][1] == pytest.approx(706598e-9)
    longest = bd["idle_gaps"][0]
    assert longest[0] == "bench.call/$time sleep"
    assert longest[1] == pytest.approx(0.010352039)
    assert len(bd["idle_gaps"]) == 10


@pytest.mark.parametrize("events, want_busy, want_gaps", [
    ([Event("a", 0, 10), Event("b", 5, 10), Event("c", 30, 5)], 20,
     [(15, 15), (35, 65)]),
    ([Event("a", -10, 20), Event("b", 90, 20)], 20, [(10, 80)]),
    ([], 0, [(0, 100)]),
])
def test_union_on_hand_made_intervals(events, want_busy, want_gaps):
    assert trace_reduce.busy_ns(events, 0, 100) == want_busy
    assert trace_reduce.idle_gaps(events, 0, 100) == want_gaps
