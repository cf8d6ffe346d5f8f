"""The ``ssl_schedule_draws`` reader: on the tiny copy of
``credit-mlp.fewshot-s8`` it reads the generator calls of every session
entry's schedule (2 per entry and epoch) per traced call; it reads only the
``vfl.run`` spans inside the calls, and nothing where the program's spans
lack the counter or the trace holds no span at all."""
import os
import sys

import jax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
sys.path.insert(0, ROOT)

from bench import flops, harness, trace_reduce  # noqa: E402

DATA = os.path.join(os.path.dirname(__file__), "data")
SEED = 2**31 + 8191


class _Tracer(harness.Tracer):
    """The harness's tracer into ``directory``, Python frames left out."""

    def __init__(self, directory):
        super().__init__(True)
        self.directory = directory

    def start(self):
        if not self.active:
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(self.directory, profiler_options=options)
            self.active = True


@pytest.fixture(scope="module")
def traced_window(tmp_path_factory):
    cell = harness.load_cell("tiny.fewshot", os.path.join(DATA, "workloads"))
    config = harness.load_config(cell["config"], os.path.join(DATA,
                                                              "configs"))
    driver = harness.load_driver(cell["driver"])
    state = driver.setup(cell, config, SEED)
    trace_dir = str(tmp_path_factory.mktemp("trace"))
    tracer = _Tracer(trace_dir)
    try:
        win = driver.window(state, 0.0, tracer)
    finally:
        tracer.stop()
        state.probes.uninstall()
    return state, win, trace_dir


def _ctx(trace, win, state):
    return {"trace": trace, "devices": trace.devices[:1], "chips": 1,
            "counters": win.counters, "config": state.config,
            "cell": state.cell, "peak": flops.peaks("TPU v5 lite")}


def test_reads_two_draws_per_entry_and_epoch(traced_window, monkeypatch):
    state, win, trace_dir = traced_window
    monkeypatch.setattr(harness, "TRACE_DIR", trace_dir)
    read = harness.metric_readers()["ssl_schedule_draws"]
    entries = sum(sum(phase) for phase in win.counters["ssl_groups"])
    epochs = state.config["protocol"]["client_epochs"]
    assert entries == 8
    assert read(_ctx(trace_reduce.load(trace_dir), win, state)) == (
        2.0 * entries * epochs, "count")


def test_reads_nothing_in_a_trace_without_program_spans(traced_window,
                                                        monkeypatch):
    state, win, _ = traced_window
    monkeypatch.setattr(harness, "TRACE_DIR", DATA)
    bare = _ctx(trace_reduce.load(os.path.join(DATA, "cpu_trace.xplane.pb")),
                win, state)
    assert harness.metric_readers()["ssl_schedule_draws"](bare) is None


def test_ssl_schedule_draws_reads_the_runs_inside_the_calls(monkeypatch):
    """Runs outside the traced calls are left out, and a program whose
    vfl.run spans lack the counter (one from before it) reads nothing."""
    from bench.metrics import _spans
    from bench.trace_reduce import Event, Trace

    def ev(name, start_ms, end_ms):
        return Event(name, start_ms * 1e6, (end_ms - start_ms) * 1e6)

    calls = [ev("bench.call", t, t + 1000) for t in (0, 2000)]
    ctx = {"trace": Trace(ops={"tpu:0": []}, annotations=calls),
           "devices": ["tpu:0"], "counters": {"kind": "protocol"}}
    read = harness.metric_readers()["ssl_schedule_draws"]
    runs = [
        (ev("vfl.run", 5, 995), {"run": 1, "ssl_schedule_draws": 1280}),
        (ev("vfl.run", 2005, 2995), {"run": 2, "ssl_schedule_draws": 1280}),
        (ev("vfl.run", 4005, 4995), {"run": 3, "ssl_schedule_draws": 80}),
    ]
    monkeypatch.setattr(_spans, "run_metadata", lambda directory: runs)
    assert read(ctx) == (1280.0, "count")
    monkeypatch.setattr(_spans, "run_metadata", lambda directory: [
        (e, {"run": stats["run"], "ssl_host_steps": 0})
        for e, stats in runs])
    assert read(ctx) is None
    assert read({**ctx, "trace": Trace(ops={"tpu:0": []},
                                       annotations=[])}) is None
