"""The few-shot cell's driver (``bench/drivers/fewshot.py``) on a tiny
tabular copy of ``credit-mlp.fewshot-s8`` on the CPU: the program is correct
by the real cell's limits and the tiny cell's own; the control (the
reference one precision below the configuration's) and each planted fault
are not; and the cell's new readers read the window's recorded trace, or
return ``None`` where a trace has nothing for them."""
import os
import sys

import jax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
sys.path.insert(0, ROOT)

from bench import control, flops, harness, trace_reduce  # noqa: E402

DATA = os.path.join(os.path.dirname(__file__), "data")
SEED = 2**31 + 4099
NEW_READERS = ("sdpa_roofline", "ssl_host_steps", "idle_ms.round2")


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
def fewshot(tmp_path_factory):
    cell = harness.load_cell("tiny.fewshot", os.path.join(DATA, "workloads"))
    cell["limits"] = {**harness.load_cell(cell["limits_from"])["limits"],
                      **cell["limits"]}
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
    return driver, state, win, cell, trace_dir


def _correct(cell, numbers):
    return harness.judge(numbers, {k: v for k, v in cell["limits"].items()
                                   if k in numbers})[0]


def test_group_plan_and_kernel_work(fewshot):
    _, state, win, _, _ = fewshot
    # steps 4 and 5': one session per party signature, S = 2 members each
    assert win.counters["ssl_groups"] == [[2, 2], [2, 2]]
    sizes = flops.split_sizes(state.config)
    assert win.counters["kernels"]["sdpa"] == flops.sdpa(
        2 * 2, sizes["pool"], sizes["overlap"], 8, 8)
    assert set(win.counters["kernels"]) == {"kmeans", "sdpa"}


def test_program_is_correct_and_control_is_not(fewshot):
    driver, state, win, cell, _ = fewshot
    assert win.attempted >= 1 and win.failed == 0
    numbers = driver.check(state, win)
    assert set(cell["limits"]) <= set(numbers)
    assert _correct(cell, numbers), numbers
    assert numbers["ledger_mismatches"] == 0
    control_numbers = driver.control_numbers(state)
    assert not _correct(cell, control_numbers), control_numbers


@pytest.mark.parametrize("fault", ["answer_altered", "half_answers_zeroed",
                                   "labels_altered", "half_batch",
                                   "gate_inverted", "estimates_rolled",
                                   "round2_dropped"])
def test_planted_faults_fail(fewshot, fault):
    driver, state, _, cell, _ = fewshot
    if fault in ("gate_inverted", "estimates_rolled", "round2_dropped"):
        numbers = driver.fault_numbers(state)[fault]
    else:
        numbers = control.fault_numbers(driver, state)[fault]
    assert not _correct(cell, numbers), numbers


def _ctx(trace, win, state):
    return {"trace": trace, "devices": trace.devices[:1], "chips": 1,
            "counters": win.counters, "config": state.config,
            "cell": state.cell, "peak": flops.peaks("TPU v5 lite")}


def test_new_readers_on_the_recorded_window(fewshot, monkeypatch):
    _, state, win, _, trace_dir = fewshot
    monkeypatch.setattr(harness, "TRACE_DIR", trace_dir)
    ctx = _ctx(trace_reduce.load(trace_dir), win, state)
    readers = harness.metric_readers()
    assert readers["ssl_host_steps"](ctx) == (0.0, "count")
    idle, unit = readers["idle_ms.round2"](ctx)
    assert unit == "ms" and idle > 0
    # on the CPU the interpreted kernel is no single named operation
    assert readers["sdpa_roofline"](ctx) is None


def test_new_readers_find_nothing_in_other_traces(fewshot, monkeypatch):
    """The one-shot test trace holds no program span, no vfl.run and no
    kernel; a cell without the kernel's work reads no roofline, while
    ssl_host_steps reads wherever vfl.run carries its counter."""
    _, state, win, _, trace_dir = fewshot
    readers = harness.metric_readers()
    monkeypatch.setattr(harness, "TRACE_DIR", os.path.join(DATA))
    bare = _ctx(trace_reduce.load(os.path.join(DATA, "cpu_trace.xplane.pb")),
                win, state)
    for name in NEW_READERS:
        assert readers[name](bare) is None, name
    monkeypatch.setattr(harness, "TRACE_DIR", trace_dir)
    ctx = _ctx(trace_reduce.load(trace_dir), win, state)
    ctx["counters"] = {"kind": "protocol", "kernels": {}}
    assert readers["ssl_host_steps"](ctx) == (0.0, "count")
    assert readers["sdpa_roofline"](ctx) is None


def test_ssl_host_steps_reads_the_runs_inside_the_calls(monkeypatch):
    """Any protocol cell's vfl.run spans carry the counter; runs outside
    the traced calls are left out, and a program whose spans lack it reads
    nothing."""
    from bench.metrics import _spans
    from bench.trace_reduce import Event, Trace

    def ev(name, start_ms, end_ms):
        return Event(name, start_ms * 1e6, (end_ms - start_ms) * 1e6)

    calls = [ev("bench.call", t, t + 1000) for t in (0, 2000)]
    ctx = {"trace": Trace(ops={"tpu:0": []}, annotations=calls),
           "devices": ["tpu:0"], "counters": {"kind": "protocol"}}
    read = harness.metric_readers()["ssl_host_steps"]
    runs = [
        (ev("vfl.run", 5, 995), {"run": 1, "ssl_host_steps": 620}),
        (ev("vfl.run", 2005, 2995), {"run": 2, "ssl_host_steps": 0}),
        (ev("vfl.run", 4005, 4995), {"run": 3, "ssl_host_steps": 9}),
    ]
    monkeypatch.setattr(_spans, "run_metadata", lambda directory: runs)
    assert read(ctx) == (310.0, "count")
    monkeypatch.setattr(_spans, "run_metadata", lambda directory: [
        (e, {"run": stats["run"]}) for e, stats in runs])
    assert read(ctx) is None
