"""What decides ``correct``, shown to fail where it must: the benchmark's
own drivers run tiny copies of the cells on the CPU (the harness's look for
a chip skipped), judged by the real cell's limits where the benchmark has that cell
(``limits_from``) and by the limits in the tiny cell's file: the SSL session's
gaps, which a tiny session keeps near rounding and a chip-size session of
320 steps does not (PERF.md), are compared at this size only. The program passes; the
control (the reference one precision below the configuration's) fails; and
so does each fault a cell can have, planted in the program's output or in
the program itself: a state returned unchanged, half of the answers left
out, an answer altered where it is produced."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
sys.path.insert(0, ROOT)

from bench import harness  # noqa: E402

DATA = os.path.join(os.path.dirname(__file__), "data")
SEED = 2**31 + 977


def _tiny(name):
    cell = harness.load_cell(name, os.path.join(DATA, "workloads"))
    if "limits_from" in cell:
        cell["limits"] = {**harness.load_cell(cell["limits_from"])["limits"],
                          **cell.get("limits", {})}
    config = harness.load_config(cell["config"], os.path.join(DATA, "configs"))
    return cell, config


def _run(name, seconds=0.0):
    cell, config = _tiny(name)
    driver = harness.load_driver(cell["driver"])
    state = driver.setup(cell, config, SEED)
    try:
        win = driver.window(state, seconds, harness.Tracer(False))
    finally:
        if hasattr(state, "probes"):
            state.probes.uninstall()
    return driver, state, win, cell


def _correct(cell, numbers):
    return harness.judge(numbers, {k: v for k, v in cell["limits"].items()
                                   if k in numbers})[0]


@pytest.fixture(scope="module")
def oneshot():
    return _run("tiny.oneshot")


@pytest.mark.parametrize("run", ["oneshot"])
def test_program_is_correct_and_control_is_not(run, request):
    driver, state, win, cell = request.getfixturevalue(run)
    assert win.attempted >= 1 and win.failed == 0
    numbers = driver.check(state, win)
    assert set(cell["limits"]) <= set(numbers)
    assert _correct(cell, numbers), numbers
    control = driver.control_numbers(state)
    assert not _correct(cell, control), control


@pytest.mark.parametrize("run", ["oneshot"])
def test_planted_output_faults_fail(run, request):
    """Each fault planted in what the timed path produced, as the chip-side
    readings plant it (bench/control.py)."""
    from bench import control

    driver, state, _, cell = request.getfixturevalue(run)
    faults = control.fault_numbers(driver, state)
    assert {"answer_altered", "half_answers_zeroed", "labels_altered",
            "half_batch"} <= set(faults)
    for name, numbers in faults.items():
        assert not _correct(cell, numbers), (name, numbers)


@pytest.fixture
def fresh_sessions():
    """A fault planted in the program compiles anew, and leaves no compiled
    session behind for the next test."""
    from repro.engine import sessions

    sessions.clear_session_cache()
    yield
    sessions.clear_session_cache()


def test_protocol_state_returned_unchanged_fails(monkeypatch):
    from repro import engine

    def unchanged(keys, tasks_per_seed, hp, mode="auto", mesh=None):
        params = [[t.params for t in tasks] for tasks in tasks_per_seed]
        metrics = [[{} for _ in tasks] for tasks in tasks_per_seed]
        return params, metrics, ["vmap"] * len(tasks_per_seed)

    monkeypatch.setattr(engine, "train_clients_ssl_seeds", unchanged)
    driver, state, win, cell = _run("tiny.oneshot")
    numbers = driver.numbers(state)
    assert numbers["ssl_unmoved_leaves"] > 0
    assert not _correct(cell, numbers)


def test_protocol_half_batch_training_fails(monkeypatch, fresh_sessions):
    """Every SSL step takes its loss over the first half of its labeled and
    unlabeled rows only."""
    from repro.core import ssl

    orig = ssl.ssl_loss

    def half(logits_fn, params, key, xl, yl, xu, cfg, *args, **kwargs):
        return orig(logits_fn, params, key, xl[:xl.shape[0] // 2],
                    yl[:yl.shape[0] // 2], xu[:xu.shape[0] // 2], cfg,
                    *args, **kwargs)

    monkeypatch.setattr(ssl, "ssl_loss", half)
    driver, state, win, cell = _run("tiny.oneshot")
    numbers = driver.numbers(state)
    assert numbers["ssl_unmoved_leaves"] == 0
    assert not _correct(cell, numbers), numbers


def test_judge_compares_limited_numbers_only():
    """A number without a limit is a reading and decides nothing; a limited
    number that is missing or not finite is not correct."""
    ok, checks = harness.judge({"gap": 0.1, "reading": 5.0}, {"gap": 0.2})
    assert ok and set(checks) == {"gap"}
    assert not harness.judge({"reading": 5.0}, {"gap": 0.2})[0]
    assert not harness.judge({"gap": float("nan")}, {"gap": 0.2})[0]
    assert not harness.judge({"gap": 0.3}, {"gap": 0.2})[0]
