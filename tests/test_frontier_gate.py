"""Unit tests for the frontier CI gate (``benchmarks.frontier.check_gate``)
on hand-built row blobs — so the gate's logic is exercised in tier-1, not
only when bench CI happens to run:

* a fully consistent blob stays green;
* missing few-shot rows violate (an unmeasured margin must not pass);
* ``seed_fold`` / ``scenario_fold`` mismatches violate (the folds must
  actually have run), and so does a ``device_fold`` mismatch under
  ``--devices``;
* bytes-invariance, bytes-regression, and margin floors violate exactly
  when they should — and the dominance checks apply only to
  baseline-listed scenarios (the full smoke catalog's unlisted rows get
  invariance + fold discipline only).
"""
import json
import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from benchmarks import frontier

SEEDS = (0, 1)
_BASELINE = {
    "hard/overlap-32": {
        "one_shot_bytes": 12288,
        "min_mean_margin": 0.01,
        "min_worst_margin": 0.0,
        "fewshot_min_mean_margin": 0.01,
        "fewshot_min_worst_margin": 0.0,
    },
}

_METRIC = {"one_shot": 0.92, "few_shot": 0.93,
           "iterative": 0.80, "fedcvt": 0.82}
_BYTES = {"one_shot": 12288, "few_shot": 20480,
          "iterative": 12288 * 200, "fedcvt": 12288 * 220}


def _row(method, seed, scenario="hard/overlap-32", **over):
    row = {
        "scenario": scenario,
        "seed": seed,
        "method": method,
        "metric_name": "accuracy",
        "metric": _METRIC[method],
        "comm_bytes": _BYTES[method],
        "comm_times": 3,
        "seed_fold": len(SEEDS),
        "scenario_fold": 1,
        "group_size": 1,
        "vmap_eligible": True,
        "overlap": 32,
        "num_parties": 2,
        "modality": "tabular",
    }
    row.update(over)
    return row


def _green_rows(scenario="hard/overlap-32", **over):
    return [_row(m, s, scenario=scenario, **over)
            for m in frontier.METHODS for s in SEEDS]


@pytest.fixture
def baseline_path(tmp_path):
    p = tmp_path / "baseline.json"
    p.write_text(json.dumps(_BASELINE))
    return str(p)


def test_green_blob_passes(baseline_path):
    assert frontier.check_gate(_green_rows(), baseline_path) == []


def test_aggregate_rows_are_ignored(baseline_path):
    rows = _green_rows()
    # a degenerate aggregate row must not feed the per-seed checks
    rows.append(_row("one_shot", "aggregate", aggregate=True,
                     seed_fold=1, scenario_fold=0))
    assert frontier.check_gate(rows, baseline_path) == []


def test_missing_few_shot_rows_violate(baseline_path):
    rows = [r for r in _green_rows() if r["method"] != "few_shot"]
    problems = frontier.check_gate(rows, baseline_path)
    assert any("no few_shot rows" in p for p in problems)


def test_seed_fold_mismatch_violates(baseline_path):
    rows = _green_rows()
    rows[0] = dict(rows[0], seed_fold=1)
    problems = frontier.check_gate(rows, baseline_path)
    assert any("seed_fold=1" in p and "per-seed loop" in p
               for p in problems)


def test_scenario_fold_mismatch_violates(baseline_path):
    """A row recorded against a size-C group must have folded all C
    scenarios — the grouped sweep silently degrading to the per-scenario
    loop is exactly what this assert exists to catch."""
    rows = _green_rows(group_size=3, scenario_fold=3)
    assert frontier.check_gate(rows, baseline_path) == []
    rows[3] = dict(rows[3], scenario_fold=1)
    problems = frontier.check_gate(rows, baseline_path)
    assert any("scenario_fold=1" in p and "size-3 group" in p
               for p in problems)


def test_fold_checks_only_under_vmap_matrix_leg(baseline_path):
    """The fold checks apply with no environment variable set: a sweep
    whose rows ran neither the seed nor the scenario fold violates on
    every row, twice."""
    rows = _green_rows(seed_fold=1, scenario_fold=1, group_size=3)
    problems = frontier.check_gate(rows, baseline_path)
    assert sum("seed_fold=1" in p for p in problems) == len(rows)
    assert sum("scenario_fold=1" in p for p in problems) == len(rows)


def test_engine_path_violations(baseline_path):
    """Under ``--devices N`` every row must record ``device_fold == N``."""
    rows = _green_rows(device_fold=2)
    assert frontier.check_gate(rows, baseline_path, devices=2) == []
    rows[0] = dict(rows[0], device_fold=1)                 # one_shot
    rows[4] = dict(rows[4], device_fold=1)                 # iterative
    problems = frontier.check_gate(rows, baseline_path, devices=2)
    assert sum("device_fold=1" in p for p in problems) == 2


def test_bytes_invariance_and_regression(baseline_path):
    rows = _green_rows()
    rows[1] = dict(rows[1], comm_bytes=_BYTES["one_shot"] + 4)
    problems = frontier.check_gate(rows, baseline_path)
    assert any("seed-invariant" in p for p in problems)
    rows = _green_rows()
    for r in rows:
        if r["method"] == "one_shot":
            r["comm_bytes"] = _BASELINE["hard/overlap-32"]["one_shot_bytes"] + 8
    problems = frontier.check_gate(rows, baseline_path)
    assert any("regressed" in p for p in problems)


def test_margin_floors_violate(baseline_path):
    rows = _green_rows()
    for r in rows:
        if r["method"] == "one_shot":
            r["metric"] = _METRIC["iterative"] + 0.005   # below 0.01 floor
    problems = frontier.check_gate(rows, baseline_path)
    assert any("one-shot mean margin" in p for p in problems)
    rows = _green_rows()
    for r in rows:
        if r["method"] == "few_shot" and r["seed"] == SEEDS[1]:
            r["metric"] = _METRIC["iterative"] - 0.05    # one losing seed
    problems = frontier.check_gate(rows, baseline_path)
    assert any("few-shot worst-seed margin" in p for p in problems)


def test_bytes_ratio_violates(baseline_path):
    rows = _green_rows()
    for r in rows:
        if r["method"] == "iterative":
            r["comm_bytes"] = _BYTES["one_shot"] * 50    # < 100x advantage
    problems = frontier.check_gate(rows, baseline_path)
    assert any("< 100x" in p for p in problems)


def test_dominance_checks_scoped_to_baseline_listed_scenarios(
        baseline_path):
    """An unlisted low-overlap scenario (e.g. the smoke catalog's image
    rows, whose iteration budgets make no 100x claim) gets NO dominance
    checks — but keeps seed-invariance and fold discipline."""
    rows = _green_rows(scenario="image/halves")
    for r in rows:                 # would fail every dominance check...
        if r["method"] == "iterative":
            r["comm_bytes"] = _BYTES["one_shot"] * 2
        if r["method"] == "one_shot":
            r["metric"] = _METRIC["iterative"] - 0.1
    rows = [r for r in rows if r["method"] != "few_shot"]  # ...and this one
    assert frontier.check_gate(rows, baseline_path) == []
    # invariance still applies to unlisted scenarios
    rows[1] = dict(rows[1], comm_bytes=_BYTES["one_shot"] + 4)
    problems = frontier.check_gate(rows, baseline_path)
    assert any("seed-invariant" in p for p in problems)
