"""Fault-injected VFL (DESIGN.md §16): declarative ``FaultSpec`` party
faults — dropout at a named protocol stage, stragglers, DP-noised
uploads, representation-only parties — threaded through the one-shot /
few-shot protocol and the iterative baselines, and the frontier gate's
graceful-degradation floors:

* ``FaultSpec`` construction/validation and its pure predicate surface
  (``drops`` / ``skips_ssl`` / ``parties_survived`` /
  ``iterative_active_steps``);
* fold parity: a faulted C×S grid through ``run_scenarios_seeds`` ==
  the per-scenario ``run_seeds`` loop at 1e-5 with byte-identical
  per-entry ledgers (one-shot, few-shot, AND the iterative scan fold
  with its retry-inflated dropout ledgers), and the faulted seed fold ==
  the unfolded single-``run_one_shot`` calls;
* faults are data, not structure: changing the fault assignment on a
  warm fold adds ZERO fresh session-cache misses (the masks/keys ride
  the stacked programs as arguments, never as cache-key shape);
* an all-``None`` fault grid is byte- and metric-identical to the
  fault-free call — the healthy path must not feel the plumbing;
* the iterative dropout model: ledger-visible ``retry_reps`` /
  ``retry_timeout`` rounds, ``fault_modeled`` honesty on unmodeled
  kinds, and the few-shot+finetune refusal;
* ``check_gate``'s fault floors on hand-built row blobs (missing family
  members, wrong survivor counts, missing retry cost, broken
  degradation, the zero-fault-rows full-sweep rule).
"""
import json
import os
import sys

import jax
import jax.numpy as jnp
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from benchmarks import frontier
from repro import engine
from repro.core import (IterativeConfig, ProtocolConfig, SSLConfig,
                        run_few_shot, run_one_shot, run_vanilla)
from repro.core.protocol import (_few_shot_finetune_seeds,
                                 run_scenarios_seeds, run_seeds)
from repro.data import make_tabular_credit, make_vfl_partition
from repro.models import make_mlp_extractor
from repro.scenarios.faults import (ITERATIVE_DROP_FRACTION, POINT_EVAL,
                                    POINT_ROUND2, POINT_SSL, POINT_UPLOAD1,
                                    FaultSpec)

_FAST = ProtocolConfig(client_epochs=2, server_epochs=3)
SEEDS = (0, 1)
_SSL = [SSLConfig(modality="tabular")] * 2

FA_DROP = FaultSpec("dropout", party=1, stage="pre_ssl")
FA_STRAG = FaultSpec("straggler", party=0, epoch_fraction=0.5)
FA_DP = FaultSpec("dp_upload", party=1, dp_sigma=0.5)
FA_REP = FaultSpec("representation_only", party=1)

#: the C=2 × S=2 mixed grid every fold-parity test sweeps: a dropped
#: party next to a HEALTHY entry in the same fold (the healthy twin must
#: not feel its neighbors), a straggler next to a frozen party
_FAULTS = [[FA_DROP, None], [FA_STRAG, FA_REP]]


def _ext():
    return [make_mlp_extractor(rep_dim=8, hidden=(16,)) for _ in range(2)]


def _scenario_splits(c, overlap=64):
    out = []
    for s in SEEDS:
        x, y = make_tabular_credit(jax.random.PRNGKey(7000 + 97 * c + s), 700)
        out.append(make_vfl_partition(x[:, :22], y, overlap_size=overlap,
                                      feature_sizes=[11, 11], seed=s))
    return out


@pytest.fixture(scope="module")
def grid_splits():
    return [_scenario_splits(0), _scenario_splits(1)]


def _run_grid(runner, grid_splits, cfg=_FAST, faults=None):
    num_scenarios = len(grid_splits)
    kw = {} if faults is None else {"faults": faults}
    return run_scenarios_seeds(
        runner,
        [[jax.random.PRNGKey(s) for s in SEEDS]
         for _ in range(num_scenarios)],
        grid_splits,
        [[_ext() for _ in SEEDS] for _ in range(num_scenarios)],
        [[_SSL for _ in SEEDS] for _ in range(num_scenarios)],
        cfg, **kw)


def _run_loop(runner, grid_splits, cfg=_FAST, faults=None):
    return [run_seeds(runner, [jax.random.PRNGKey(s) for s in SEEDS], sp,
                      [_ext() for _ in SEEDS], [_SSL for _ in SEEDS], cfg,
                      **({} if faults is None else {"faults": faults[c]}))
            for c, sp in enumerate(grid_splits)]


def _assert_ledgers_equal(a, b):
    assert a.total_bytes() == b.total_bytes()
    assert a.comm_times() == b.comm_times()
    assert a.by_tag() == b.by_tag()


def _assert_grid_matches_loop(folded, loop):
    for scen_folded, scen_loop in zip(folded, loop):
        for res, ref in zip(scen_folded, scen_loop):
            assert abs(float(res.metric) - float(ref.metric)) < 1e-5, \
                (float(res.metric), float(ref.metric))
            _assert_ledgers_equal(res.ledger, ref.ledger)
            for cb, cs in zip(res.clients, ref.clients):
                for lb, ls in zip(jax.tree_util.tree_leaves(cb.params),
                                  jax.tree_util.tree_leaves(cs.params)):
                    assert jnp.allclose(lb, ls, atol=1e-5), \
                        float(jnp.max(jnp.abs(lb - ls)))


# --------------------------------------------------- FaultSpec semantics
def test_fault_spec_validation():
    with pytest.raises(ValueError, match="kind"):
        FaultSpec("meteor")
    with pytest.raises(ValueError, match="stage"):
        FaultSpec("dropout", stage="mid_coffee")
    with pytest.raises(ValueError, match="retry_rounds"):
        FaultSpec("dropout", retry_rounds=0)
    with pytest.raises(ValueError, match="epoch_fraction"):
        FaultSpec("straggler", epoch_fraction=1.5)
    with pytest.raises(ValueError, match="dp_sigma"):
        FaultSpec("dp_upload", dp_sigma=-0.1)
    with pytest.raises(ValueError, match="party"):
        FaultSpec("dropout", party=-1)


def test_fault_spec_predicates():
    fa = FaultSpec("dropout", party=1, stage="post_ssl")
    # gone from its stage threshold onward, never before, never another party
    assert not fa.drops(1, POINT_UPLOAD1) and not fa.drops(1, POINT_SSL)
    assert fa.drops(1, POINT_ROUND2) and fa.drops(1, POINT_EVAL)
    assert not fa.drops(0, POINT_EVAL)
    assert not fa.skips_ssl(1)          # dropped AFTER its SSL ran
    assert FaultSpec("dropout", party=1, stage="pre_ssl").skips_ssl(1)
    assert FA_REP.skips_ssl(1) and not FA_REP.skips_ssl(0)
    assert not FA_REP.drops(1, POINT_EVAL)   # frozen, but still present
    assert fa.parties_survived(4) == 3
    for other in (FA_STRAG, FA_DP, FA_REP):
        assert other.parties_survived(4) == 4
    for stage, frac in ITERATIVE_DROP_FRACTION.items():
        drop = FaultSpec("dropout", stage=stage)
        assert drop.iterative_active_steps(200) == int(frac * 200)
    assert FA_STRAG.iterative_active_steps(200) == 200


# ------------------------------------------------------------ fold parity
def test_faulted_seed_fold_matches_single_runs(grid_splits):
    """S=2 faulted ``run_seeds`` == the two unfolded ``run_one_shot``
    calls: per-seed metric at 1e-5, byte-identical ledgers (including the
    dropped party's SKIPPED upload events), matching fault diagnostics."""
    splits = grid_splits[0]
    faults = [FA_DROP, FA_DP]
    folded = run_seeds(run_one_shot, [jax.random.PRNGKey(s) for s in SEEDS],
                       splits, [_ext() for _ in SEEDS],
                       [_SSL for _ in SEEDS], _FAST, faults=faults)
    for s, res in enumerate(folded):
        ref = run_one_shot(jax.random.PRNGKey(SEEDS[s]), splits[s], _ext(),
                           _SSL, _FAST, fault=faults[s])
        assert abs(float(res.metric) - float(ref.metric)) < 1e-5
        _assert_ledgers_equal(res.ledger, ref.ledger)
        assert res.diagnostics["fault_kind"] == faults[s].kind
        assert res.diagnostics["parties_survived"] == \
            faults[s].parties_survived(2)
    # the dropped party's uploads never hit the wire; the DP party's do
    drop_tags = folded[0].ledger.by_tag()
    dp_tags = folded[1].ledger.by_tag()
    assert drop_tags != dp_tags
    assert folded[0].ledger.total_bytes() < folded[1].ledger.total_bytes()


def test_faulted_scenario_fold_matches_loop_one_shot(grid_splits):
    folded = _run_grid(run_one_shot, grid_splits, faults=_FAULTS)
    loop = _run_loop(run_one_shot, grid_splits, faults=_FAULTS)
    _assert_grid_matches_loop(folded, loop)
    flat = [r for scen in folded for r in scen]
    for r, fa in zip(flat, [fa for row in _FAULTS for fa in row]):
        assert r.diagnostics["seed_fold"] == len(SEEDS)
        assert r.diagnostics["fault_kind"] == \
            ("none" if fa is None else fa.kind)
        assert r.diagnostics["degraded_metric"] == pytest.approx(
            float(r.metric))


def test_faulted_scenario_fold_matches_loop_few_shot(grid_splits):
    """Same parity through round 2: the dropped/frozen party's zeroed
    ①' bundle, the Eq. 10 reconstruction at ⑥', and the skipped ⑤'
    sessions must all fold without feeling their healthy neighbors."""
    folded = _run_grid(run_few_shot, grid_splits, faults=_FAULTS)
    loop = _run_loop(run_few_shot, grid_splits, faults=_FAULTS)
    _assert_grid_matches_loop(folded, loop)


def test_faulted_scenario_fold_matches_loop_iterative(grid_splits):
    """The §11 scan fold with per-entry dropout truncation: entries
    stalling at DIFFERENT round counts (pre_upload vs post_ssl) share one
    stacked carry, and the retry-inflated ledgers come out byte-identical
    to the per-scenario loop's."""
    icfg = IterativeConfig(iterations=8)
    faults = [[FaultSpec("dropout", party=1, stage="pre_upload"), None],
              [FaultSpec("dropout", party=0, stage="post_ssl"), FA_STRAG]]
    folded = _run_grid(run_vanilla, grid_splits, cfg=icfg, faults=faults)
    loop = _run_loop(run_vanilla, grid_splits, cfg=icfg, faults=faults)
    _assert_grid_matches_loop(folded, loop)


def test_all_none_fault_grid_is_the_fault_free_path(grid_splits):
    """``faults=[None, None]`` must be indistinguishable from omitting the
    kwarg entirely — same metric, same prototype-ledger bytes. The healthy
    path pays nothing for the fault plumbing."""
    splits = grid_splits[0]
    plain = run_seeds(run_one_shot, [jax.random.PRNGKey(s) for s in SEEDS],
                      splits, [_ext() for _ in SEEDS],
                      [_SSL for _ in SEEDS], _FAST)
    nones = run_seeds(run_one_shot, [jax.random.PRNGKey(s) for s in SEEDS],
                      splits, [_ext() for _ in SEEDS],
                      [_SSL for _ in SEEDS], _FAST,
                      faults=[None] * len(SEEDS))
    for res, ref in zip(nones, plain):
        assert float(res.metric) == float(ref.metric)
        _assert_ledgers_equal(res.ledger, ref.ledger)
        assert "fault_kind" not in res.diagnostics


def test_changing_faults_adds_zero_fresh_session_misses(grid_splits):
    """Faults are data, not structure: after a warm faulted fold, a sweep
    with a DIFFERENT fault assignment (other kind, other party, other
    stage — same shapes) adds ZERO fresh session-cache misses in any
    domain. The §16 contract that lets a mixed-fault family share one
    group's compiled programs."""
    engine.clear_session_cache()
    _run_grid(run_one_shot, grid_splits, faults=_FAULTS)
    warm = {d: st["misses"]
            for d, st in engine.session_cache_stats_by_domain().items()}
    flipped = [[FA_STRAG, FA_DP],
               [FaultSpec("dropout", party=0, stage="post_ssl"), None]]
    _run_grid(run_one_shot, grid_splits, faults=flipped)
    after = {d: st["misses"]
             for d, st in engine.session_cache_stats_by_domain().items()}
    assert after == warm, (warm, after)


# ------------------------------------------------- iterative fault model
def test_iterative_dropout_charges_retry_rounds():
    split = _scenario_splits(0)[0]
    fa = FaultSpec("dropout", party=1, stage="pre_ssl", retry_rounds=2)
    icfg = IterativeConfig(iterations=8)
    res = run_vanilla(jax.random.PRNGKey(0), split, _ext(), _SSL, icfg,
                      fault=fa)
    ref = run_vanilla(jax.random.PRNGKey(0), split, _ext(), _SSL, icfg)
    tags = res.ledger.by_tag()
    # survivors re-send, the server probes the dead party — all in-ledger
    retry_cnt, retry_bytes = tags["retry_reps"]
    probe_cnt, probe_bytes = tags["retry_timeout"]
    assert retry_cnt == fa.retry_rounds          # one survivor x 2 rounds
    assert probe_cnt == fa.retry_rounds and probe_bytes == 4 * fa.retry_rounds
    d = res.diagnostics
    assert d["fault_modeled"] is True
    assert d["fault_retry_rounds"] == fa.retry_rounds
    assert d["fault_retry_bytes"] == retry_bytes + probe_bytes
    assert d["parties_survived"] == 1 and d["fault_kind"] == "dropout"
    # the stalled loop moved FEWER bytes than the full run, retries included
    assert res.ledger.total_bytes() < ref.ledger.total_bytes()
    assert "retry_reps" not in ref.ledger.by_tag()


def test_iterative_unmodeled_kinds_run_fault_free_and_say_so():
    """Straggler/DP/rep-only have no iterative model: the run must be
    byte-identical to fault-free and honestly flagged unmodeled — never a
    silent pretend-degradation."""
    split = _scenario_splits(0)[0]
    icfg = IterativeConfig(iterations=8)
    ref = run_vanilla(jax.random.PRNGKey(0), split, _ext(), _SSL, icfg)
    res = run_vanilla(jax.random.PRNGKey(0), split, _ext(), _SSL, icfg,
                      fault=FA_STRAG)
    assert float(res.metric) == float(ref.metric)
    _assert_ledgers_equal(res.ledger, ref.ledger)
    assert res.diagnostics["fault_modeled"] is False
    assert res.diagnostics["parties_survived"] == 2


def test_few_shot_finetune_refuses_faults(grid_splits):
    with pytest.raises(ValueError, match="does not support fault"):
        _few_shot_finetune_seeds(
            [jax.random.PRNGKey(0)], grid_splits[0][:1], [_ext()], [_SSL],
            _FAST, faults=[FA_DROP])


# ------------------------------------------------------- gate fault floors
_GATE_BASELINE = {
    "fault_families": {
        "fault": {
            "baseline_scenario": "fault/none",
            "max_oneshot_drop": 0.05,
            "required": ["fault/none", "fault/drop", "fault/strag"],
        },
    },
}

#: scenario -> (fault_kind, parties_survived of 4)
_GATE_SCENARIOS = {"fault/none": ("none", 4),
                   "fault/drop": ("dropout", 3),
                   "fault/strag": ("straggler", 4)}
_GMETRIC = {"one_shot": 0.90, "few_shot": 0.91,
            "iterative": 0.85, "fedcvt": 0.86}
_GBYTES = {"one_shot": 12288, "few_shot": 20480,
           "iterative": 12288 * 200, "fedcvt": 12288 * 220}


def _frow(method, seed, scenario, **over):
    kind, survived = _GATE_SCENARIOS[scenario]
    row = {
        "scenario": scenario,
        "seed": seed,
        "method": method,
        "metric_name": "accuracy",
        "metric": _GMETRIC[method],
        "comm_bytes": _GBYTES[method],
        "comm_times": 3,
        "overlap": 32,
        "num_parties": 4,
        "modality": "tabular",
        "fault_kind": kind,
        "parties_survived": survived,
    }
    if kind == "dropout":
        row["fault_stage"] = "pre_ssl"
        if method in ("iterative", "fedcvt"):
            row["fault_retry_rounds"] = 3
            row["fault_retry_bytes"] = 18444
    if method in ("one_shot", "few_shot"):
        row["degraded_metric"] = row["metric"]
    row.update(over)
    return row


def _fault_green_rows():
    return [_frow(m, s, scenario)
            for scenario in _GATE_SCENARIOS
            for m in frontier.METHODS for s in SEEDS]


@pytest.fixture
def fault_baseline_path(tmp_path):
    p = tmp_path / "baseline.json"
    p.write_text(json.dumps(_GATE_BASELINE))
    return str(p)


def test_fault_gate_green(fault_baseline_path):
    assert frontier.check_gate(_fault_green_rows(), fault_baseline_path,
                               expect_faults=True) == []


def test_zero_fault_rows_violate_only_in_full_sweeps(fault_baseline_path):
    plain = [{k: v for k, v in r.items()
              if k not in ("fault_kind", "parties_survived", "fault_stage",
                           "degraded_metric", "fault_retry_rounds",
                           "fault_retry_bytes")}
             for r in _fault_green_rows()]
    problems = frontier.check_gate(plain, fault_baseline_path,
                                   expect_faults=True)
    assert any("no fault-injected rows" in p for p in problems)
    # an explicit --scenarios selection is a partial sweep by construction
    assert frontier.check_gate(plain, fault_baseline_path,
                               expect_faults=False) == []


def test_missing_family_member_violates(fault_baseline_path):
    rows = [r for r in _fault_green_rows()
            if r["scenario"] != "fault/strag"]
    problems = frontier.check_gate(rows, fault_baseline_path,
                                   expect_faults=True)
    assert any("fault/strag" in p and "whole family" in p for p in problems)


def test_dropout_survivor_count_violates(fault_baseline_path):
    rows = _fault_green_rows()
    for r in rows:
        if r["scenario"] == "fault/drop" and r["method"] == "one_shot":
            r["parties_survived"] = 4          # nobody actually dropped
    problems = frontier.check_gate(rows, fault_baseline_path,
                                   expect_faults=True)
    assert any("parties_survived=4" in p and "expected 3" in p
               for p in problems)
    # ...and a NON-dropout fault must not lose anyone
    rows = _fault_green_rows()
    for r in rows:
        if r["scenario"] == "fault/strag" and r["method"] == "few_shot":
            r["parties_survived"] = 3
    problems = frontier.check_gate(rows, fault_baseline_path,
                                   expect_faults=True)
    assert any("straggler" in p and "expected 4" in p for p in problems)


def test_iterative_dropout_without_retry_cost_violates(fault_baseline_path):
    rows = _fault_green_rows()
    for r in rows:
        if r["scenario"] == "fault/drop" and r["method"] == "iterative":
            r["fault_retry_rounds"] = 0
            r["fault_retry_bytes"] = 0
    problems = frontier.check_gate(rows, fault_baseline_path,
                                   expect_faults=True)
    assert any("no retry/timeout cost" in p for p in problems)


def test_oneshot_degradation_floor_violates(fault_baseline_path):
    rows = _fault_green_rows()
    for r in rows:
        if r["scenario"] == "fault/drop" and r["method"] == "one_shot":
            r["metric"] = _GMETRIC["one_shot"] - 0.06   # beyond 0.05 budget
            r["degraded_metric"] = r["metric"]
    problems = frontier.check_gate(rows, fault_baseline_path,
                                   expect_faults=True)
    assert any("graceful degradation broke" in p for p in problems)


def test_missing_twin_and_missing_degraded_metric_violate(
        fault_baseline_path):
    rows = [r for r in _fault_green_rows()
            if not (r["scenario"] == "fault/none"
                    and r["method"] == "one_shot")]
    problems = frontier.check_gate(rows, fault_baseline_path,
                                   expect_faults=True)
    assert any("no one_shot rows to measure degradation" in p
               for p in problems)
    rows = _fault_green_rows()
    for r in rows:
        if r["scenario"] == "fault/drop" and r["method"] == "few_shot":
            r.pop("degraded_metric")
    problems = frontier.check_gate(rows, fault_baseline_path,
                                   expect_faults=True)
    assert any("no degraded_metric" in p for p in problems)
