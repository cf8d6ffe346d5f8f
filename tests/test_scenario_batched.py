"""Scenario-axis folding (DESIGN.md §12): ``run_scenarios_seeds`` stacks C
grouped scenarios × S seeds × K parties onto the engine's one anonymous
batch axis and must be indistinguishable from the per-scenario loop:

* per-(scenario, seed) metrics AND parameter leaves within 1e-5 of
  ``run_seeds`` run scenario by scenario — for one-shot, few-shot, and the
  iterative scan fold;
* ledgers byte-identical per (scenario, seed) against the loop's;
* the warm-cache contract: C >= 2 adds ZERO fresh session-cache misses
  over a C = 1 run (the cache keys carry neither batch width nor data
  shapes — ``run_seeds`` IS the width-1 case of the same code);
* heterogeneous-shape grids fall back to the per-scenario path and say so
  (``scenario_fold`` 1);

plus Hypothesis property tests for the group partitioner
(``scenarios.grouping``): arbitrary catalog subsets partition into an
exact cover whose groups satisfy the engine's ``parties_are_homogeneous``
predicate across members, arch/shape mismatches fall out as singletons,
and group order is deterministic.
"""
import dataclasses

import jax
import jax.numpy as jnp
import pytest

from repro import engine, scenarios
from repro.core import (IterativeConfig, ProtocolConfig, SSLConfig,
                        run_few_shot, run_one_shot, run_vanilla)
from repro.core.protocol import run_scenarios_seeds, run_seeds
from repro.data import make_tabular_credit, make_vfl_partition
from repro.models import make_mlp_extractor
from repro.scenarios import grouping

_FAST = ProtocolConfig(client_epochs=2, server_epochs=3)
SEEDS = (0, 1)
_SSL = [SSLConfig(modality="tabular")] * 2


def _ext():
    return [make_mlp_extractor(rep_dim=8, hidden=(16,)) for _ in range(2)]


def _scenario_splits(c, overlap=64):
    """One synthetic 'scenario': same shapes for every c, different data."""
    out = []
    for s in SEEDS:
        x, y = make_tabular_credit(jax.random.PRNGKey(5000 + 97 * c + s), 700)
        out.append(make_vfl_partition(x[:, :22], y, overlap_size=overlap,
                                      feature_sizes=[11, 11], seed=s))
    return out


@pytest.fixture(scope="module")
def grid_splits():
    return [_scenario_splits(0), _scenario_splits(1)]


def _run_grid(runner, grid_splits, cfg=_FAST):
    num_scenarios = len(grid_splits)
    return run_scenarios_seeds(
        runner,
        [[jax.random.PRNGKey(s) for s in SEEDS]
         for _ in range(num_scenarios)],
        grid_splits,
        [[_ext() for _ in SEEDS] for _ in range(num_scenarios)],
        [[_SSL for _ in SEEDS] for _ in range(num_scenarios)],
        cfg)


def _run_loop(runner, grid_splits, cfg=_FAST):
    return [run_seeds(runner, [jax.random.PRNGKey(s) for s in SEEDS], sp,
                      [_ext() for _ in SEEDS], [_SSL for _ in SEEDS], cfg)
            for sp in grid_splits]


def _assert_ledgers_equal(a, b):
    assert a.total_bytes() == b.total_bytes()
    assert a.comm_times() == b.comm_times()
    assert a.by_tag() == b.by_tag()


def _assert_grid_matches_loop(folded, loop):
    for scen_folded, scen_loop in zip(folded, loop):
        for res, ref in zip(scen_folded, scen_loop):
            assert abs(float(res.metric) - float(ref.metric)) < 1e-5, \
                (float(res.metric), float(ref.metric))
            assert res.diagnostics["device_fold"] == \
                ref.diagnostics["device_fold"]
            _assert_ledgers_equal(res.ledger, ref.ledger)
            for cb, cs in zip(res.clients, ref.clients):
                for lb, ls in zip(jax.tree_util.tree_leaves(cb.params),
                                  jax.tree_util.tree_leaves(cs.params)):
                    assert jnp.allclose(lb, ls, atol=1e-5), \
                        float(jnp.max(jnp.abs(lb - ls)))


def test_scenario_fold_matches_per_scenario_loop_one_shot(grid_splits):
    """The tentpole parity: one folded C=2 × S=2 one-shot sweep == the
    per-scenario ``run_seeds`` loop at 1e-5 on metric and every client
    parameter leaf, with byte-identical per-(scenario, seed) ledgers."""
    folded = _run_grid(run_one_shot, grid_splits)
    loop = _run_loop(run_one_shot, grid_splits)
    _assert_grid_matches_loop(folded, loop)
    flat = [r for scen in folded for r in scen]
    assert len({id(r.ledger) for r in flat}) == len(flat)   # per-entry copies
    for r in flat:
        assert r.diagnostics["seed_fold"] == len(SEEDS)
        assert r.diagnostics["scenario_fold"] == len(grid_splits)
    # communication is a shape function: byte-identity holds across the
    # whole flat batch, not just within a scenario
    for r in flat[1:]:
        _assert_ledgers_equal(r.ledger, flat[0].ledger)


def test_scenario_fold_matches_per_scenario_loop_few_shot(grid_splits):
    """Same parity through the whole few-shot pipeline (aux fits, SDPA
    gating, masked phase ⑤', final re-fit) — including the Eq. 9 gate's
    per-party take rates, which must not feel their fold neighbors."""
    folded = _run_grid(run_few_shot, grid_splits)
    loop = _run_loop(run_few_shot, grid_splits)
    _assert_grid_matches_loop(folded, loop)
    for scen_folded, scen_loop in zip(folded, loop):
        for res, ref in zip(scen_folded, scen_loop):
            assert res.diagnostics["fewshot_take_rate"] == \
                ref.diagnostics["fewshot_take_rate"]


def test_scenario_fold_matches_per_scenario_loop_iterative(grid_splits):
    """The §11 scan fold rides the same anonymous axis: C·S stacked
    whole-session carries == the per-scenario loop."""
    icfg = IterativeConfig(iterations=10)
    folded = _run_grid(run_vanilla, grid_splits, icfg)
    loop = _run_loop(run_vanilla, grid_splits, icfg)
    _assert_grid_matches_loop(folded, loop)
    for scen in folded:
        for r in scen:
            assert r.diagnostics["scenario_fold"] == len(grid_splits)


def test_scenario_fold_adds_zero_fresh_session_misses(grid_splits):
    """The warm-cache contract behind the grouped frontier: after a C = 1
    run, folding C >= 2 scenarios must add ZERO fresh session-cache misses
    in ANY domain — same model identity, same hparams, and the keys carry
    neither batch width nor data shapes. (The cache is deliberately NOT
    cleared between the two runs: the C >= 2 sweep must re-serve the C = 1
    programs.)"""
    engine.clear_session_cache()
    run_seeds(run_few_shot, [jax.random.PRNGKey(s) for s in SEEDS],
              grid_splits[0], [_ext() for _ in SEEDS],
              [_SSL for _ in SEEDS], _FAST)
    warm = {d: st["misses"]
            for d, st in engine.session_cache_stats_by_domain().items()}
    _run_grid(run_few_shot, grid_splits)
    after = {d: st["misses"]
             for d, st in engine.session_cache_stats_by_domain().items()}
    assert after == warm, (warm, after)


def test_heterogeneous_grid_falls_back_per_scenario(grid_splits):
    """Scenarios whose splits don't share one shape cannot stack: the grid
    runs scenario by scenario (each still seed-folded) and the results say
    so via scenario_fold — the signal the frontier gate asserts on."""
    grid = [grid_splits[0], _scenario_splits(1, overlap=96)]
    folded = _run_grid(run_one_shot, grid)
    loop = _run_loop(run_one_shot, grid)
    _assert_grid_matches_loop(folded, loop)
    for scen in folded:
        for r in scen:
            assert r.diagnostics["scenario_fold"] == 1
            assert r.diagnostics["seed_fold"] == len(SEEDS)


def test_run_seeds_is_the_width_one_case(grid_splits):
    """C = 1 through ``run_seeds`` reports scenario_fold 1 — the width-1
    invariant the C >= 2 fold generalizes (same impls, same cache keys)."""
    results = run_seeds(run_one_shot, [jax.random.PRNGKey(s) for s in SEEDS],
                        grid_splits[0], [_ext() for _ in SEEDS],
                        [_SSL for _ in SEEDS], _FAST)
    for r in results:
        assert r.diagnostics["scenario_fold"] == 1
        assert r.diagnostics["seed_fold"] == len(SEEDS)


def test_run_scenarios_seeds_rejects_state_kwargs_and_ragged_grids(
        grid_splits):
    keys = [[jax.random.PRNGKey(s) for s in SEEDS] for _ in range(2)]
    ext = [[_ext() for _ in SEEDS] for _ in range(2)]
    ssl = [[_SSL for _ in SEEDS] for _ in range(2)]
    with pytest.raises(ValueError, match="state kwargs"):
        run_scenarios_seeds(run_one_shot, keys, grid_splits, ext, ssl,
                            _FAST, clients=None)
    ragged = [grid_splits[0], grid_splits[1][:1]]
    with pytest.raises(ValueError, match="rectangular"):
        run_scenarios_seeds(run_one_shot, keys, ragged, ext, ssl, _FAST)


# ------------------------------------------------ partitioner properties
import random  # noqa: E402

_NAMES = scenarios.names()
_CATALOG: dict = {}


def _entry(name):
    """Built catalog entry (spec, bundle), cached across examples —
    building draws the synthetic dataset, grouping does not."""
    if name not in _CATALOG:
        bundle = scenarios.build(name, seed=0, smoke=True)
        _CATALOG[name] = (bundle.spec, bundle)
    return _CATALOG[name]


def _check_exact_cover_of_homogeneous_groups(subset):
    """Any catalog subset partitions into an exact cover; within every
    group the engine's ``parties_are_homogeneous`` predicate holds across
    members party position by party position (plus full shape equality) —
    the stackability ground truth behind the fold signature."""
    entries = [_entry(n) for n in subset]
    groups = scenarios.group_scenarios(entries)
    flat = sorted(i for g in groups for i in g.indices)
    assert flat == list(range(len(entries)))
    for g in groups:
        assert g.names == [entries[i][0].name for i in g.indices]
        head = entries[g.indices[0]][1]
        for i in g.indices[1:]:
            assert grouping.bundles_fold_compatible(entries[i][1], head)
            assert grouping.split_signature(entries[i][1].split) == \
                grouping.split_signature(head.split)


def _check_deterministic_and_order_preserving(subset):
    """Same input ⇒ same groups, groups in first-occurrence order, members
    in input order — the frontier's row order must be reproducible."""
    entries = [_entry(n) for n in subset]
    first = scenarios.group_scenarios(entries)
    second = scenarios.group_scenarios(entries)
    assert [g.indices for g in first] == [g.indices for g in second]
    assert [g.names for g in first] == [g.names for g in second]
    assert [g.indices[0] for g in first] == \
        sorted(g.indices[0] for g in first)
    for g in first:
        assert g.indices == sorted(g.indices)


def _fixed_subsets():
    """Deterministic fallback corpus for images without Hypothesis: the
    full catalog, every singleton, and seeded random subsets/orderings."""
    rng = random.Random(0)
    subsets = [list(_NAMES)] + [[n] for n in _NAMES]
    for _ in range(15):
        k = rng.randint(1, len(_NAMES))
        subsets.append(rng.sample(_NAMES, k))
    return subsets


def test_partitioner_properties_on_fixed_subsets():
    """The partitioner invariants on a deterministic corpus — always runs
    in tier-1, with or without Hypothesis."""
    for subset in _fixed_subsets():
        _check_exact_cover_of_homogeneous_groups(subset)
        _check_deterministic_and_order_preserving(subset)


def test_partition_is_exact_cover_of_homogeneous_groups_hypothesis():
    hypothesis = pytest.importorskip("hypothesis")
    from hypothesis import strategies as st

    @hypothesis.settings(max_examples=25, deadline=None)
    @hypothesis.given(st.lists(st.sampled_from(_NAMES), unique=True,
                               min_size=1))
    def check(subset):
        _check_exact_cover_of_homogeneous_groups(subset)

    check()


def test_partition_deterministic_and_order_preserving_hypothesis():
    hypothesis = pytest.importorskip("hypothesis")
    from hypothesis import strategies as st

    @hypothesis.settings(max_examples=25, deadline=None)
    @hypothesis.given(st.lists(st.sampled_from(_NAMES), unique=True,
                               min_size=1))
    def check(subset):
        _check_deterministic_and_order_preserving(subset)

    check()


def test_partition_none_and_distinct_signatures_are_singletons():
    """The pure bucketing law: ``None`` (unhashable) signatures never
    group, equal signatures always do, order is first-occurrence."""
    assert grouping.partition(["a", None, "a", "b", None]) == \
        [[0, 2], [1], [3], [4]]
    assert grouping.partition([]) == []


def test_arch_mismatch_falls_out_as_singleton():
    """Equal shapes with a DIFFERENT architecture must not group: the
    signature carries the apply-fn identity (``model_key``), exactly like
    the engine predicate."""
    spec, bundle = _entry("credit/overlap-32")
    other = dataclasses.replace(
        bundle, extractors=[make_mlp_extractor(rep_dim=16, hidden=(32,))
                            for _ in range(2)])
    groups = scenarios.group_scenarios([(spec, bundle), (spec, other)])
    assert [g.indices for g in groups] == [[0], [1]]
    assert not grouping.bundles_fold_compatible(bundle, other)


def test_known_catalog_groups():
    """Pin the catalog's smoke-size group structure the frontier relies
    on: the credit sweep family folds into one stack; hard/* (different
    N_o ⇒ different schedule shapes) and the party-count/feature-skew
    variants stay apart."""
    entries = [_entry(n) for n in _NAMES]
    groups = scenarios.group_scenarios(entries)
    group_of = {n: gi for gi, g in enumerate(groups) for n in g.names}
    family = ["credit/overlap-32", "credit/overlap-64", "credit/overlap-128",
              "credit/overlap-256", "credit/label-noise"]
    assert len({group_of[n] for n in family}) == 1
    assert group_of["hard/overlap-32"] != group_of["hard/overlap-64"]
    # the equal-shape variants exist precisely to close that gap: a fixed
    # 64-row aligned capacity + validity mask gives both members ONE shape
    # signature, so they stack — while staying apart from the unmasked
    # hard/overlap-64 (same shapes, but the mask changes the loss)
    assert group_of["hard/overlap-32-eq"] == group_of["hard/overlap-64-eq"]
    assert group_of["hard/overlap-64-eq"] != group_of["hard/overlap-64"]
    for loner in ("credit/feature-skew", "credit/parties-4",
                  "credit/parties-8", "image/halves", "image/patch-4"):
        assert sum(1 for n in _NAMES if group_of[n] == group_of[loner]) == 1
