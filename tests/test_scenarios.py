"""Scenario registry + iterative-engine session coverage.

* registry round-trips: get/replace/hash, duplicate rejection, tag queries,
  smoke shrinking;
* every registered scenario builds and completes a one-shot session (tiny
  budgets, smoke sizes) with the paper's 3 comm times;
* the iterative baselines' ledgers count exactly one up + one down transfer
  per round, on the engine's scan fold and on the per-step host loop
  (``engine_oracles``) alike, with byte-identical totals;
* the engine's iterative session cache re-serves compiled programs across
  calls (the no-recompile contract of DESIGN.md §8).
"""
import dataclasses

import jax
import pytest
from engine_oracles import iterative_loop_seeds

from repro import scenarios
from repro.core import (IterativeConfig, ProtocolConfig, run_one_shot,
                        run_vanilla)
from repro.engine import iterative, sessions


def _on_path(mode, monkeypatch):
    """Steer the iterative sessions onto the host loop for "python"."""
    if mode == "python":
        monkeypatch.setattr(iterative, "run_iterative_session_seeds",
                            iterative_loop_seeds)


def test_registry_roundtrip():
    names = scenarios.names()
    assert len(names) >= 12
    spec = scenarios.get("hard/overlap-32")
    assert spec.name == "hard/overlap-32"
    clone = dataclasses.replace(spec)
    assert clone == spec and hash(clone) == hash(spec)
    assert spec.budget("client_epochs", 1) == 80
    assert spec.budget("not-a-budget", 7) == 7
    with pytest.raises(KeyError):
        scenarios.get("no/such-scenario")
    with pytest.raises(ValueError):
        scenarios.register(spec)            # duplicate name rejected


def test_catalog_covers_the_papers_axes():
    assert {f"credit/overlap-{n}" for n in (32, 2048)} <= set(scenarios.names())
    assert any(s.num_parties == 8 for s in scenarios.by_tag("parties"))
    assert any(s.image_grid for s in scenarios.by_tag("image"))
    assert len(scenarios.by_tag("smoke")) >= 2
    skew = scenarios.get("credit/feature-skew")
    assert skew.feature_sizes[0] > 3 * skew.feature_sizes[1]


def test_smoke_variant_shrinks_but_preserves_condition():
    spec = scenarios.get("credit/overlap-2048")
    small = spec.smoke()
    assert small.overlap <= spec.smoke_overlap
    assert small.num_samples <= spec.smoke_samples
    assert small.name == spec.name
    assert small.gen_params == spec.gen_params


@pytest.mark.parametrize("name", scenarios.names())
def test_every_scenario_builds_and_runs_one_shot(name):
    bundle = scenarios.build(name, seed=0, smoke=True)
    spec = bundle.spec
    assert len(bundle.split.aligned) == spec.num_parties
    assert len(bundle.extractors) == spec.num_parties
    if spec.overlap_capacity is None:
        assert bundle.split.labels.shape[0] == spec.overlap
        assert bundle.split.aligned_mask is None
    else:
        # equal-shape family (DESIGN.md §14): the aligned block is padded
        # to the fixed capacity; the mask marks the N_o real rows
        assert bundle.split.labels.shape[0] == spec.overlap_capacity
        assert int(bundle.split.aligned_mask.sum()) == spec.overlap
    res = run_one_shot(jax.random.PRNGKey(0), bundle.split, bundle.extractors,
                       bundle.ssl_cfgs,
                       ProtocolConfig(client_epochs=1, server_epochs=1))
    assert res.ledger.comm_times() == 3         # THE paper invariant
    assert 0.0 <= res.metric <= 1.0


@pytest.mark.parametrize("mode", ["scan", "python"])
def test_iterative_ledger_counts_one_up_one_down_per_round(mode, monkeypatch):
    _on_path(mode, monkeypatch)
    bundle = scenarios.build("credit/overlap-64", seed=0, smoke=True)
    res = run_vanilla(jax.random.PRNGKey(1), bundle.split, bundle.extractors,
                      bundle.ssl_cfgs, IterativeConfig(iterations=25))
    # 2 rounds (reps up, grads down) per iteration per client
    assert res.ledger.comm_times() == 2 * 25
    ups = [e for e in res.ledger.events if e.direction == "up"]
    downs = [e for e in res.ledger.events if e.direction == "down"]
    assert len(ups) == len(downs) == 25 * 2      # per client per iteration
    bs, rep = 32, bundle.spec.rep_dim
    assert res.ledger.total_bytes() == 25 * 2 * 2 * bs * rep * 4


def test_iterative_engine_modes_agree(monkeypatch):
    bundle = scenarios.build("credit/overlap-64", seed=0, smoke=True)
    runs = {}
    for mode in ("scan", "python"):
        _on_path(mode, monkeypatch)
        res = run_vanilla(jax.random.PRNGKey(2), bundle.split,
                          bundle.extractors, bundle.ssl_cfgs,
                          IterativeConfig(iterations=30))
        runs[mode] = res
    assert abs(runs["scan"].metric - runs["python"].metric) < 1e-4
    assert (runs["scan"].ledger.total_bytes()
            == runs["python"].ledger.total_bytes())


def test_iterative_session_cache_reuses_compiled_program():
    sessions.clear_session_cache()
    bundle = scenarios.build("hard/overlap-32", seed=0, smoke=True)
    cfg = IterativeConfig(iterations=10)
    for seed in (0, 1):
        run_vanilla(jax.random.PRNGKey(seed), bundle.split, bundle.extractors,
                    bundle.ssl_cfgs, cfg)
    stats = sessions.session_cache_stats("iterative")
    assert stats["misses"] == 1                  # compiled exactly once
    assert stats["hits"] == 1                    # second session re-served
    # fresh-but-equivalent extractors (same factory arguments) also hit
    b2 = scenarios.build("hard/overlap-32", seed=2, smoke=True)
    run_vanilla(jax.random.PRNGKey(3), b2.split, b2.extractors, b2.ssl_cfgs,
                cfg)
    assert sessions.session_cache_stats("iterative")["hits"] == 2
