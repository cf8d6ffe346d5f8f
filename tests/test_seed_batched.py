"""Seed-batched execution (DESIGN.md §10-11): ``run_seeds`` folds S seeds
of one scenario point into the engine's stacked programs and must be
indistinguishable from a Python loop of single-seed runs:

* per-seed metrics within 1e-5 of the loop's (params too, for one-shot
  and the iterative baselines);
* ledgers byte-identical — across seeds AND against the loop;
* seeds >= 2 add ZERO fresh compiled-session builds over a 1-seed run
  (the cache keys carry no batch width; ``jax.jit`` re-specializes the
  one cached session per stacked shape);
* the seed-folded k-means is bit-identical to the per-call path;
* the ITERATIVE fold (§11): ``run_vanilla``/``run_fedcvt``/``run_fedbcd``
  stack their whole-session scan carries on a leading seed axis, and the
  chained ``run_few_shot_finetune`` threads the folded few-shot output
  carry straight into the folded finetune session.

Plus the single-seed blind-spot regressions PR 4 fixed:

* ``build_schedule``'s epoch-0 labeled/unlabeled RNG-stream collision;
* the ``n_unlabeled == 0`` (full-overlap party) NaN;
* ``parties_are_homogeneous`` — the spec-level engine predicate (apply-fn
  identity, not the shape heuristic);
* few-shot phase ⑤' reusing the step-③ cluster pseudo-labels Ŷ_o^k.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import engine
from repro.core import (IterativeConfig, ProtocolConfig, SSLConfig,
                        run_fedbcd, run_fedcvt, run_few_shot,
                        run_few_shot_finetune, run_one_shot, run_vanilla)
from repro.core.protocol import fewshot_phase5_labels, run_seeds
from repro.data import make_tabular_credit, make_vfl_partition
from repro.models import make_mlp_extractor

_FAST = ProtocolConfig(client_epochs=2, server_epochs=3)
SEEDS = (0, 1)


def _splits():
    out = []
    for s in SEEDS:
        x, y = make_tabular_credit(jax.random.PRNGKey(1000 + s), 700)
        out.append(make_vfl_partition(x[:, :22], y, overlap_size=64,
                                      feature_sizes=[11, 11], seed=s))
    return out


def _ext():
    return [make_mlp_extractor(rep_dim=8, hidden=(16,)) for _ in range(2)]


_SSL = [SSLConfig(modality="tabular")] * 2


def _run_seeds(runner, splits, cfg=_FAST):
    return run_seeds(runner, [jax.random.PRNGKey(s) for s in SEEDS], splits,
                     [_ext() for _ in SEEDS], [_SSL for _ in SEEDS], cfg)


def _assert_ledgers_equal(a, b):
    assert a.total_bytes() == b.total_bytes()
    assert a.comm_times() == b.comm_times()
    assert a.by_tag() == b.by_tag()


@pytest.fixture(scope="module")
def splits():
    return _splits()


def test_run_seeds_matches_single_seed_loop_one_shot(splits):
    """The tentpole parity: the S·K-folded one-shot run per seed == the
    single-seed runner, at 1e-5 on the metric AND every client parameter
    leaf, with byte-identical ledgers."""
    batched = _run_seeds(run_one_shot, splits)
    assert batched[0].ledger is not batched[1].ledger
    for s, split in zip(SEEDS, splits):
        solo = run_one_shot(jax.random.PRNGKey(s), split, _ext(), _SSL, _FAST)
        res = batched[SEEDS.index(s)]
        assert abs(float(res.metric) - float(solo.metric)) < 1e-5, \
            (s, float(res.metric), float(solo.metric))
        _assert_ledgers_equal(res.ledger, solo.ledger)
        for cb, cs in zip(res.clients, solo.clients):
            for lb, ls in zip(jax.tree_util.tree_leaves(cb.params),
                              jax.tree_util.tree_leaves(cs.params)):
                assert jnp.allclose(lb, ls, atol=1e-5), \
                    float(jnp.max(jnp.abs(lb - ls)))
    # byte-identity ACROSS seeds too (communication is a shape function)
    _assert_ledgers_equal(batched[0].ledger, batched[1].ledger)


def test_run_seeds_matches_single_seed_loop_few_shot(splits):
    """Same parity through the whole few-shot pipeline (aux fits, SDPA
    gating, masked phase ⑤', final re-fit)."""
    batched = _run_seeds(run_few_shot, splits)
    for s, split in zip(SEEDS, splits):
        solo = run_few_shot(jax.random.PRNGKey(s), split, _ext(), _SSL, _FAST)
        res = batched[SEEDS.index(s)]
        assert abs(float(res.metric) - float(solo.metric)) < 1e-5, \
            (s, float(res.metric), float(solo.metric))
        _assert_ledgers_equal(res.ledger, solo.ledger)
        assert res.diagnostics["fewshot_take_rate"] == \
            solo.diagnostics["fewshot_take_rate"]
    _assert_ledgers_equal(batched[0].ledger, batched[1].ledger)


def test_seed_batch_adds_zero_fresh_compiles(splits):
    """Seeds >= 2 must add ZERO fresh compiled-session builds over a
    single-seed run: the session cache keys on semantic step identity,
    never on the stacked batch width."""
    engine.clear_session_cache()
    run_seeds(run_few_shot, [jax.random.PRNGKey(0)], splits[:1], [_ext()],
              [_SSL], _FAST)
    one_seed = {d: st["misses"]
                for d, st in engine.session_cache_stats_by_domain().items()}
    engine.clear_session_cache()
    _run_seeds(run_few_shot, splits)
    two_seeds = {d: st["misses"]
                 for d, st in engine.session_cache_stats_by_domain().items()}
    assert two_seeds == one_seed, (one_seed, two_seeds)


@pytest.mark.parametrize("runner,icfg", [
    (run_vanilla, IterativeConfig(iterations=20)),
    (run_fedcvt, IterativeConfig(iterations=10)),
    (run_fedbcd, IterativeConfig(iterations=20)),
], ids=["vanilla", "fedcvt", "fedbcd"])
def test_run_seeds_matches_single_seed_loop_iterative(runner, icfg, splits):
    """The §11 parity: every iterative baseline's seed fold (stacked
    whole-session scan carries, one vmap-of-scan program) per seed == the
    single-seed runner at 1e-5 on the metric AND every client parameter
    leaf, with byte-identical ledgers across seeds and vs the loop."""
    batched = _run_seeds(runner, splits, icfg)
    assert batched[0].ledger is not batched[1].ledger
    _assert_ledgers_equal(batched[0].ledger, batched[1].ledger)
    for s, split in zip(SEEDS, splits):
        solo = runner(jax.random.PRNGKey(s), split, _ext(), _SSL, icfg)
        res = batched[SEEDS.index(s)]
        assert abs(float(res.metric) - float(solo.metric)) < 1e-5, \
            (s, float(res.metric), float(solo.metric))
        _assert_ledgers_equal(res.ledger, solo.ledger)
        assert res.diagnostics["device_fold"] == \
            solo.diagnostics["device_fold"]
        for cb, cs in zip(res.clients, solo.clients):
            for lb, ls in zip(jax.tree_util.tree_leaves(cb.params),
                              jax.tree_util.tree_leaves(cs.params)):
                assert jnp.allclose(lb, ls, atol=1e-5), \
                    float(jnp.max(jnp.abs(lb - ls)))


def test_seed_batched_iterative_adds_zero_fresh_compiles(splits):
    """Seeds >= 2 of an iterative baseline must add ZERO fresh compiled-
    session builds over a single-seed run: the width-1 session IS the
    folded session (one cache key, no batch width in it)."""
    icfg = IterativeConfig(iterations=10)
    engine.clear_session_cache()
    run_seeds(run_vanilla, [jax.random.PRNGKey(0)], splits[:1], [_ext()],
              [_SSL], icfg)
    one_seed = {d: st["misses"]
                for d, st in engine.session_cache_stats_by_domain().items()}
    # the stronger §11 guarantee: a LATER multi-seed run re-serves the
    # single-seed program — don't even clear the cache
    _run_seeds(run_vanilla, splits, icfg)
    two_seeds = {d: st["misses"]
                 for d, st in engine.session_cache_stats_by_domain().items()}
    assert two_seeds == one_seed, (one_seed, two_seeds)


def test_run_seeds_few_shot_finetune_chains_the_folds(splits):
    """The chained fold: seed-batched few-shot hands its per-seed output
    state to the seed-batched vanilla finetune inside one ``run_seeds``
    call — per seed == the single-seed ``run_few_shot_finetune`` at 1e-5,
    with the combined (few-shot + finetune) ledger byte-identical."""
    batched = run_seeds(run_few_shot_finetune,
                        [jax.random.PRNGKey(s) for s in SEEDS], splits,
                        [_ext() for _ in SEEDS], [_SSL for _ in SEEDS],
                        _FAST, finetune_iterations=20)
    _assert_ledgers_equal(batched[0].ledger, batched[1].ledger)
    for s, split in zip(SEEDS, splits):
        solo = run_few_shot_finetune(jax.random.PRNGKey(s), split, _ext(),
                                     _SSL, _FAST, finetune_iterations=20)
        res = batched[SEEDS.index(s)]
        assert abs(float(res.metric) - float(solo.metric)) < 1e-5, \
            (s, float(res.metric), float(solo.metric))
        assert abs(res.diagnostics["fewshot_metric"]
                   - solo.diagnostics["fewshot_metric"]) < 1e-5
        _assert_ledgers_equal(res.ledger, solo.ledger)
        # the combined ledger spans both stages: 5 few-shot comm times
        # plus 2 per finetune iteration
        assert res.ledger.comm_times() == 5 + 2 * 20


def test_run_seeds_unregistered_runner_falls_back_to_loop(splits):
    """Runners outside the batched_impl registry still work: run_seeds
    loops per seed over the runner's cached sessions, asserts ledger
    byte-identity post hoc, and each seed matches a direct call."""
    icfg = IterativeConfig(iterations=10)

    def wrapped_vanilla(key, split, extractors, ssl_cfgs, cfg, **kw):
        return run_vanilla(key, split, extractors, ssl_cfgs, cfg, **kw)

    results = run_seeds(wrapped_vanilla, [jax.random.PRNGKey(s) for s in SEEDS],
                        splits, [_ext() for _ in SEEDS],
                        [_SSL for _ in SEEDS], icfg)
    _assert_ledgers_equal(results[0].ledger, results[1].ledger)
    solo = run_vanilla(jax.random.PRNGKey(SEEDS[0]), splits[0], _ext(), _SSL,
                       icfg)
    assert float(results[0].metric) == pytest.approx(float(solo.metric),
                                                     abs=1e-6)


def test_run_seeds_heterogeneous_splits_fall_back_to_loop():
    """Seed sets whose splits don't share one shape take the same loop —
    even for a registered runner — and the ledger identity still holds
    when the byte-determining shapes (bs, rep_dim, iterations) agree."""
    splits = []
    for s, overlap in zip(SEEDS, (64, 96)):   # n differs; bs=32 both
        x, y = make_tabular_credit(jax.random.PRNGKey(2000 + s), 700)
        splits.append(make_vfl_partition(x[:, :22], y, overlap_size=overlap,
                                         feature_sizes=[11, 11], seed=s))
    icfg = IterativeConfig(iterations=10)
    results = run_seeds(run_vanilla, [jax.random.PRNGKey(s) for s in SEEDS],
                        splits, [_ext() for _ in SEEDS],
                        [_SSL for _ in SEEDS], icfg)
    _assert_ledgers_equal(results[0].ledger, results[1].ledger)
    for res in results:
        assert res.diagnostics["seed_fold"] == 1   # looped, not folded


def test_run_seeds_rejects_per_seed_state_kwargs(splits):
    """One clients/server/ledger object cannot serve S seeds — run_seeds
    must refuse instead of crashing in the batched path or silently
    accumulating a shared ledger in the loop path."""
    with pytest.raises(ValueError, match="state kwargs"):
        run_seeds(run_one_shot, [jax.random.PRNGKey(0)], splits[:1],
                  [_ext()], [_SSL], _FAST, clients=None)


def test_pseudo_labels_seeds_bit_identical_to_per_call():
    """The seed-folded k-means (one vmapped program over the S·K gradient
    stack) must assign exactly the labels of the per-call path."""
    grads = jax.random.normal(jax.random.PRNGKey(0), (6, 32, 16))
    keys = list(jax.random.split(jax.random.PRNGKey(7), 6))
    folded = engine.pseudo_labels_seeds(keys, list(grads), num_classes=2,
                                        kmeans_iters=25)
    for k, g, f in zip(keys, grads, folded):
        eager = engine.pseudo_labels(k, g, 2, 25)
        assert bool(jnp.all(f == eager))


# --------------------------------------- kernel-route folds (DESIGN.md §15)
def test_pseudo_labels_seeds_use_kernels_keeps_the_fold():
    """The retired fallback: seeds >= 2 under ``use_kernels=True`` must run
    the ONE batched Pallas grid — ``info["fold"]`` records the full stacked
    width, no fallback reason — and match the per-call kernel path
    bit-exactly."""
    grads = jax.random.normal(jax.random.PRNGKey(0), (6, 32, 16))
    keys = list(jax.random.split(jax.random.PRNGKey(7), 6))
    info = {}
    folded = engine.pseudo_labels_seeds(keys, list(grads), num_classes=2,
                                        kmeans_iters=25, use_kernels=True,
                                        info=info)
    assert info["fold"] == 6
    assert "fallback" not in info
    for k, g, f in zip(keys, grads, folded):
        eager = engine.pseudo_labels(k, g, 2, 25, use_kernels=True)
        assert bool(jnp.all(f == eager))
        # and the kernel route assigns exactly the jnp route's labels
        assert bool(jnp.all(f == engine.pseudo_labels(k, g, 2, 25)))


def test_pseudo_labels_seeds_ragged_fallback_is_recorded():
    """Only ragged gradient stacks may take the per-entry loop now — and
    the reason lands in ``info`` (→ ``kernel_fallback`` on result rows)."""
    keys = list(jax.random.split(jax.random.PRNGKey(3), 2))
    grads = [jax.random.normal(jax.random.PRNGKey(0), (32, 16)),
             jax.random.normal(jax.random.PRNGKey(1), (40, 16))]
    info = {}
    folded = engine.pseudo_labels_seeds(keys, grads, num_classes=2,
                                        use_kernels=True, info=info)
    assert info["fold"] == 1
    assert "ragged" in info["fallback"]
    for k, g, f in zip(keys, grads, folded):
        assert bool(jnp.all(f == engine.pseudo_labels(k, g, 2,
                                                      use_kernels=True)))


def test_run_seeds_use_kernels_one_shot_parity_and_fold(splits):
    """One-shot under the kernel route: per-seed metric == the jnp route's
    (the kernel assignment is bit-equal to the oracle), and every result
    records kernel_fold == S·K with no fallback."""
    cfg = dataclasses.replace(_FAST, use_kernels=True)
    kernel = _run_seeds(run_one_shot, splits, cfg)
    plain = _run_seeds(run_one_shot, splits)
    for rk, rj in zip(kernel, plain):
        assert abs(float(rk.metric) - float(rj.metric)) < 1e-5
        assert rk.diagnostics["kernel_fold"] == len(SEEDS) * 2   # S=2 × K=2
        assert "kernel_fallback" not in rk.diagnostics


def test_run_seeds_use_kernels_few_shot_matches_solo_kernel_route(splits):
    """Few-shot under ``use_kernels=True``: the seed fold == the solo run
    on the SAME route at 1e-5 (take rates exactly equal), with the fold
    diagnostics pinning the stacked widths — kernel_fold S·K on the folded
    rows vs 1·K solo, sdpa_fold S vs 1."""
    cfg = dataclasses.replace(_FAST, use_kernels=True)
    batched = _run_seeds(run_few_shot, splits, cfg)
    for s, split in zip(SEEDS, splits):
        solo = run_few_shot(jax.random.PRNGKey(s), split, _ext(), _SSL, cfg)
        res = batched[SEEDS.index(s)]
        assert abs(float(res.metric) - float(solo.metric)) < 1e-5, \
            (s, float(res.metric), float(solo.metric))
        assert res.diagnostics["fewshot_take_rate"] == \
            solo.diagnostics["fewshot_take_rate"]
        _assert_ledgers_equal(res.ledger, solo.ledger)
        assert res.diagnostics["kernel_fold"] == len(SEEDS) * 2
        assert solo.diagnostics["kernel_fold"] == 2               # 1 seed × K
        assert res.diagnostics["sdpa_fold"] == len(SEEDS)
        assert solo.diagnostics["sdpa_fold"] == 1
        assert "kernel_fallback" not in res.diagnostics


def test_use_kernels_seed_batch_adds_zero_fresh_compiles(splits):
    """The cache discipline holds on the kernel route too: seeds >= 2 add
    ZERO fresh session builds over a 1-seed kernel-route run (the kmeans/
    sdpa/fewshot_gate keys carry the route, never the width)."""
    cfg = dataclasses.replace(_FAST, use_kernels=True)
    engine.clear_session_cache()
    run_seeds(run_few_shot, [jax.random.PRNGKey(0)], splits[:1], [_ext()],
              [_SSL], cfg)
    one_seed = {d: st["misses"]
                for d, st in engine.session_cache_stats_by_domain().items()}
    engine.clear_session_cache()
    _run_seeds(run_few_shot, splits, cfg)
    two_seeds = {d: st["misses"]
                 for d, st in engine.session_cache_stats_by_domain().items()}
    assert two_seeds == one_seed, (one_seed, two_seeds)
    for domain in ("kmeans", "sdpa", "fewshot_gate"):
        assert two_seeds.get(domain, 0) >= 1, (domain, two_seeds)


# ------------------------------------------------- satellite regressions
def test_build_schedule_epoch0_streams_decorrelated():
    """Epoch 0's labeled shuffle and unlabeled draws historically seeded
    RandomState(seed0) BOTH (7919·e ≡ 0 at e = 0): the first epoch's two
    streams were generated from one generator state. Pin the fix: the
    unlabeled stream is offset (``_UNLABELED_STREAM``) and no longer
    reproduces the buggy draw."""
    from repro.engine.local_ssl import _UNLABELED_STREAM

    key = jax.random.PRNGKey(3)
    hp = engine.SSLHParams(epochs=1, batch_size=32, unlabeled_ratio=2)
    sched = engine.build_schedule(key, n_labeled=64, n_unlabeled=500, hp=hp)
    seed0 = int(jax.random.randint(key, (), 0, 2**31 - 1))
    fixed = np.random.RandomState(seed0 + _UNLABELED_STREAM)
    buggy = np.random.RandomState(seed0)           # the old e=0 stream ==
    idx_u = np.asarray(sched.idx_unlabeled)        # the labeled-shuffle seed
    assert np.array_equal(idx_u[0], fixed.randint(0, 500, size=64))
    assert not np.array_equal(idx_u[0], buggy.randint(0, 500, size=64))
    # the labeled epoch stream is untouched
    from repro.data.loader import epoch_batches

    expect_l = list(epoch_batches(64, 32, seed0))
    assert np.array_equal(np.asarray(sched.idx_labeled), np.stack(expect_l))


def test_empty_unlabeled_pool_trains_without_nan():
    """n_unlabeled == 0 (a full-overlap party): zero-width unlabeled
    batches, l_u exactly 0, finite loss — no empty-mean NaN, no randint
    crash."""
    hp = engine.SSLHParams(epochs=2, batch_size=16)
    sched = engine.build_schedule(jax.random.PRNGKey(0), n_labeled=32,
                                  n_unlabeled=0, hp=hp)
    assert sched.idx_unlabeled.shape == (4, 0)

    x, y = make_tabular_credit(jax.random.PRNGKey(0), 700)
    split = make_vfl_partition(x[:, :22], y, overlap_size=560,
                               feature_sizes=[11, 11], seed=1)
    assert all(u.shape[0] == 0 for u in split.unaligned)
    res = run_one_shot(jax.random.PRNGKey(1), split, _ext(), _SSL, _FAST)
    assert np.isfinite(float(res.metric))
    for m in res.diagnostics["ssl_metrics"]:
        assert np.isfinite(m["loss"]), m
        assert m["l_u"] == 0.0
        assert m["pseudo_mask_rate"] == 0.0


def test_full_overlap_scenario_registered_and_runs():
    """The registry's full-overlap edge scenario builds with empty pools
    and trains end to end (the smoke() shrink must not reintroduce
    unaligned rows)."""
    from repro import scenarios

    bundle = scenarios.build("edge/full-overlap", seed=0, smoke=True)
    assert all(u.shape[0] == 0 for u in bundle.split.unaligned)
    res = run_one_shot(jax.random.PRNGKey(0), bundle.split,
                       bundle.extractors, bundle.ssl_cfgs, _FAST)
    assert np.isfinite(float(res.metric))
    assert res.ledger.comm_times() == 3
    # few-shot too: the gate sees zero unaligned rows (rate 0) and the
    # masked phase-⑤' sessions run on the all-overlap labeled sets
    few = run_few_shot(jax.random.PRNGKey(0), bundle.split,
                       bundle.extractors, bundle.ssl_cfgs, _FAST)
    assert np.isfinite(float(few.metric))
    assert few.ledger.comm_times() == 5
    assert few.diagnostics["fewshot_gate_rate"] == [0.0, 0.0]


def test_fedcvt_empty_private_pool_trains_without_crash():
    """FedCVT on a full-overlap scenario: ``build_unaligned_schedule``
    historically crashed on an empty pool (``randint(0, 0)``); it must
    yield zero-width unaligned batches instead, whose masked pseudo-label
    term contributes exactly 0 (the full-catalog grouped smoke runs
    fedcvt on edge/full-overlap, so this is now a bench-critical path)."""
    from repro import scenarios
    from repro.engine import iterative

    scheds = iterative.build_unaligned_schedule(
        seed=0, pool_sizes=(0, 500), batch_size=32, iterations=5)
    assert scheds[0].shape == (5, 0)
    assert scheds[1].shape == (5, 32)

    bundle = scenarios.build("edge/full-overlap", seed=0, smoke=True)
    res = run_fedcvt(jax.random.PRNGKey(0), bundle.split, bundle.extractors,
                     bundle.ssl_cfgs, IterativeConfig(iterations=5))
    assert np.isfinite(float(res.metric))


def test_parties_are_homogeneous_is_not_a_shape_heuristic():
    """The spec-level predicate must track the engine's real precondition:
    equal feature dims with DIFFERENT forward functions are heterogeneous
    (the Python fallback is legitimate there), unequal dims are too, and
    unequal SSL configs are too."""
    from repro.models import Model

    ext = _ext()
    shapes = [(64, 11), (64, 11)]
    assert engine.parties_are_homogeneous(ext, _SSL, shapes)

    def odd_apply(params, x, train=False):
        del train
        return jnp.tanh(x @ params["w0"] + params["b0"]) @ params["w1"] \
            + params["b1"]

    odd = Model(init=ext[1].init, apply=odd_apply, rep_dim=8)
    assert not engine.parties_are_homogeneous([ext[0], odd], _SSL, shapes)
    assert not engine.parties_are_homogeneous(ext, _SSL, [(64, 11), (64, 9)])
    mixed = [_SSL[0], dataclasses.replace(_SSL[1], mask_ratio=0.5)]
    assert not engine.parties_are_homogeneous(ext, mixed, shapes)


def test_fewshot_phase5_reuses_cluster_pseudo_labels(splits):
    """Alg. 2's phase ⑤' reuses the step-③ gradient-cluster pseudo-labels
    Ŷ_o^k for the overlap rows — re-predicting with the drifted local head
    is NOT guaranteed to agree and only survives behind the legacy flag."""
    split = splits[0]
    one = run_one_shot(jax.random.PRNGKey(0), split, _ext(), _SSL, _FAST)
    client = one.clients[0]
    pseudo = one.diagnostics["pseudo_labels"][0]
    x_o, x_u = split.aligned[0], split.unaligned[0]
    n_o = x_o.shape[0]

    y_paper = fewshot_phase5_labels(client, x_o, x_u, pseudo,
                                    relabel_overlap=False)
    assert bool(jnp.all(y_paper[:n_o] == pseudo))
    y_legacy = fewshot_phase5_labels(client, x_o, x_u, pseudo,
                                     relabel_overlap=True)
    assert bool(jnp.all(y_legacy[:n_o] == client.predict(x_o)))
    # pool rows are the local model's predictions either way
    assert bool(jnp.all(y_paper[n_o:] == client.predict(x_u)))
    # the drift is real on this task: the two labelings disagree somewhere,
    # which is exactly why "they agree by construction" was wrong
    assert int(jnp.sum(y_paper[:n_o] != y_legacy[:n_o])) > 0
