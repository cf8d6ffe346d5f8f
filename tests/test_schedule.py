"""The batched schedule build (``engine.build_schedules``): one host pass per
session group gives, bit for bit, the schedule the per-entry, per-step build
gave each entry (frozen below as the oracle), so every session trains on the
rows and keys it always did; ``ssl_schedule_draws`` counts the generator
calls a build makes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import engine, obs
from repro.data.loader import epoch_batches
from repro.engine.local_ssl import _UNLABELED_STREAM


def _per_step_schedule(key, n_labeled, n_unlabeled, hp):
    """The per-entry build as it stood before the batched pass: two fresh
    generators per epoch and one unlabeled draw per step."""
    bs_l = min(hp.batch_size, n_labeled)
    bs_u = min(hp.batch_size * hp.unlabeled_ratio, n_unlabeled)
    seed0 = int(jax.random.randint(key, (), 0, 2**31 - 1))
    idx_l, idx_u = [], []
    for e in range(hp.epochs):
        u_rng = np.random.RandomState(seed0 + 7919 * e + _UNLABELED_STREAM)
        for batch in epoch_batches(n_labeled, bs_l, seed0 + e):
            idx_l.append(batch)
            idx_u.append(u_rng.randint(0, n_unlabeled, size=bs_u)
                         if n_unlabeled > 0 else np.zeros(0, np.int64))
    if not idx_l:
        return (np.zeros((0, bs_l), np.int32), np.zeros((0, bs_u), np.int32),
                np.zeros((0, 2), np.uint32))
    return (np.stack(idx_l).astype(np.int32), np.stack(idx_u).astype(np.int32),
            np.asarray(jax.random.split(jax.random.fold_in(key, 1),
                                        len(idx_l))))


def _keys(g, seed=0):
    return list(jax.random.split(jax.random.PRNGKey(seed), g))


# (n_labeled, n_unlabeled, G, hp, keys' seed); the mesh pad repeats entry 0
CASES = {
    "fewshot-capacity": (12_500, 11_500, 8, engine.SSLHParams(epochs=2), 1),
    "oneshot-credit": (1_000, 11_500, 2, engine.SSLHParams(epochs=3), 2),
    "small": (64, 500, 3, engine.SSLHParams(epochs=4, batch_size=16), 3),
    "labeled-below-batch": (20, 100, 2, engine.SSLHParams(epochs=3), 4),
    "empty-pool": (48, 0, 2, engine.SSLHParams(epochs=2, batch_size=16), 5),
    "no-epochs": (64, 500, 2, engine.SSLHParams(epochs=0), 6),
    "mesh-pad-duplicates": (64, 500, 4, engine.SSLHParams(epochs=2), 7),
}


@pytest.mark.parametrize("case", list(CASES))
def test_batched_build_matches_the_per_step_build(case):
    n_l, n_u, g, hp, seed = CASES[case]
    keys = _keys(g, seed)
    if case == "mesh-pad-duplicates":
        keys = keys[:2] + [keys[0]] * 2
    sched = engine.build_schedules(keys, n_l, n_u, hp)
    steps = engine.schedule_steps(n_l, hp)
    assert sched.idx_labeled.dtype == sched.idx_unlabeled.dtype == jnp.int32
    assert sched.step_keys.shape == (g, steps, 2)
    for i, key in enumerate(keys):
        want = _per_step_schedule(key, n_l, n_u, hp)
        for got, exp in zip(sched, want):
            got = np.asarray(got[i])
            assert got.shape == exp.shape, (got.shape, exp.shape)
            assert np.array_equal(got, exp)
    if case == "mesh-pad-duplicates":
        for a in sched:
            assert np.array_equal(np.asarray(a[2]), np.asarray(a[0]))


def test_one_key_build_is_row_zero_of_the_group_build():
    key = jax.random.PRNGKey(11)
    hp = engine.SSLHParams(epochs=3, batch_size=16)
    one = engine.build_schedule(key, 80, 300, hp)
    group = engine.build_schedules([key], 80, 300, hp)
    for a, b in zip(one, group):
        assert a.shape == b.shape[1:]
        assert np.array_equal(np.asarray(a), np.asarray(b[0]))


@pytest.mark.parametrize("n_unlabeled,per_epoch", [(500, 2), (0, 1)])
def test_draw_counter_counts_entries_epochs_and_streams(n_unlabeled,
                                                        per_epoch):
    hp = engine.SSLHParams(epochs=3, batch_size=16)
    before = obs.counters()["ssl_schedule_draws"]
    engine.build_schedules(_keys(4), 64, n_unlabeled, hp)
    assert obs.counters()["ssl_schedule_draws"] - before == 4 * 3 * per_epoch
    before = obs.counters()["ssl_schedule_draws"]
    engine.build_schedules(_keys(4), 64, n_unlabeled,
                           engine.SSLHParams(epochs=0))
    assert obs.counters()["ssl_schedule_draws"] == before
