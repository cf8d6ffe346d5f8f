"""The SSL group fold (DESIGN.md §10): party-heterogeneous tasks — here the
(10, 13)-feature credit split — train as one scanned session per party
signature, matching the per-step host loop (``engine_oracles``, the
parity oracle) entry for entry; a homogeneous set stays one session under
the cache key it always had."""
import dataclasses
import glob
import os

import jax
import jax.numpy as jnp
import pytest
from engine_oracles import ssl_loop_seeds

from repro import engine, obs
from repro.core import ProtocolConfig, SSLConfig, run_few_shot, run_seeds
from repro.core.client import make_client, ssl_task_for
from repro.data import make_tabular_credit, make_vfl_partition
from repro.engine import sessions
from repro.models import make_mlp_extractor

S = 3
HP = engine.SSLHParams(epochs=2, batch_size=16)
_SSL = [SSLConfig(modality="tabular")] * 2


def _split(seed, sizes):
    x, y = make_tabular_credit(jax.random.PRNGKey(100 + seed), 500)
    return make_vfl_partition(x[:, :sum(sizes)], y, overlap_size=48,
                              feature_sizes=list(sizes), seed=seed)


def _ext():
    return [make_mlp_extractor(rep_dim=8, hidden=(16,)) for _ in range(2)]


def _tasks(seed, split, masked=False):
    tasks = []
    for i, (e, x_o, x_u) in enumerate(zip(_ext(), split.aligned,
                                          split.unaligned)):
        c = make_client(jax.random.PRNGKey(10 * seed + i), i, e,
                        split.num_classes, sample_input=x_o[:2],
                        ssl_cfg=_SSL[i], local_data_for_mean=x_u)
        y = jax.random.randint(jax.random.PRNGKey(seed + i), (x_o.shape[0],),
                               0, split.num_classes)
        if masked:   # few-shot ⑤': labeled set at capacity N_o + N_u
            take = (jax.random.uniform(jax.random.PRNGKey(7 + i),
                                       (x_u.shape[0],)) > 0.6
                    ).astype(jnp.float32)
            tasks.append(ssl_task_for(
                c, jnp.concatenate([x_o, x_u]),
                jnp.concatenate([y, c.predict(x_u)]), x_u,
                labeled_mask=jnp.concatenate([jnp.ones(x_o.shape[0]), take]),
                unlabeled_mask=1.0 - take))
        else:
            tasks.append(ssl_task_for(c, x_o, y, x_u))
    return tasks


@pytest.fixture(scope="module")
def hetero():
    return [_split(s, (10, 13)) for s in range(S)]


@pytest.mark.parametrize("masked", [False, True])
def test_grouped_fold_matches_the_python_loop(hetero, masked):
    tasks = [_tasks(s, sp, masked) for s, sp in enumerate(hetero)]
    flat = engine.flatten_seed_tasks(tasks)
    assert not engine.tasks_are_homogeneous(flat)
    assert engine.ssl_task_groups(flat) == [[0, 2, 4], [1, 3, 5]]
    keys = [jax.random.PRNGKey(40 + s) for s in range(S)]
    p_fold, m_fold = engine.train_clients_ssl_seeds(keys, tasks, HP)
    p_loop, m_loop = ssl_loop_seeds(keys, tasks, HP)
    for s in range(S):
        for pf, pl, mf, ml in zip(p_fold[s], p_loop[s], m_fold[s], m_loop[s]):
            for a, b in zip(jax.tree_util.tree_leaves(pf),
                            jax.tree_util.tree_leaves(pl)):
                assert jnp.allclose(a, b, atol=1e-5), float(
                    jnp.max(jnp.abs(a - b)))
            assert mf.keys() == ml.keys()
            for name in mf:
                assert abs(mf[name] - ml[name]) < 1e-5, (name, mf, ml)


def test_single_seed_heterogeneous_runs_width_one_sessions(hetero):
    tasks = _tasks(0, hetero[0])
    key = jax.random.PRNGKey(3)
    p_fold, _, vmapped = engine.train_clients_ssl(key, tasks, HP)
    (p_loop,), _ = ssl_loop_seeds([key], [tasks], HP)
    assert vmapped
    for a, b in zip(jax.tree_util.tree_leaves(p_fold),
                    jax.tree_util.tree_leaves(p_loop)):
        assert jnp.allclose(a, b, atol=1e-5)


def test_ledgers_identical_across_paths(hetero, monkeypatch):
    cfg = ProtocolConfig(client_epochs=1, server_epochs=2, batch_size=16)
    keys = [jax.random.PRNGKey(60 + s) for s in range(S)]

    def run():
        return run_seeds(run_few_shot, keys, hetero,
                         [_ext() for _ in range(S)], [_SSL] * S, cfg)

    runs = {"auto": run()}
    monkeypatch.setattr(engine, "train_clients_ssl_seeds", ssl_loop_seeds)
    runs["python"] = run()
    for fold, loop in zip(runs["auto"], runs["python"]):
        assert fold.ledger.events == loop.ledger.events
        assert fold.ledger.comm_times() == loop.ledger.comm_times() == 5


def test_homogeneous_set_is_one_group_under_its_old_cache_key():
    tasks = [_tasks(s, _split(s, (11, 11))) for s in range(S)]
    flat = engine.flatten_seed_tasks(tasks)
    assert engine.tasks_are_homogeneous(flat)
    assert engine.ssl_task_groups(flat) == [list(range(2 * S))]
    keys = [jax.random.PRNGKey(80 + s) for s in range(S)]
    flat_keys = [kk for key in keys for kk in jax.random.split(key, 2)]
    # warm the cache through the one-session path itself
    engine.train_parties_ssl_vmapped(flat_keys, flat, HP)
    before = sessions.session_cache_stats("ssl")
    engine.train_clients_ssl_seeds(keys, tasks, HP)
    engine.train_clients_ssl(keys[0], tasks[0], HP)
    after = sessions.session_cache_stats("ssl")
    assert after["misses"] == before["misses"]
    assert after["hits"] == before["hits"] + 2


def test_host_step_counter(hetero):
    """The fold dispatches no SSL step from the host, at S = 1 and S > 1."""
    tasks = [_tasks(s, sp) for s, sp in enumerate(hetero)]
    keys = [jax.random.PRNGKey(s) for s in range(S)]
    c0 = obs.counters()["ssl_host_steps"]
    engine.train_clients_ssl_seeds(keys[:1], tasks[:1], HP)
    assert obs.counters()["ssl_host_steps"] == c0
    engine.train_clients_ssl_seeds(keys, tasks, HP)
    assert obs.counters()["ssl_host_steps"] == c0


@pytest.mark.parametrize("empty_pool", [False, True])
def test_schedule_draw_counter(hetero, empty_pool):
    """A grouped run's schedule builds make 2 generator calls per entry and
    epoch (the labeled shuffle and the unlabeled draw), 1 with no pool;
    the per-party host loop builds the same schedules and counts the
    same."""
    tasks = [_tasks(s, sp) for s, sp in enumerate(hetero)]
    if empty_pool:
        tasks = [[dataclasses.replace(t, x_unlabeled=t.x_unlabeled[:0])
                  for t in ts] for ts in tasks]
    keys = [jax.random.PRNGKey(s) for s in range(S)]
    per_entry = HP.epochs * (1 if empty_pool else 2)
    for name, train in (("fold", engine.train_clients_ssl_seeds),
                        ("loop", ssl_loop_seeds)):
        before = obs.counters()["ssl_schedule_draws"]
        train(keys, tasks, HP)
        assert (obs.counters()["ssl_schedule_draws"] - before
                == 2 * S * per_entry), name


def test_group_span_carries_its_attributes(hetero, tmp_path):
    from jax.profiler import ProfileData

    tasks = [_tasks(s, sp) for s, sp in enumerate(hetero)]
    keys = [jax.random.PRNGKey(s) for s in range(S)]
    engine.train_clients_ssl_seeds(keys, tasks, HP)       # compile untraced
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        with obs.span("run"):
            engine.train_clients_ssl_seeds(keys, tasks, HP)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                        recursive=True)
    pd = ProfileData.from_file(path)
    groups, schedules, runs = [], [], []
    for plane in pd.planes:
        for line in plane.lines:
            for e in line.events:
                if e.name == "vfl.ssl.group":
                    groups.append(dict(e.stats))
                elif e.name == "vfl.ssl.schedule":
                    schedules.append(dict(e.stats))
                elif e.name == "vfl.run":
                    runs.append(dict(e.stats))
    assert [(g["members"], g["rows"], g["steps"]) for g in groups] == [
        (S, 48, HP.epochs * 3)] * 2
    assert [(s["entries"], s["steps"], s["ssl_schedule_draws"])
            for s in schedules] == [(S, HP.epochs * 3, S * HP.epochs * 2)] * 2
    assert len(runs) == 1 and runs[0]["ssl_host_steps"] == 0
    assert runs[0]["ssl_schedule_draws"] == 2 * S * HP.epochs * 2
    assert all(g["run"] == runs[0]["run"] for g in groups)
