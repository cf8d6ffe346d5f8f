"""Engine-layer tests: the vmapped jitted multi-client path must be
numerically equivalent to the per-client host loop (``engine_oracles``),
record identical communication, and be the one implementation both the
protocol and the multi-pod schedule train with."""
import jax
import jax.numpy as jnp
import pytest
from engine_oracles import ssl_loop_seeds

from repro import engine, obs
from repro.core import ProtocolConfig, SSLConfig, run_one_shot
from repro.core.client import make_client, ssl_task_for
from repro.data import make_tabular_credit, make_vfl_partition
from repro.models import make_mlp_extractor


@pytest.fixture(scope="module")
def homo_split():
    """Synthetic vertical data with EQUAL per-party feature dims → the
    engine's homogeneous fast path applies."""
    x, y = make_tabular_credit(jax.random.PRNGKey(0), 700)
    return make_vfl_partition(x[:, :22], y, overlap_size=64,
                              feature_sizes=[11, 11], seed=1)


def _clients(key, split, dims):
    ext = [make_mlp_extractor(rep_dim=8, hidden=(16,)) for _ in dims]
    return [make_client(jax.random.fold_in(key, i), i, e, split.num_classes,
                        sample_input=split.aligned[i][:2],
                        ssl_cfg=SSLConfig(modality="tabular"),
                        local_data_for_mean=split.unaligned[i])
            for i, e in enumerate(ext)]


def _tasks(key, split, clients):
    tasks = []
    for c, g_dim, x_o, x_u in zip(clients, range(len(clients)),
                                  split.aligned, split.unaligned):
        y_pseudo = jax.random.randint(jax.random.fold_in(key, g_dim),
                                      (x_o.shape[0],), 0, split.num_classes)
        tasks.append(ssl_task_for(c, x_o, y_pseudo, x_u))
    return tasks


HP = engine.SSLHParams(epochs=2, batch_size=32)


def test_vmap_equivalent_to_python_loop(homo_split):
    """The tentpole invariant: vmap-over-clients scan == per-client host
    loop, at atol 1e-5 on every parameter leaf."""
    key = jax.random.PRNGKey(7)
    clients = _clients(jax.random.PRNGKey(1), homo_split, [0, 1])
    tasks = _tasks(jax.random.PRNGKey(2), homo_split, clients)

    p_vmap, m_vmap, vmapped = engine.train_clients_ssl(key, tasks, HP)
    (p_py,), (m_py,) = ssl_loop_seeds([key], [tasks], HP)
    assert vmapped
    for pv, pp in zip(p_vmap, p_py):
        for lv, lp in zip(jax.tree_util.tree_leaves(pv),
                          jax.tree_util.tree_leaves(pp)):
            assert jnp.allclose(lv, lp, atol=1e-5), float(jnp.max(jnp.abs(lv - lp)))
    for mv, mp in zip(m_vmap, m_py):
        assert mv.keys() == mp.keys()
        for name in mv:
            assert abs(mv[name] - mp[name]) < 1e-4, (name, mv[name], mp[name])


def test_auto_dispatch(homo_split):
    """A homogeneous zoo is one group (one scanned session), a
    heterogeneous one is one group per party signature; ``mode`` takes
    only "auto" and "vmap"."""
    clients = _clients(jax.random.PRNGKey(1), homo_split, [0, 1])
    tasks = _tasks(jax.random.PRNGKey(2), homo_split, clients)
    assert engine.tasks_are_homogeneous(tasks)
    assert engine.ssl_task_groups(tasks) == [[0, 1]]
    _, _, vmapped = engine.train_clients_ssl(jax.random.PRNGKey(3), tasks, HP)
    assert vmapped

    x, y = make_tabular_credit(jax.random.PRNGKey(0), 700)
    hetero = make_vfl_partition(x, y, overlap_size=64, feature_sizes=[10, 13],
                                seed=1)
    h_clients = _clients(jax.random.PRNGKey(1), hetero, [0, 1])
    h_tasks = _tasks(jax.random.PRNGKey(2), hetero, h_clients)
    assert not engine.tasks_are_homogeneous(h_tasks)
    assert engine.ssl_task_groups(h_tasks) == [[0], [1]]
    _, _, vmapped = engine.train_clients_ssl(jax.random.PRNGKey(3), h_tasks,
                                             HP, mode="vmap")
    assert vmapped
    with pytest.raises(ValueError):
        engine.train_clients_ssl(jax.random.PRNGKey(3), h_tasks, HP,
                                 mode="python")


def test_vmap_mode_honored_for_single_party(homo_split):
    """K = 1 runs one width-1 scanned session, dispatches no SSL step from
    the host, and matches the host loop at atol 1e-5."""
    from repro.engine import sessions

    clients = _clients(jax.random.PRNGKey(1), homo_split, [0, 1])[:1]
    tasks = _tasks(jax.random.PRNGKey(2), homo_split, clients)[:1]
    key = jax.random.PRNGKey(3)
    assert engine.ssl_task_groups(tasks) == [[0]]
    steps0 = obs.counters()["ssl_host_steps"]
    before = sessions.session_cache_stats("ssl")
    params, _, vmapped = engine.train_clients_ssl(key, tasks, HP)
    after = sessions.session_cache_stats("ssl")
    assert vmapped
    assert obs.counters()["ssl_host_steps"] == steps0
    assert (after["hits"] + after["misses"]
            == before["hits"] + before["misses"] + 1)
    (want,), _ = ssl_loop_seeds([key], [tasks], HP)
    for a, b in zip(jax.tree_util.tree_leaves(params),
                    jax.tree_util.tree_leaves(want)):
        assert jnp.allclose(a, b, atol=1e-5), float(jnp.max(jnp.abs(a - b)))


def test_homogeneity_checks_forward_fn(homo_split):
    """Same param shapes but a different apply function must NOT be stacked
    under party 0's extractor — shape equality alone is not homogeneity."""
    from dataclasses import replace as dc_replace

    from repro.models import Model, make_mlp_extractor

    clients = _clients(jax.random.PRNGKey(1), homo_split, [0, 1])
    tasks = _tasks(jax.random.PRNGKey(2), homo_split, clients)
    assert engine.tasks_are_homogeneous(tasks)

    base = make_mlp_extractor(rep_dim=8, hidden=(16,))

    def tanh_apply(params, x, train=False):
        del train
        h = jnp.tanh(x @ params["w0"] + params["b0"])
        return h @ params["w1"] + params["b1"]

    odd = Model(init=base.init, apply=tanh_apply, rep_dim=8)
    tasks_odd = [tasks[0], dc_replace(tasks[1], extractor=odd)]
    assert not engine.tasks_are_homogeneous(tasks_odd)


def test_few_shot_with_vmap_mode(homo_split):
    """A default-config few-shot run trains every SSL session scanned,
    phase ⑤''s masked fixed-shape sessions at any ragged per-party gate
    counts included (DESIGN.md §9): no SSL step is dispatched from the
    host."""
    from repro.core import run_few_shot

    ext = [make_mlp_extractor(rep_dim=8, hidden=(16,)) for _ in range(2)]
    ssl = [SSLConfig(modality="tabular")] * 2
    cfg = ProtocolConfig(client_epochs=2, server_epochs=3)
    steps0 = obs.counters()["ssl_host_steps"]
    res = run_few_shot(jax.random.PRNGKey(1), homo_split, ext, ssl, cfg)
    assert obs.counters()["ssl_host_steps"] == steps0
    assert res.ledger.comm_times() == 5
    assert res.metric > 0.5


def test_protocol_ledger_identical_across_paths(homo_split, monkeypatch):
    """run_one_shot on the engine and on the host loop: identical CommLedger
    bytes, the paper's 3 comm times, and matching metrics."""
    ext = [make_mlp_extractor(rep_dim=8, hidden=(16,)) for _ in range(2)]
    ssl = [SSLConfig(modality="tabular")] * 2
    cfg = ProtocolConfig(client_epochs=2, server_epochs=3)
    results = {"vmap": run_one_shot(jax.random.PRNGKey(1), homo_split, ext,
                                    ssl, cfg)}
    monkeypatch.setattr(engine, "train_clients_ssl_seeds", ssl_loop_seeds)
    results["python"] = run_one_shot(jax.random.PRNGKey(1), homo_split, ext,
                                     ssl, cfg)
    v, p = results["vmap"].ledger, results["python"].ledger
    assert v.total_bytes() == p.total_bytes()
    assert v.comm_times() == p.comm_times() == 3
    assert v.by_tag() == p.by_tag()
    assert abs(results["vmap"].metric - results["python"].metric) < 1e-3


def test_vfl_step_shares_engine_implementation():
    """The multi-pod schedule must train with the engine's step function and
    the real repro.models extractor — no private re-implementation."""
    import inspect

    from repro.launch import vfl_step

    assert vfl_step.make_ssl_step_fn is engine.make_ssl_step_fn
    assert vfl_step.make_ssl_optimizer is engine.make_ssl_optimizer
    assert not hasattr(vfl_step, "_extract")
    src = inspect.getsource(vfl_step)
    assert "make_mlp_extractor" in src
    assert "gradient_pseudo_labels" in src


def test_schedule_shapes():
    sched = engine.build_schedule(jax.random.PRNGKey(0), n_labeled=64,
                                  n_unlabeled=100,
                                  hp=engine.SSLHParams(epochs=3, batch_size=32,
                                                       unlabeled_ratio=2))
    steps = 3 * (64 // 32)
    assert sched.idx_labeled.shape == (steps, 32)
    assert sched.idx_unlabeled.shape == (steps, 64)
    assert sched.step_keys.shape[0] == steps
    assert int(jnp.max(sched.idx_labeled)) < 64
    assert int(jnp.max(sched.idx_unlabeled)) < 100
