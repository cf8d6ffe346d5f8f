"""Masked fixed-shape SSL sessions + the shared session cache (DESIGN.md §9).

* scanned session ≡ per-party host loop (``engine_oracles``) for *masked*
  tasks at deliberately ragged per-party valid-row counts (the few-shot ⑤'
  shape problem, isolated);
* ``run_few_shot`` on the engine and on the host loop record byte-identical
  ledgers;
* Eq. 9 gating is deterministic (every sample with p̂ > 0 is kept); the
  legacy Bernoulli subsampling sits behind ``fewshot_stochastic_gate``;
* all-gated pools are represented as zero-valid unlabeled masks (no row in
  both the labeled and unlabeled sets, l_u exactly 0);
* the second seed of a sweep re-serves cached SSL and server-fit sessions
  (recompile-count regression);
* ``ProtocolConfig`` / ``IterativeConfig`` are frozen — no shared mutable
  default config across runner calls.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from engine_oracles import ssl_loop_seeds

from repro import engine
from repro.core import (IterativeConfig, ProtocolConfig, SSLConfig,
                        run_few_shot)
from repro.core.client import make_client, ssl_task_for
from repro.core.ssl import ssl_loss
from repro.data import make_tabular_credit, make_vfl_partition
from repro.models import make_mlp_extractor

HP = engine.SSLHParams(epochs=2, batch_size=32)


@pytest.fixture(scope="module")
def homo_split():
    x, y = make_tabular_credit(jax.random.PRNGKey(0), 700)
    return make_vfl_partition(x[:, :22], y, overlap_size=64,
                              feature_sizes=[11, 11], seed=1)


def _clients(key, split):
    ext = [make_mlp_extractor(rep_dim=8, hidden=(16,)) for _ in range(2)]
    return [make_client(jax.random.fold_in(key, i), i, e, split.num_classes,
                        sample_input=split.aligned[i][:2],
                        ssl_cfg=SSLConfig(modality="tabular"),
                        local_data_for_mean=split.unaligned[i])
            for i, e in enumerate(ext)]


def _masked_tasks(key, split, clients, valid_counts):
    """Few-shot-⑤'-shaped tasks: labeled = x_o ∘ x_u at full capacity, with
    deliberately ragged per-party gate counts via the validity masks."""
    tasks = []
    for c, n_take, x_o, x_u in zip(clients, valid_counts, split.aligned,
                                   split.unaligned):
        x_lab = jnp.concatenate([x_o, x_u], axis=0)
        y_lab = jax.random.randint(jax.random.fold_in(key, c.index),
                                   (x_lab.shape[0],), 0, split.num_classes)
        take = jnp.zeros(x_u.shape[0], jnp.float32).at[:n_take].set(1.0)
        lab_mask = jnp.concatenate([jnp.ones(x_o.shape[0], jnp.float32), take])
        tasks.append(ssl_task_for(c, x_lab, y_lab, x_u,
                                  labeled_mask=lab_mask,
                                  unlabeled_mask=1.0 - take))
    return tasks


def test_masked_tasks_are_homogeneous_at_ragged_counts(homo_split):
    clients = _clients(jax.random.PRNGKey(1), homo_split)
    tasks = _masked_tasks(jax.random.PRNGKey(2), homo_split, clients, [7, 201])
    assert engine.tasks_are_homogeneous(tasks)
    # mask presence must still be consistent across parties
    bare = dataclasses.replace(tasks[1], labeled_mask=None,
                               unlabeled_mask=None)
    assert not engine.tasks_are_homogeneous([tasks[0], bare])


def test_masked_vmap_equivalent_to_python_loop(homo_split):
    """The tentpole invariant at ragged gate counts: masked scanned session
    == per-client host loop at atol 1e-5 on every parameter leaf."""
    clients = _clients(jax.random.PRNGKey(1), homo_split)
    tasks = _masked_tasks(jax.random.PRNGKey(2), homo_split, clients, [3, 170])
    key = jax.random.PRNGKey(7)
    p_vmap, m_vmap, vmapped = engine.train_clients_ssl(key, tasks, HP)
    (p_py,), (m_py,) = ssl_loop_seeds([key], [tasks], HP)
    assert vmapped
    for pv, pp in zip(p_vmap, p_py):
        for lv, lp in zip(jax.tree_util.tree_leaves(pv),
                          jax.tree_util.tree_leaves(pp)):
            assert jnp.allclose(lv, lp, atol=1e-5), \
                float(jnp.max(jnp.abs(lv - lp)))
    for mv, mp in zip(m_vmap, m_py):
        assert mv.keys() == mp.keys()
        for name in mv:
            assert abs(mv[name] - mp[name]) < 1e-4, (name, mv[name], mp[name])


def test_masked_rows_contribute_zero_loss(homo_split):
    """An all-ones mask reproduces the unmasked loss; padded rows with junk
    data change nothing; a zero-valid unlabeled batch has l_u == 0 exactly
    (the empty-pool representation — no row in both sets, no [:1] leak)."""
    clients = _clients(jax.random.PRNGKey(1), homo_split)
    c = clients[0]
    x_o, x_u = homo_split.aligned[0], homo_split.unaligned[0]
    xb_l, xb_u = x_o[:16], x_u[:32]
    yb = jnp.zeros(16, jnp.int32)
    key = jax.random.PRNGKey(3)

    def logits_fn(p, x):
        return c.head.apply(p.head, c.extractor.apply(p.extractor, x))

    cfg = c.ssl_cfg
    base, _ = ssl_loss(logits_fn, c.params, key, xb_l, yb, xb_u, cfg,
                       c.feature_mean)
    ones, _ = ssl_loss(logits_fn, c.params, key, xb_l, yb, xb_u, cfg,
                       c.feature_mean,
                       labeled_mask=jnp.ones(16), unlabeled_mask=jnp.ones(32))
    assert jnp.allclose(base, ones, atol=1e-6)

    # corrupt the masked-out half of the labeled batch: loss is unchanged
    half = jnp.concatenate([jnp.ones(8), jnp.zeros(8)])
    ref, _ = ssl_loss(logits_fn, c.params, key, xb_l, yb, xb_u, cfg,
                      c.feature_mean, labeled_mask=half)
    junk = xb_l.at[8:].set(1e3)
    got, _ = ssl_loss(logits_fn, c.params, key, junk, yb, xb_u, cfg,
                      c.feature_mean, labeled_mask=half)
    assert jnp.allclose(ref, got, atol=1e-6)

    # zero-valid unlabeled batch == empty pool: l_u exactly 0
    _, metrics = ssl_loss(logits_fn, c.params, key, xb_l, yb, xb_u, cfg,
                          c.feature_mean, unlabeled_mask=jnp.zeros(32))
    assert float(metrics["l_u"]) == 0.0
    assert float(metrics["pseudo_mask_rate"]) == 0.0


def _fast(**kw):
    return ProtocolConfig(client_epochs=2, server_epochs=3, **kw)


def test_few_shot_stays_on_vmap_path_with_ragged_gates(homo_split,
                                                       monkeypatch):
    """At ragged per-party gate counts, the few-shot run on the engine
    records a ledger byte-identical to the host loop's."""
    ext = [make_mlp_extractor(rep_dim=8, hidden=(16,)) for _ in range(2)]
    ssl = [SSLConfig(modality="tabular")] * 2
    results = {}
    for mode in ("vmap", "python"):
        if mode == "python":
            monkeypatch.setattr(engine, "train_clients_ssl_seeds",
                                ssl_loop_seeds)
        res = run_few_shot(jax.random.PRNGKey(1), homo_split, ext, ssl,
                           _fast())
        assert res.ledger.comm_times() == 5
        results[mode] = res
    # ragged gates actually exercised (else the test proves nothing)
    takes = results["vmap"].diagnostics["fewshot_take_rate"]
    assert takes[0] != takes[1]
    v, p = results["vmap"].ledger, results["python"].ledger
    assert v.total_bytes() == p.total_bytes()
    assert v.by_tag() == p.by_tag()
    assert abs(results["vmap"].metric - results["python"].metric) < 1e-3


def test_eq9_gate_is_deterministic_by_default(homo_split):
    """The paper keeps ALL samples passing the Eq. 9 gate: the take rate
    must equal the gate rate (p̂ > 0), and two runs with different PRNG
    keys but identical upstream state agree. The Bernoulli subsampling
    only engages behind fewshot_stochastic_gate."""
    ext = [make_mlp_extractor(rep_dim=8, hidden=(16,)) for _ in range(2)]
    ssl = [SSLConfig(modality="tabular")] * 2
    res = run_few_shot(jax.random.PRNGKey(1), homo_split, ext, ssl, _fast())
    assert res.diagnostics["fewshot_take_rate"] == \
        res.diagnostics["fewshot_gate_rate"]
    res_s = run_few_shot(jax.random.PRNGKey(1), homo_split, ext, ssl,
                         _fast(fewshot_stochastic_gate=True))
    # Bernoulli(p̂ ≤ 1) keeps at most the gated samples, a.s. fewer
    for t_s, t_d in zip(res_s.diagnostics["fewshot_take_rate"],
                        res.diagnostics["fewshot_take_rate"]):
        assert t_s <= t_d


def test_sweep_reuses_cached_ssl_and_server_fit_sessions(homo_split):
    """Recompile-count regression: the second seed of a sweep must add ZERO
    fresh compiles — both the SSL sessions and every server classifier fit
    re-serve the cached compiled programs."""
    ext = [make_mlp_extractor(rep_dim=8, hidden=(16,)) for _ in range(2)]
    ssl = [SSLConfig(modality="tabular")] * 2
    engine.clear_session_cache()
    run_few_shot(jax.random.PRNGKey(0), homo_split, ext, ssl, _fast())
    first = engine.session_cache_stats_by_domain()
    assert first["server_fit"]["misses"] == 1     # K aux + joint + refits: 1 arch
    assert first["server_fit"]["hits"] >= 3
    assert first["ssl"]["misses"] >= 1
    # fresh-but-equivalent extractors (same factory args) on another seed
    ext2 = [make_mlp_extractor(rep_dim=8, hidden=(16,)) for _ in range(2)]
    run_few_shot(jax.random.PRNGKey(1), homo_split, ext2, ssl, _fast())
    second = engine.session_cache_stats_by_domain()
    assert second["server_fit"]["misses"] == first["server_fit"]["misses"]
    assert second["ssl"]["misses"] == first["ssl"]["misses"]
    assert second["ssl"]["hits"] > first["ssl"]["hits"]


def test_configs_are_frozen_and_not_shared():
    with pytest.raises(dataclasses.FrozenInstanceError):
        ProtocolConfig().fewshot_threshold = 0.5
    with pytest.raises(dataclasses.FrozenInstanceError):
        IterativeConfig().iterations = 7
    # replace() is the supported mutation idiom
    assert dataclasses.replace(ProtocolConfig(),
                               fewshot_threshold=0.5).fewshot_threshold == 0.5


def test_all_gated_pool_trains_without_leak(homo_split):
    """When every unaligned sample passes the gate the unlabeled mask is
    all-zero: the session still runs (l_u == 0) instead of recycling
    x_u[:1] into both sets."""
    clients = _clients(jax.random.PRNGKey(1), homo_split)
    n_u = homo_split.unaligned[0].shape[0]
    tasks = _masked_tasks(jax.random.PRNGKey(2), homo_split, clients,
                          [n_u, n_u])
    for t in tasks:
        assert float(jnp.sum(t.unlabeled_mask)) == 0.0
    params, metrics, vmapped = engine.train_clients_ssl(
        jax.random.PRNGKey(3), tasks, HP)
    assert vmapped
    for m in metrics:
        assert m["l_u"] == 0.0
        assert np.isfinite(m["loss"])
