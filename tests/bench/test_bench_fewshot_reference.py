"""bench/reference's few-shot pieces against the program on the same inputs,
at small sizes on the CPU (where both compute in float32): the tabular SSL
session step for step, plain and masked as phase 5' runs it, Eq. 10 and the
Eq. 8-9 gate."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))

from bench.reference import fewshot as ref_fs  # noqa: E402
from bench.reference import ssl_tabular as ref_tab  # noqa: E402
from repro.core import estimator  # noqa: E402
from repro.models.extractors import (make_classifier,  # noqa: E402
                                     make_mlp_extractor)

TOL = 1e-5


def _tab_tasks(threshold, masked):
    """Two parties' tiny tabular SSL tasks (10 and 13 features) in the
    program's own types; ``masked`` pads the labeled set with the private
    pool under a gate, as phase 5' does."""
    from repro.core.ssl import SSLConfig
    from repro.engine import PartyParams, PartyTask

    head = make_classifier(2)
    cfg = SSLConfig(modality="tabular", confidence_threshold=threshold)
    tasks = []
    for p, d in enumerate((10, 13)):
        ext = make_mlp_extractor(rep_dim=8, hidden=(16,))
        k = jax.random.split(jax.random.PRNGKey(20 + p), 6)
        x_l = jax.random.normal(k[0], (40, d))
        x_u = jax.random.normal(k[1], (56, d))
        y = jax.random.randint(k[4], (40,), 0, 2)
        params = PartyParams(ext.init(k[2], x_l),
                             head.init(k[3], jnp.zeros((1, 8))))
        extra = {}
        if masked:
            take = (jax.random.uniform(k[5], (56,)) > 0.5).astype(jnp.float32)
            x_l = jnp.concatenate([x_l, x_u])
            y = jnp.concatenate([y, jnp.zeros(56, jnp.int32)])
            extra = dict(labeled_mask=jnp.concatenate([jnp.ones(40), take]),
                         unlabeled_mask=1.0 - take)
        tasks.append(PartyTask(extractor=ext, head=head, params=params,
                               ssl_cfg=cfg, x_labeled=x_l, y_pseudo=y,
                               x_unlabeled=x_u,
                               feature_mean=jnp.mean(x_u, axis=0), **extra))
    return tasks


@pytest.mark.parametrize("threshold, masked", [(0.3, False), (0.95, False),
                                               (0.3, True)])
def test_tabular_session_matches_program(threshold, masked):
    """The reference session follows the program's grouped session (one
    width-1 group per party signature) step for step from the same start
    and key: the same batches, masks, augmentations, loss, clipping and
    momentum."""
    from repro import engine

    tasks = _tab_tasks(threshold, masked)
    hp = engine.SSLHParams(epochs=2, batch_size=16, learning_rate=0.05)
    key = jax.random.PRNGKey(3)
    got, metrics, vmapped = engine.train_clients_ssl(key, tasks, hp)
    assert vmapped
    ref_hp = ref_tab.Hyper(
        epochs=2, batch_size=16, learning_rate=0.05, momentum=hp.momentum,
        grad_clip=hp.grad_clip, unlabeled_ratio=hp.unlabeled_ratio,
        lambda_u=1.0, confidence_threshold=threshold, mask_ratio=0.2,
        sigma=0.1)
    for p, (task, kk) in enumerate(zip(tasks, jax.random.split(key, 2))):
        want = ref_tab.session(
            {"extractor": task.params.extractor, "head": task.params.head},
            task.x_labeled, task.y_pseudo, task.x_unlabeled, kk, ref_hp,
            task.labeled_mask, task.unlabeled_mask)
        assert want.losses.shape == (2 * (task.x_labeled.shape[0] // 16),)
        np.testing.assert_allclose(float(want.losses[-1]),
                                   metrics[p]["loss"], rtol=1e-4)
        for a, b in zip(jax.tree_util.tree_leaves(
                {"extractor": got[p].extractor, "head": got[p].head}),
                jax.tree_util.tree_leaves(want.params)):
            np.testing.assert_allclose(a, b, rtol=1e-4, atol=TOL)


def _reps():
    k = jax.random.split(jax.random.PRNGKey(0), 4)
    h_u = jax.random.normal(k[0], (50, 8))
    h_o = [jax.random.normal(k[1], (24, 8)), jax.random.normal(k[2], (24, 8))]
    return h_u, h_o


@pytest.mark.parametrize("k", [0, 1])
def test_eq10_estimates_match_program(k):
    h_u, h_o = _reps()
    got = estimator.estimate_missing_parties(h_u, h_o, k)
    want = ref_fs.estimates(h_u, h_o, k)
    assert len(got) == len(want) == 1
    np.testing.assert_allclose(want[0], got[0], rtol=TOL, atol=TOL)


@pytest.mark.parametrize("k, threshold", [(0, 0.6), (1, 0.6), (0, 0.9)])
def test_gate_matches_program(k, threshold):
    h_u, h_o = _reps()
    aux = make_classifier(3)
    joint = make_classifier(3)
    aux_p = jax.tree_util.tree_map(lambda a: 4 * a, aux.init(
        jax.random.PRNGKey(1), h_u))
    joint_p = jax.tree_util.tree_map(lambda a: 4 * a, joint.init(
        jax.random.PRNGKey(2), jnp.zeros((1, 16))))
    missing = ref_fs.estimates(h_u, h_o, k)
    full = jnp.concatenate([h_u, missing[0]] if k == 0
                           else [missing[0], h_u], axis=-1)
    got = estimator.infer_prob(lambda h: aux.apply(aux_p, h),
                               lambda h: joint.apply(joint_p, h),
                               h_u, full, threshold)
    want = ref_fs.gate(aux_p, joint_p, h_u, missing, k, threshold)
    assert 0 < int(jnp.sum(want > 0)) < want.shape[0]
    np.testing.assert_array_equal(got > 0, want > 0)
    np.testing.assert_allclose(want, got, rtol=TOL, atol=TOL)
