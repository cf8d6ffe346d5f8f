"""bench/reference against the program's jnp route on the same parameters,
at small sizes on the CPU (where both compute in float32)."""
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

from bench.reference import vfl as ref  # noqa: E402
from repro.core import clustering, estimator  # noqa: E402
from repro.models.extractors import (make_classifier,  # noqa: E402
                                     make_cnn_extractor, make_mlp_extractor)

TOL = 1e-5


@pytest.mark.parametrize("widths, blocks, hw", [((8, 16), 2, (8, 4)),
                                                ((4, 8, 16), 1, (16, 8))])
def test_cnn_forward_matches_program(widths, blocks, hw):
    params = ref.init_cnn(jax.random.PRNGKey(0), 3, widths, blocks, 12)
    x = jax.random.normal(jax.random.PRNGKey(1), (5,) + hw + (3,))
    prog = make_cnn_extractor(rep_dim=12, widths=widths,
                              blocks_per_stage=blocks)
    # the initializer writes the program's parameter layout
    layout = prog.init(jax.random.PRNGKey(2), x)
    assert (jax.tree_util.tree_structure(params)
            == jax.tree_util.tree_structure(layout))
    got = ref.cnn_forward(params, x, widths, blocks)
    want = prog.apply(params, x)
    np.testing.assert_allclose(got, want, rtol=TOL, atol=TOL)
    blocked = ref.extractor_forward("cnn", params, x, widths, blocks,
                                    block_rows=2)
    np.testing.assert_allclose(blocked, got, rtol=TOL, atol=TOL)


def test_mlp_and_heads_match_program():
    params = ref.init_dense(jax.random.PRNGKey(0), [13, 64, 16])
    x = jax.random.normal(jax.random.PRNGKey(1), (9, 13))
    want = make_mlp_extractor(rep_dim=16, hidden=(64,)).apply(params, x)
    np.testing.assert_allclose(ref.dense_forward(params, x), want, rtol=TOL,
                               atol=TOL)
    head = ref.init_dense(jax.random.PRNGKey(3), [32, 10])
    h = jax.random.normal(jax.random.PRNGKey(4), (9, 32))
    np.testing.assert_allclose(ref.dense_forward(head, h),
                               make_classifier(10).apply(head, h),
                               rtol=TOL, atol=TOL)


def test_kmeans_refit_is_a_fixed_point_of_the_program_clustering():
    k = jax.random.split(jax.random.PRNGKey(0), 3)
    centers = jax.random.normal(k[0], (4, 8)) * 3
    x = centers[jax.random.randint(k[1], (200,), 0, 4)] + 0.1 * (
        jax.random.normal(k[2], (200, 8)))
    labels = clustering.gradient_pseudo_labels(jax.random.PRNGKey(5), x, 4)
    assert int(jnp.sum(ref.kmeans_refit(x, labels, 4) != labels)) == 0
    _, best = ref.kmeans_fit(jax.random.PRNGKey(6), x, 4)
    assert float(ref.kmeans_inertia(x, labels, 4)) <= float(best) * (1 + 1e-4)
    # a partition that is not a fixed point moves
    wrong = labels.at[:20].set((labels[:20] + 1) % 4)
    assert int(jnp.sum(ref.kmeans_refit(x, wrong, 4) != wrong)) >= 20


def test_eq10_matches_program():
    k = jax.random.split(jax.random.PRNGKey(0), 3)
    h_u = jax.random.normal(k[0], (40, 16))
    h_o_a = jax.random.normal(k[1], (24, 16))
    h_o_b = jax.random.normal(k[2], (24, 16))
    want = estimator.sdpa_transform(h_u, h_o_a, h_o_b)
    np.testing.assert_allclose(ref.eq10(h_u, h_o_a, h_o_b), want, rtol=TOL,
                               atol=TOL)


def _ssl_tasks(threshold):
    """Two parties' tiny one-shot SSL tasks in the program's own types."""
    from repro.core.ssl import SSLConfig
    from repro.engine import PartyParams, PartyTask

    ext = make_cnn_extractor(rep_dim=8, widths=(4, 8), blocks_per_stage=1)
    head = make_classifier(4)
    cfg = SSLConfig(modality="image", confidence_threshold=threshold,
                    max_shift=1, cutout_size=2)
    tasks = []
    for p in range(2):
        k = jax.random.split(jax.random.PRNGKey(10 + p), 5)
        x_l = jax.random.normal(k[0], (48, 8, 4, 3))
        x_u = jax.random.normal(k[1], (70, 8, 4, 3))
        params = PartyParams(ext.init(k[2], x_l), head.init(k[3],
                                                            jnp.zeros((1, 8))))
        tasks.append(PartyTask(extractor=ext, head=head, params=params,
                               ssl_cfg=cfg, x_labeled=x_l,
                               y_pseudo=jax.random.randint(k[4], (48,), 0, 4),
                               x_unlabeled=x_u))
    return tasks


@pytest.mark.parametrize("threshold", [0.3, 0.95])
def test_ssl_session_matches_program(threshold):
    """The reference session follows the program's vmapped session step for
    step from the same start and key: the same batches, augmentations,
    loss, clipping and momentum."""
    from repro import engine

    from bench.reference import ssl as ref_ssl

    tasks = _ssl_tasks(threshold)
    hp = engine.SSLHParams(epochs=2, batch_size=16, learning_rate=0.05)
    key = jax.random.PRNGKey(3)
    got, metrics, vmapped = engine.train_clients_ssl(key, tasks, hp,
                                                     mode="vmap")
    assert vmapped
    ref_hp = ref_ssl.Hyper(
        epochs=2, batch_size=16, learning_rate=0.05, momentum=hp.momentum,
        grad_clip=hp.grad_clip, unlabeled_ratio=hp.unlabeled_ratio,
        lambda_u=1.0, confidence_threshold=threshold, max_shift=1,
        cutout_size=2, jitter=0.25, noise=0.1)
    for p, (task, kk) in enumerate(zip(tasks, jax.random.split(key, 2))):
        want = ref_ssl.session(
            {"extractor": task.params.extractor, "head": task.params.head},
            task.x_labeled, task.y_pseudo, task.x_unlabeled, kk, ref_hp,
            (4, 8), 1)
        assert want.losses.shape == (6,)
        np.testing.assert_allclose(float(want.losses[-1]),
                                   metrics[p]["loss"], rtol=1e-4)
        for a, b in zip(jax.tree_util.tree_leaves(
                {"extractor": got[p].extractor, "head": got[p].head}),
                jax.tree_util.tree_leaves(want.params)):
            np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)


def test_controls_round_below_the_reference():
    x = jax.random.normal(jax.random.PRNGKey(0), (64, 64))
    w = jax.random.normal(jax.random.PRNGKey(1), (64, 64))
    exact = ref.matmul(x, w)
    gaps = {p: float(jnp.linalg.norm(ref.matmul(x, w, p) - exact)
                     / jnp.linalg.norm(exact))
            for p in ("high", "fp8")}
    assert gaps["high"] < 1e-4 and 1e-2 < gaps["fp8"] < 0.1
