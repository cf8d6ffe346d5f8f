"""Plain reference of one party's local-SSL session on tabular features
(FixMatch-tab), independent of the program under test: nothing here imports
``repro``.

The batches, their order and the per-step keys follow ``bench/reference/
ssl.py``'s ``schedule`` (shuffled drop-remainder epochs of the labeled rows,
``unlabeled_ratio`` times as many private rows drawn uniformly per step).
Per step, with the party's feature mean x̄ taken over its private rows:

* the weak view (Eq. 5): each feature kept with probability 1 - r_m,
  else replaced by its mean, α(x) = m ⊗ x + (1 - m) ⊗ x̄;
* the strong view (Eq. 6): the unlabeled rows' weak view, under the same
  mask m, plus Gaussian noise of deviation σ, A(x) = α(x) + n;
* the supervised term: cross-entropy of the labeled rows' weak view against
  their pseudo-labels, over the valid labeled rows;
* FixMatch's term: the unlabeled weak view, under stop_gradient, gives a
  pseudo-label where its softmax exceeds ``confidence_threshold`` and the
  row is valid; the strong view is trained towards it;
* loss = supervised + ``lambda_u`` x FixMatch; the gradient is clipped to
  global norm ``grad_clip`` and applied by SGD with heavy-ball momentum.

Few-shot's phase 5' sessions are masked: the labeled set is every aligned
row followed by the whole private pool, with a validity mask over it (1 on
the aligned rows, the gate on the pool), and the unlabeled rows carry the
complementary mask. A mean over a batch is over its valid rows (at least
one). ``dtype`` float32 is the reference; bfloat16 holds parameters,
optimizer state and activations in bfloat16 (the control). ``half`` counts
only the first half of each batch's rows (a planted fault).
"""
from __future__ import annotations

from functools import lru_cache
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from bench.reference import ssl as ref_ssl
from bench.reference import vfl as ref


class Hyper(NamedTuple):
    epochs: int
    batch_size: int
    learning_rate: float
    momentum: float
    grad_clip: float
    unlabeled_ratio: int
    lambda_u: float
    confidence_threshold: float
    mask_ratio: float
    sigma: float


def hyper(config: dict) -> Hyper:
    """The session's hyper-parameters as a configuration file states them."""
    pc = config["protocol"]
    return Hyper(epochs=int(pc["client_epochs"]),
                 batch_size=int(pc["batch_size"]), **config["ssl"])


def _ce(logits, labels):
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return -jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]


def weak(key, x, mean, hp: Hyper):
    """Eq. 5: each feature kept with probability 1 - r_m, else its mean."""
    keep = jax.random.bernoulli(key, 1.0 - hp.mask_ratio, x.shape)
    return jnp.where(keep, x, mean)


def weak_and_strong(key, x, mean, hp: Hyper):
    """Eq. 5 and 6 under one mask: (α(x), α(x) + n)."""
    k_mask, k_noise = jax.random.split(key)
    view = weak(k_mask, x, mean, hp)
    return view, view + hp.sigma * jax.random.normal(k_noise, x.shape)


def fixmatch_loss(params, key, xl, yl, xu, w_l, w_u, mean, hp: Hyper,
                  precision: str = "highest"):
    """One minibatch of the objective, each row weighted by ``w_l`` /
    ``w_u`` (its validity, times the half-batch fault's weights)."""
    dtype = jax.tree_util.tree_leaves(params)[0].dtype

    def logits(x):
        reps = ref.dense_forward(params["extractor"], x.astype(dtype),
                                 precision)
        return ref.dense_forward(params["head"], reps, precision)

    k_l, k_u = jax.random.split(key)
    l_s = (jnp.sum(_ce(logits(weak(k_l, xl, mean, hp)), yl) * w_l)
           / jnp.maximum(jnp.sum(w_l), 1.0))
    view, noisy = weak_and_strong(k_u, xu, mean, hp)
    q = jax.lax.stop_gradient(jax.nn.softmax(
        logits(view).astype(jnp.float32), axis=-1))
    mask = (jnp.max(q, -1) > hp.confidence_threshold) * w_u
    l_u = (jnp.sum(_ce(logits(noisy), jnp.argmax(q, -1)) * mask)
           / jnp.maximum(jnp.sum(mask), 1.0))
    return l_s + hp.lambda_u * l_u


@lru_cache(maxsize=None)
def _session_fn(hp: Hyper, precision: str):
    def run(params, x_l, y_l, x_u, m_l, m_u, mean, idx_l, idx_u, keys, h_l,
            h_u):
        dtype = jax.tree_util.tree_leaves(params)[0].dtype
        grad_fn = jax.value_and_grad(
            lambda p, k, il, iu: fixmatch_loss(
                p, k, x_l[il], y_l[il], x_u[iu], m_l[il] * h_l,
                m_u[iu] * h_u, mean, hp, precision))

        def body(carry, inp):
            p, m, g1 = carry
            il, iu, k, first = inp
            loss, g = grad_fn(p, k, il, iu)
            leaves = jax.tree_util.tree_leaves(g)
            norm = jnp.sqrt(sum(jnp.sum(jnp.square(a.astype(jnp.float32)))
                                for a in leaves))
            scale = jnp.minimum(1.0, hp.grad_clip / (norm + 1e-12))
            g = jax.tree_util.tree_map(lambda a: (a * scale).astype(dtype), g)
            g1 = jax.tree_util.tree_map(lambda a, b: jnp.where(first, b, a),
                                        g1, g)
            m = jax.tree_util.tree_map(lambda a, b: hp.momentum * a + b, m, g)
            p = jax.tree_util.tree_map(
                lambda a, b: (a - hp.learning_rate * b).astype(dtype), p, m)
            return (p, m, g1), loss

        zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
        first = jnp.arange(keys.shape[0]) == 0
        (params, _, g1), losses = jax.lax.scan(
            body, (params, zeros, zeros), (idx_l, idx_u, keys, first))
        return params, losses, g1

    return jax.jit(run)


def session(params: dict, x_l, y_l, x_u, key, hp: Hyper,
            labeled_mask: Optional[jnp.ndarray] = None,
            unlabeled_mask: Optional[jnp.ndarray] = None,
            dtype=jnp.float32, half: bool = False,
            precision: str = "highest") -> ref_ssl.Session:
    """One party's session from ``params`` ({"extractor", "head"}); masks
    of ``None`` mark every row valid."""
    idx_l, idx_u, keys = ref_ssl.schedule(key, x_l.shape[0], x_u.shape[0],
                                          hp)
    halves = []
    for n in (idx_l.shape[1], idx_u.shape[1]):
        w = jnp.ones((n,), jnp.float32)
        halves.append(w.at[n // 2:].set(0.0) if half else w)
    valid = lambda m, n: (jnp.ones((n,), jnp.float32) if m is None
                          else jnp.asarray(m, jnp.float32))
    cast = lambda t: jax.tree_util.tree_map(lambda a: a.astype(dtype), t)
    mean = jnp.mean(x_u, axis=0)
    p, losses, g1 = _session_fn(hp, precision)(
        cast(params), cast(x_l), y_l, cast(x_u),
        valid(labeled_mask, x_l.shape[0]), valid(unlabeled_mask, x_u.shape[0]),
        mean.astype(dtype), idx_l, idx_u, keys, *halves)
    f32 = lambda t: jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), t)
    return ref_ssl.Session(f32(p), losses, f32(g1))
