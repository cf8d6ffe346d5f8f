"""Plain reference of one party's local-SSL session (FixMatch on image
halves), independent of the program under test: nothing here imports
``repro``.

A session is ``epochs`` shuffled passes over the pseudo-labeled rows in
drop-remainder batches of ``batch_size``, each step also drawing
``unlabeled_ratio`` times as many private rows uniformly. Per step:

* the supervised term: cross-entropy of the weakly augmented labeled rows
  (random flip, random translation by up to ``max_shift`` with zero fill)
  against their pseudo-labels;
* FixMatch's term: the weak view of the unlabeled rows, under
  stop_gradient, gives a pseudo-label where its softmax exceeds
  ``confidence_threshold``; the strong view (weak + cutout + per-channel
  colour gain and bias + Gaussian noise) is trained towards it;
* loss = supervised + ``lambda_u`` x FixMatch; the gradient is clipped to
  global norm ``grad_clip`` and applied by SGD with heavy-ball momentum.

The order of rows and every random draw follow from the party's key as the
configuration's session states them, so the reference and the program see
the same batches and the same augmentations. Every matmul and convolution
runs at ``precision``, the one the configuration states for the sessions.
``dtype`` float32 is the reference; bfloat16 holds parameters, optimizer
state and activations in bfloat16 (the control). ``half`` takes each
batch's mean over its first half of rows only (a planted fault), in the
same compiled program.
"""
from __future__ import annotations

from functools import lru_cache
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import vfl as ref

#: offset of the unlabeled draws' generator seeds from the labeled ones
_UNLABELED_STREAM = 104729


class Hyper(NamedTuple):
    epochs: int
    batch_size: int
    learning_rate: float
    momentum: float
    grad_clip: float
    unlabeled_ratio: int
    lambda_u: float
    confidence_threshold: float
    max_shift: int
    cutout_size: int
    jitter: float
    noise: float


def hyper(config: dict) -> Hyper:
    """The session's hyper-parameters as a configuration file states them."""
    s = dict(config["ssl"])
    pc = config["protocol"]
    return Hyper(epochs=int(pc["client_epochs"]),
                 batch_size=int(pc["batch_size"]), **s)


def schedule(key, n_labeled: int, n_unlabeled: int, hp: Hyper):
    """(labeled indices, unlabeled indices, step keys), one row per step."""
    bs_l = min(hp.batch_size, n_labeled)
    bs_u = min(hp.batch_size * hp.unlabeled_ratio, n_unlabeled)
    seed0 = int(jax.random.randint(key, (), 0, 2**31 - 1))
    idx_l, idx_u = [], []
    for e in range(hp.epochs):
        perm = np.random.RandomState(seed0 + e).permutation(n_labeled)
        u_rng = np.random.RandomState(seed0 + 7919 * e + _UNLABELED_STREAM)
        for s in range(0, (n_labeled // bs_l) * bs_l, bs_l):
            idx_l.append(perm[s:s + bs_l])
            idx_u.append(u_rng.randint(0, n_unlabeled, size=bs_u))
    keys = jax.random.split(jax.random.fold_in(key, 1), len(idx_l))
    return (jnp.asarray(np.stack(idx_l), jnp.int32),
            jnp.asarray(np.stack(idx_u), jnp.int32), keys)


# --------------------------------------------------------- augmentation
def _flip(key, x):
    flip = jax.random.bernoulli(key, 0.5, (x.shape[0],))
    return jnp.where(flip[:, None, None, None], x[:, :, ::-1, :], x)


def _translate(key, x, max_shift: int):
    n, h, w, _ = x.shape
    kx, ky = jax.random.split(key)
    dx = jax.random.randint(kx, (n,), -max_shift, max_shift + 1)
    dy = jax.random.randint(ky, (n,), -max_shift, max_shift + 1)

    def one(img, sy, sx):
        img = jnp.roll(img, (sy, sx), axis=(0, 1))
        rows, cols = jnp.arange(h), jnp.arange(w)
        keep_r = jnp.where(sy >= 0, rows >= sy, rows < h + sy)
        keep_c = jnp.where(sx >= 0, cols >= sx, cols < w + sx)
        return img * (keep_r[:, None] & keep_c[None, :])[:, :, None]

    return jax.vmap(one)(x, dy, dx)


def weak(key, x, hp: Hyper):
    k1, k2 = jax.random.split(key)
    return _translate(k2, _flip(k1, x), hp.max_shift)


def strong(key, x, hp: Hyper):
    n, h, w, c = x.shape
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)
    y = weak(k1, x, hp)
    ky, kx = jax.random.split(k2)
    cy = jax.random.randint(ky, (n,), 0, h)[:, None, None]
    cx = jax.random.randint(kx, (n,), 0, w)[:, None, None]
    half = hp.cutout_size // 2
    keep = ((jnp.abs(jnp.arange(h)[None, :, None] - cy) > half)
            | (jnp.abs(jnp.arange(w)[None, None, :] - cx) > half))
    y = y * keep[..., None]
    gain = 1.0 + hp.jitter * jax.random.uniform(k3, (n, 1, 1, c), minval=-1,
                                                maxval=1)
    bias = hp.jitter * jax.random.uniform(k4, (n, 1, 1, c), minval=-1,
                                          maxval=1)
    return y * gain + bias + hp.noise * jax.random.normal(k5, y.shape)


# -------------------------------------------------------------- session
def _ce(logits, labels):
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return -jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]


def fixmatch_loss(params, key, xl, yl, xu, w_l, w_u, hp: Hyper, widths,
                  blocks: int, precision: str = "highest"):
    """One minibatch of the objective, each row weighted by ``w_l`` /
    ``w_u`` (ones, or ones on the first half of the batch only)."""
    dtype = jax.tree_util.tree_leaves(params)[0].dtype

    def logits(x):
        reps = ref.cnn_forward(params["extractor"], x.astype(dtype), widths,
                               blocks, precision)
        return ref.dense_forward(params["head"], reps, precision)

    k_l, k_u = jax.random.split(key)
    l_s = (jnp.sum(_ce(logits(weak(k_l, xl, hp)), yl) * w_l)
           / jnp.sum(w_l))
    kw, ks = jax.random.split(k_u)
    q = jax.lax.stop_gradient(jax.nn.softmax(
        logits(weak(kw, xu, hp)).astype(jnp.float32), axis=-1))
    mask = (jnp.max(q, -1) > hp.confidence_threshold) * w_u
    ce_u = _ce(logits(strong(ks, xu, hp)), jnp.argmax(q, -1))
    l_u = jnp.sum(ce_u * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return l_s + hp.lambda_u * l_u


@lru_cache(maxsize=None)
def _session_fn(hp: Hyper, widths: tuple, blocks: int, precision: str):
    def run(params, x_l, y_l, x_u, idx_l, idx_u, keys, w_l, w_u):
        dtype = jax.tree_util.tree_leaves(params)[0].dtype
        grad_fn = jax.value_and_grad(
            lambda p, k, xl, yl, xu: fixmatch_loss(p, k, xl, yl, xu, w_l, w_u,
                                                   hp, widths, blocks,
                                                   precision))

        def body(carry, inp):
            p, m, g1 = carry
            il, iu, k, first = inp
            loss, g = grad_fn(p, k, x_l[il], y_l[il], x_u[iu])
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


class Session(NamedTuple):
    params: dict          # {"extractor": ..., "head": ...} after the session
    losses: jnp.ndarray   # (steps,) loss of every step
    first_grad: dict      # the first step's clipped gradient


def session(params: dict, x_l, y_l, x_u, key, hp: Hyper, widths, blocks: int,
            dtype=jnp.float32, half: bool = False,
            precision: str = "highest") -> Session:
    """One party's session from ``params`` ({"extractor", "head"})."""
    idx_l, idx_u, keys = schedule(key, x_l.shape[0], x_u.shape[0], hp)
    weights = []
    for n in (idx_l.shape[1], idx_u.shape[1]):
        w = jnp.ones((n,), jnp.float32)
        weights.append(w.at[n // 2:].set(0.0) if half else w)
    cast = lambda t: jax.tree_util.tree_map(lambda a: a.astype(dtype), t)
    p, losses, g1 = _session_fn(hp, tuple(widths), int(blocks), precision)(
        cast(params), cast(x_l), y_l, cast(x_u), idx_l, idx_u, keys,
        *weights)
    f32 = lambda t: jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), t)
    return Session(f32(p), losses, f32(g1))
