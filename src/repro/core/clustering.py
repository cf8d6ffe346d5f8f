"""k-means over partial gradients → temporary labels (step ③, Alg. 1 l.28).

The paper's intuition: ∇_{h_i} L for same-class samples point in similar
directions, so clustering the N_o gradient rows into C groups recovers the
server's labels up to permutation — without the labels ever leaving the
server.

Implementation: k-means++ seeding + Lloyd iterations, fully jittable
(lax.fori_loop). The inner assignment (pairwise distance + argmin) is the
compute hot-spot and is served by the Pallas kernel in
``repro.kernels.kmeans`` (enabled with use_kernel=True; the pure-jnp path is
the oracle).
"""
from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp


# k-means matmuls are f32-exact: at a TPU's default precision an f32 matmul
# is one bf16 pass, which flips assignments of near-tied gradient rows. The
# Pallas assignment kernel runs its dot at the same precision, so the two
# routes stay bit-equal on the chip (kernels/kmeans/ops.py)
_HIGHEST = jax.lax.Precision.HIGHEST


def _pairwise_sq_dists(x: jnp.ndarray, centers: jnp.ndarray) -> jnp.ndarray:
    """(N, C) squared euclidean distances, MXU-friendly expansion."""
    x2 = jnp.sum(x * x, axis=1, keepdims=True)          # (N, 1)
    c2 = jnp.sum(centers * centers, axis=1)             # (C,)
    return x2 - 2.0 * jnp.matmul(x, centers.T, precision=_HIGHEST) + c2[None, :]


def assign_clusters(x: jnp.ndarray, centers: jnp.ndarray, use_kernel: bool = False
                    ) -> jnp.ndarray:
    if use_kernel:
        from repro.kernels.kmeans import ops as kops
        return kops.kmeans_assign(x, centers)
    return jnp.argmin(_pairwise_sq_dists(x, centers), axis=1)


def _kmeanspp_init(key, x: jnp.ndarray, k: int) -> jnp.ndarray:
    """k-means++ seeding (jittable: fori_loop over k)."""
    n = x.shape[0]
    key, k0 = jax.random.split(key)
    first = jax.random.randint(k0, (), 0, n)
    centers0 = jnp.zeros((k, x.shape[1]), x.dtype).at[0].set(x[first])

    def body(i, carry):
        centers, key = carry
        d = _pairwise_sq_dists(x, centers)
        # distances to the i centers chosen so far; rest are masked out
        valid = jnp.arange(k) < i
        dmin = jnp.min(jnp.where(valid[None, :], d, jnp.inf), axis=1)
        dmin = jnp.maximum(dmin, 0.0)
        key, kc = jax.random.split(key)
        probs = dmin / jnp.maximum(jnp.sum(dmin), 1e-12)
        idx = jax.random.choice(kc, n, p=probs)
        return centers.at[i].set(x[idx]), key

    centers, _ = jax.lax.fori_loop(1, k, body, (centers0, key))
    return centers


def _normalized_search(key, x: jnp.ndarray, num_clusters: int,
                       num_iters: int, restarts: int
                       ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The search core shared by :func:`kmeans` and the batched fold:
    cosine-normalize rows + multi-restart Lloyd → (xn, lowest-inertia
    centers). The final full-size assignment is the caller's — that's the
    kernel-servable hot-spot, single-entry or batched-grid alike."""
    x = x.astype(jnp.float32)
    # Normalize rows: the cluster signal is the gradient *direction* (the
    # magnitude mostly encodes confidence), cosine k-means is markedly more
    # robust here and is what "similar directions" in the paper implies.
    norms = jnp.linalg.norm(x, axis=1, keepdims=True)
    xn = x / jnp.maximum(norms, 1e-12)

    def one_run(k):
        centers = _kmeanspp_init(k, xn, num_clusters)

        def step(_, centers):
            # jnp path inside the vmapped restarts; only the final
            # full-size assignment is worth a kernel launch
            assign = assign_clusters(xn, centers, use_kernel=False)
            onehot = jax.nn.one_hot(assign, num_clusters, dtype=xn.dtype)  # (N, C)
            sums = jnp.matmul(onehot.T, xn, precision=_HIGHEST)           # (C, d)
            counts = jnp.sum(onehot, axis=0)[:, None]
            new = sums / jnp.maximum(counts, 1.0)
            # keep empty clusters where they were
            new = jnp.where(counts > 0, new, centers)
            new = new / jnp.maximum(jnp.linalg.norm(new, axis=1, keepdims=True),
                                    1e-12)
            return new

        centers = jax.lax.fori_loop(0, num_iters, step, centers)
        inertia = jnp.sum(jnp.min(_pairwise_sq_dists(xn, centers), axis=1))
        return centers, inertia

    all_centers, inertias = jax.vmap(one_run)(jax.random.split(key, restarts))
    return xn, all_centers[jnp.argmin(inertias)]


@partial(jax.jit, static_argnames=("num_clusters", "num_iters", "use_kernel",
                                   "restarts"))
def kmeans(key, x: jnp.ndarray, num_clusters: int, num_iters: int = 25,
           use_kernel: bool = False, restarts: int = 4
           ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Multi-restart Lloyd; returns the lowest-inertia (assignments, centers)."""
    xn, centers = _normalized_search(key, x, num_clusters, num_iters, restarts)
    return assign_clusters(xn, centers, use_kernel=use_kernel), centers


def gradient_pseudo_labels(key, partial_grads: jnp.ndarray, num_classes: int,
                           num_iters: int = 25, use_kernel: bool = False,
                           restarts: int = 4) -> jnp.ndarray:
    """Ŷ_o^k ← k-means(∇_{H_o^k} Loss, C)   (Alg. 1, line 28).

    Fully jittable, so it also runs *inside* the engine's shard_map one-shot
    session (``repro.launch.vfl_step``) where it stays party-local — zero
    pod-axis collectives. ``restarts=1`` keeps that compiled path lean; the
    host-scale protocol keeps the default multi-restart robustness.
    Callers outside the engine should prefer ``repro.engine.pseudo_labels``,
    which carries the engine-wide ``use_kernels`` switch.
    """
    labels, _ = kmeans(key, partial_grads, num_classes, num_iters, use_kernel,
                       restarts=restarts)
    return labels


def gradient_pseudo_labels_batched(keys: jnp.ndarray,
                                   partial_grads: jnp.ndarray,
                                   num_classes: int, num_iters: int = 25,
                                   use_kernel: bool = False,
                                   restarts: int = 4) -> jnp.ndarray:
    """Step ③ for a stacked batch: keys (B, 2), partial_grads (B, N, d) →
    (B, N) pseudo labels.

    The batch axis is the engine's anonymous stacked fold axis (S seeds ×
    C scenarios × K parties upstream). The jnp route vmaps the single-entry
    program verbatim — bit-identical per entry to the per-call path. The
    kernel route vmaps only the center *search* and serves every entry's
    final full-size assignment with ONE batched ``(B, N/BN)`` Pallas grid
    (``repro.kernels.kmeans.ops.kmeans_assign_batched``) — no per-entry
    launch loop, no vmap-of-pallas_call. Callers wanting the session-cached
    compiled fold should go through ``repro.engine.pseudo_labels_batched``.
    """
    if not use_kernel:
        return jax.vmap(
            lambda k, g: gradient_pseudo_labels(
                k, g, num_classes, num_iters, use_kernel=False,
                restarts=restarts))(keys, partial_grads)
    xn, centers = jax.vmap(
        lambda k, g: _normalized_search(k, g, num_classes, num_iters,
                                        restarts))(keys, partial_grads)
    from repro.kernels.kmeans import ops as kops
    return kops.kmeans_assign_batched(xn, centers)


def cluster_purity(pseudo: jnp.ndarray, true: jnp.ndarray, num_classes: int) -> float:
    """Diagnostic: fraction of samples whose cluster's majority true-label
    matches their own (label-permutation-invariant accuracy upper bound)."""
    conf = jnp.zeros((num_classes, num_classes), jnp.int32)
    conf = conf.at[pseudo, true].add(1)
    return float(jnp.sum(jnp.max(conf, axis=1)) / pseudo.shape[0])


def align_pseudo_to_true(pseudo: jnp.ndarray, true: jnp.ndarray, num_classes: int
                         ) -> jnp.ndarray:
    """Greedy cluster→label matching (diagnostics only; clients cannot do
    this — they never see true labels)."""
    conf = jnp.zeros((num_classes, num_classes), jnp.int32).at[pseudo, true].add(1)
    conf = jnp.asarray(conf)
    import numpy as np

    conf = np.array(conf)
    mapping = -np.ones(num_classes, np.int32)
    used = set()
    for _ in range(num_classes):
        i, j = np.unravel_index(np.argmax(conf), conf.shape)
        mapping[i] = j
        conf[i, :] = -1
        conf[:, j] = -1
        used.add(j)
    # unassigned clusters (if any) map to remaining labels arbitrarily
    remaining = [j for j in range(num_classes) if j not in used]
    for i in range(num_classes):
        if mapping[i] < 0:
            mapping[i] = remaining.pop()
    return jnp.asarray(mapping)[pseudo]
