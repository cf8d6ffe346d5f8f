"""Plain float32 reference of what the benchmark checks, independent of the
program under test (nothing here imports ``repro``).

* ``init_cnn`` / ``init_dense`` — seeded weights in the
  parameter layout the program's extractors take;
* ``cnn_forward`` — WideResNet-style extractor: 3x3 stem, ``blocks`` pre-
  activation residual blocks per stage (GroupNorm with gcd(8, C) groups,
  eps 1e-5, ReLU, 3x3 convs, stride 2 on the first block of every stage after
  the first, 1x1 projection where the width changes), GroupNorm + ReLU,
  global average pool, dense head to ``rep_dim``;
* ``dense_forward`` — ReLU MLPs (the tabular extractor and every head);
* ``kmeans_refit`` — one Lloyd step of cosine k-means from a given
  partition: the assignment a converged step-3 clustering reproduces;
* ``kmeans_fit`` / ``kmeans_inertia`` — the reference's own cosine k-means
  (k-means++ seeding, Lloyd iterations) and a partition's inertia;
* ``eq10`` — Eq. 10, softmax(H_u^A H_o^A^T / sqrt(d)) H_o^B;

Every matmul and convolution takes a ``precision``: ``"highest"`` is the
forwards' reference (float32 at ``Precision.HIGHEST``); ``"default"`` is
XLA's default, the precision a configuration may state for a layer; the
lower ones are the controls: ``"high"`` (three bf16 passes) and ``"fp8"``
(inputs scaled per tensor into float8_e4m3fn, accumulated in float32).
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

_P = jax.lax.Precision
_FP8_MAX = 448.0


def _round(x, precision: str):
    """Round an operand to the control's input precision."""
    if precision == "fp8":
        scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / _FP8_MAX
        q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32)
        return q * scale
    return x


def _lax_precision(precision: str):
    return {"high": _P.HIGH, "default": _P.DEFAULT}.get(precision,
                                                        _P.HIGHEST)


def matmul(a, b, precision: str = "highest"):
    return jnp.matmul(_round(a, precision), _round(b, precision),
                      precision=_lax_precision(precision))


def conv(x, w, stride: int = 1, precision: str = "highest"):
    return jax.lax.conv_general_dilated(
        _round(x, precision), _round(w, precision), (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=_lax_precision(precision))


def group_norm(x, scale, bias, groups: int = 8, eps: float = 1e-5):
    n, h, w, c = x.shape
    g = math.gcd(groups, c)
    xg = x.reshape(n, h, w, g, c // g)
    mean = jnp.mean(xg, axis=(1, 2, 4), keepdims=True)
    var = jnp.mean(jnp.square(xg - mean), axis=(1, 2, 4), keepdims=True)
    xg = (xg - mean) / jnp.sqrt(var + eps)
    return xg.reshape(n, h, w, c) * scale + bias


# ------------------------------------------------------------- weights
def _he(key, shape, fan_in):
    return jax.random.normal(key, shape, jnp.float32) * math.sqrt(2.0 / fan_in)


def init_cnn(key, c_in: int, widths, blocks: int, rep_dim: int) -> dict:
    """He-normal conv and dense weights, unit GroupNorm scales, zero biases,
    in the program's extractor layout."""
    keys = iter(jax.random.split(key, 2 + 3 * len(widths) * blocks))
    params = {"stem": _he(next(keys), (3, 3, c_in, widths[0]), 9 * c_in)}
    prev = widths[0]
    for s, width in enumerate(widths):
        for b in range(blocks):
            p = {"conv1": _he(next(keys), (3, 3, prev, width), 9 * prev),
                 "conv2": _he(next(keys), (3, 3, width, width), 9 * width),
                 "gn1_scale": jnp.ones((prev,)), "gn1_bias": jnp.zeros((prev,)),
                 "gn2_scale": jnp.ones((width,)), "gn2_bias": jnp.zeros((width,))}
            k3 = next(keys)
            if prev != width:
                p["proj"] = _he(k3, (1, 1, prev, width), prev)
            params[f"s{s}b{b}"] = p
            prev = width
    params["head_w"] = _he(next(keys), (prev, rep_dim), prev)
    params["head_b"] = jnp.zeros((rep_dim,))
    params["out_gn_scale"] = jnp.ones((prev,))
    params["out_gn_bias"] = jnp.zeros((prev,))
    return params


def init_dense(key, dims) -> dict:
    """ReLU-MLP weights ``w{i}``/``b{i}`` for layer widths ``dims``."""
    keys = jax.random.split(key, len(dims) - 1)
    params = {}
    for i in range(len(dims) - 1):
        params[f"w{i}"] = _he(keys[i], (dims[i], dims[i + 1]), dims[i])
        params[f"b{i}"] = jnp.zeros((dims[i + 1],))
    return params


# ------------------------------------------------------------ forwards
def cnn_forward(params: dict, x, widths, blocks: int,
                precision: str = "highest"):
    h = conv(x, params["stem"], precision=precision)
    for s in range(len(widths)):
        for b in range(blocks):
            p = params[f"s{s}b{b}"]
            stride = 2 if (b == 0 and s > 0) else 1
            y = jax.nn.relu(group_norm(h, p["gn1_scale"], p["gn1_bias"]))
            if "proj" in p:
                shortcut = conv(y, p["proj"], stride, precision)
            elif stride != 1:
                shortcut = h[:, ::stride, ::stride, :]
            else:
                shortcut = h
            y = conv(y, p["conv1"], stride, precision)
            y = jax.nn.relu(group_norm(y, p["gn2_scale"], p["gn2_bias"]))
            h = shortcut + conv(y, p["conv2"], 1, precision)
    h = jax.nn.relu(group_norm(h, params["out_gn_scale"], params["out_gn_bias"]))
    h = jnp.mean(h, axis=(1, 2))
    return matmul(h, params["head_w"], precision) + params["head_b"]


def dense_forward(params: dict, x, precision: str = "highest"):
    """ReLU MLP over ``w0, b0, w1, ...``: the tabular extractor and every
    classifier head."""
    n = len([k for k in params if k.startswith("w")])
    h = x
    for i in range(n):
        h = matmul(h, params[f"w{i}"], precision) + params[f"b{i}"]
        if i < n - 1:
            h = jax.nn.relu(h)
    return h



def extractor_forward(kind: str, params: dict, x, widths=(), blocks: int = 1,
                      precision: str = "highest", block_rows: int = 2048):
    """One party's extractor over ``x`` in blocks of rows, so that a test
    split of any size fits beside the program's buffers."""
    if kind == "cnn":
        fn = jax.jit(lambda p, xb: cnn_forward(p, xb, widths, blocks,
                                               precision))
    else:
        fn = jax.jit(lambda p, xb: dense_forward(p, xb, precision))
    parts = [fn(params, x[i:i + block_rows])
             for i in range(0, x.shape[0], block_rows)]
    return jnp.concatenate(parts, axis=0)


# ----------------------------------------------------- protocol pieces
@partial(jax.jit, static_argnames=("num_clusters", "precision"))
def kmeans_refit(grads, labels, num_clusters: int,
                 precision: str = "highest"):
    """Cosine k-means, one Lloyd step from the partition ``labels``: the
    rows are normalized, each cluster's center is the normalized mean of its
    rows (an empty cluster has none and wins no row), and every row is
    assigned to the nearest center. A converged clustering is a fixed point
    of this step."""
    x = grads / jnp.maximum(jnp.linalg.norm(grads, axis=1, keepdims=True),
                            1e-12)
    onehot = jax.nn.one_hot(labels, num_clusters, dtype=jnp.float32)
    sums = jnp.matmul(onehot.T, x, precision=_P.HIGHEST)
    counts = jnp.sum(onehot, axis=0)[:, None]
    centers = sums / jnp.maximum(counts, 1.0)
    centers = centers / jnp.maximum(
        jnp.linalg.norm(centers, axis=1, keepdims=True), 1e-12)
    d = (jnp.sum(x * x, axis=1, keepdims=True)
         - 2.0 * matmul(x, centers.T, precision)
         + jnp.sum(centers * centers, axis=1)[None, :])
    d = jnp.where(counts[:, 0][None, :] > 0, d, jnp.inf)
    return jnp.argmin(d, axis=1)


def eq10(h_u_a, h_o_a, h_o_b, precision: str = "highest"):
    d = h_u_a.shape[-1]
    scores = matmul(h_u_a, h_o_a.T, precision) / math.sqrt(d)
    return matmul(jax.nn.softmax(scores, axis=-1), h_o_b, precision)


def _normalize_rows(x):
    return x / jnp.maximum(jnp.linalg.norm(x, axis=1, keepdims=True), 1e-12)


@partial(jax.jit, static_argnames=("num_clusters",))
def kmeans_inertia(grads, labels, num_clusters: int):
    """Sum of squared distances of the normalized rows to the normalized
    means of their clusters under the partition ``labels``."""
    x = _normalize_rows(grads)
    onehot = jax.nn.one_hot(labels, num_clusters, dtype=jnp.float32)
    centers = _normalize_rows(jnp.matmul(onehot.T, x, precision=_P.HIGHEST)
                              / jnp.maximum(jnp.sum(onehot, 0)[:, None], 1.0))
    return jnp.sum(jnp.square(x - centers[labels]))


@partial(jax.jit, static_argnames=("num_clusters", "iters", "restarts"))
def kmeans_fit(key, grads, num_clusters: int, iters: int = 25,
               restarts: int = 4):
    """Cosine k-means from scratch (k-means++ seeding, Lloyd iterations,
    best of ``restarts``): the partition the reference itself finds."""
    x = _normalize_rows(grads)
    n = x.shape[0]

    def dists(c):
        return (jnp.sum(x * x, 1, keepdims=True)
                - 2.0 * jnp.matmul(x, c.T, precision=_P.HIGHEST)
                + jnp.sum(c * c, 1)[None, :])

    def one(k):
        k0, k1 = jax.random.split(k)
        centers = jnp.zeros((num_clusters, x.shape[1])).at[0].set(
            x[jax.random.randint(k0, (), 0, n)])
        for i in range(1, num_clusters):
            k1, kc = jax.random.split(k1)
            dmin = jnp.maximum(jnp.min(dists(centers[:i]), axis=1), 0.0)
            idx = jax.random.choice(kc, n, p=dmin / jnp.maximum(dmin.sum(),
                                                                1e-12))
            centers = centers.at[i].set(x[idx])

        def step(_, c):
            onehot = jax.nn.one_hot(jnp.argmin(dists(c), 1), num_clusters)
            counts = jnp.sum(onehot, 0)[:, None]
            new = jnp.matmul(onehot.T, x, precision=_P.HIGHEST) / jnp.maximum(
                counts, 1.0)
            return _normalize_rows(jnp.where(counts > 0, new, c))

        centers = jax.lax.fori_loop(0, iters, step, centers)
        labels = jnp.argmin(dists(centers), 1)
        return labels, jnp.sum(jnp.min(dists(centers), 1))

    labels, inertia = jax.vmap(one)(jax.random.split(key, restarts))
    best = jnp.argmin(inertia)
    return labels[best], kmeans_inertia(grads, labels[best], num_clusters)
