"""Pure-jnp oracle for the k-means assignment kernel.

Matmuls run at ``Precision.HIGHEST``: at a TPU's default precision an f32
matmul is one bf16 pass, which flips assignments near ties."""
from __future__ import annotations

import jax
import jax.numpy as jnp

_HIGHEST = jax.lax.Precision.HIGHEST


def kmeans_assign(x: jnp.ndarray, centers: jnp.ndarray) -> jnp.ndarray:
    """argmin_c ‖x_i - μ_c‖²  →  (N,) int32.

    x: (N, d) float; centers: (C, d) float.
    """
    x = x.astype(jnp.float32)
    centers = centers.astype(jnp.float32)
    x2 = jnp.sum(x * x, axis=1, keepdims=True)
    c2 = jnp.sum(centers * centers, axis=1)
    d = x2 - 2.0 * jnp.matmul(x, centers.T, precision=_HIGHEST) + c2[None, :]
    return jnp.argmin(d, axis=1).astype(jnp.int32)


def kmeans_min_dist(x: jnp.ndarray, centers: jnp.ndarray) -> jnp.ndarray:
    x = x.astype(jnp.float32)
    centers = centers.astype(jnp.float32)
    x2 = jnp.sum(x * x, axis=1, keepdims=True)
    c2 = jnp.sum(centers * centers, axis=1)
    d = x2 - 2.0 * jnp.matmul(x, centers.T, precision=_HIGHEST) + c2[None, :]
    return jnp.min(d, axis=1)
