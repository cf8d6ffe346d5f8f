"""Pure-jnp oracle for the SDPA representation-estimation kernel (Eq. 10).

Matmuls run at ``Precision.HIGHEST`` (a TPU's default f32 matmul is one
bf16 pass, ~1e-2 off)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def sdpa_estimate(h_u: jnp.ndarray, h_o_a: jnp.ndarray, h_o_b: jnp.ndarray
                  ) -> jnp.ndarray:
    """Ĥ_u^B = softmax(H_u^A H_o^Aᵀ / √d) H_o^B.

    h_u: (N_u, d), h_o_a: (N_o, d), h_o_b: (N_o, d_b) → (N_u, d_b) f32.
    """
    h_u = h_u.astype(jnp.float32)
    h_o_a = h_o_a.astype(jnp.float32)
    h_o_b = h_o_b.astype(jnp.float32)
    d = h_u.shape[-1]
    hi = jax.lax.Precision.HIGHEST
    scores = (jnp.matmul(h_u, h_o_a.T, precision=hi)
              / jnp.sqrt(jnp.asarray(d, jnp.float32)))
    return jnp.matmul(jax.nn.softmax(scores, axis=-1), h_o_b, precision=hi)
