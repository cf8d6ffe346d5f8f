"""Few-shot VFL server-side machinery: representation estimation + gating.

* ``sdpa_transform`` — Eq. (10): Ĥ_u^B = softmax(H_u^A H_o^Aᵀ / √d) H_o^B.
  The jnp path is the oracle; ``use_kernel=True`` routes to the Pallas
  flash-style blocked kernel (repro.kernels.sdpa_estimator) which is the
  TPU hot-spot when N_u ≫ N_o.
* ``infer_prob`` — Eq. (8)-(9): agreement × confidence gating probability
  p̂_{u,i} for pseudo-labeling client unaligned samples.
"""
from __future__ import annotations

from typing import Callable, Sequence

import jax
import jax.numpy as jnp


def sdpa_transform(h_u_a: jnp.ndarray, h_o_a: jnp.ndarray, h_o_b: jnp.ndarray,
                   use_kernel: bool = False) -> jnp.ndarray:
    """Ĥ_u^B = softmax(H_u^A ⊗ H_o^Aᵀ / √d) ⊗ H_o^B    (Eq. 10).

    Shapes: h_u_a (N_u, d_a), h_o_a (N_o, d_a), h_o_b (N_o, d_b).
    """
    if use_kernel:
        from repro.kernels.sdpa_estimator import ops as kops
        return kops.sdpa_estimate(h_u_a, h_o_a, h_o_b)
    # f32-exact on a TPU too, where a default-precision f32 matmul is one
    # bf16 pass — the kernel route computes at the same precision
    hi = jax.lax.Precision.HIGHEST
    d = h_u_a.shape[-1]
    scores = (jnp.matmul(h_u_a, h_o_a.T, precision=hi)
              / jnp.sqrt(jnp.asarray(d, h_u_a.dtype)))
    return jnp.matmul(jax.nn.softmax(scores, axis=-1), h_o_b, precision=hi)


def sdpa_transform_batched(h_u_a: jnp.ndarray, h_o_a: jnp.ndarray,
                           h_o_b: jnp.ndarray, use_kernel: bool = False
                           ) -> jnp.ndarray:
    """Eq. 10 over a stacked leading batch axis (the engine's anonymous
    fold axis: seeds, or a served partial-party batch).

    Shapes: h_u_a (B, N_u, d_a), h_o_a (B, N_o, d_a), h_o_b (B, N_o, d_b).
    The kernel route is ONE batched ``(B, N_u/BU, N_o/BO)`` Pallas grid
    launch; the jnp route vmaps the single-entry oracle verbatim."""
    if use_kernel:
        from repro.kernels.sdpa_estimator import ops as kops
        return kops.sdpa_estimate_batched(h_u_a, h_o_a, h_o_b)
    return jax.vmap(
        lambda q, a, b: sdpa_transform(q, a, b, use_kernel=False)
    )(h_u_a, h_o_a, h_o_b)


def estimate_missing_parties(
    h_u_k: jnp.ndarray,
    h_o_all: Sequence[jnp.ndarray],
    k: int,
    use_kernel: bool = False,
) -> list:
    """For client k's unaligned reps, estimate every other party's missing
    representation (K-ary generalization of Eq. 10, DESIGN.md §1)."""
    out = []
    for j, h_o_j in enumerate(h_o_all):
        if j == k:
            continue
        out.append(sdpa_transform(h_u_k, h_o_all[k], h_o_j, use_kernel=use_kernel))
    return out


def infer_prob(
    aux_logits_fn: Callable,      # (h_u_k,)            -> (N_u, C)  local-only f_c^k
    joint_logits_fn: Callable,    # (full_concat_rep,)  -> (N_u, C)  joint f_c
    h_u_k: jnp.ndarray,
    full_rep: jnp.ndarray,
    threshold: float,
) -> jnp.ndarray:
    """p̂_{u,i} = 1[ŷ^A = ŷ^{A,B}] · 1[p^A > t] · 1[p^{A,B} > t] · p^{A,B}  (Eq. 9)."""
    p_local = jax.nn.softmax(aux_logits_fn(h_u_k), axis=-1)
    p_joint = jax.nn.softmax(joint_logits_fn(full_rep), axis=-1)
    y_local = jnp.argmax(p_local, axis=-1)
    y_joint = jnp.argmax(p_joint, axis=-1)
    conf_local = jnp.max(p_local, axis=-1)
    conf_joint = jnp.max(p_joint, axis=-1)
    agree = (y_local == y_joint).astype(p_joint.dtype)
    gate = agree * (conf_local > threshold) * (conf_joint > threshold)
    return gate * conf_joint
