"""Plain reference of few-shot VFL's step 3' (Alg. 2), independent of the
program under test: nothing here imports ``repro``.

* ``estimates`` — Eq. 10 for party k's private rows: every other party j's
  missing representation, softmax(H_u^k H_o^k^T / sqrt(d)) H_o^j, at the
  given precision (the configuration states HIGHEST);
* ``gate`` — Eqs. 8-9 at threshold t: with p^k the softmax of the server's
  auxiliary head f_c^k over H_u^k alone and p the softmax of the joint head
  f_c over [H_u^k, the estimates] in party order,
  p̂ = 1[argmax p^k = argmax p] · 1[max p^k > t] · 1[max p > t] · max p.

The heads are ``bench/reference/vfl.py``'s dense stacks over the program's
trained parameters. Each entry is computed on its own, so a stack of seeds
never holds more than one (N_u, N_o) score matrix at a time.
"""
from __future__ import annotations

from typing import List, Sequence

import jax
import jax.numpy as jnp

from bench.reference import vfl as ref


def estimates(h_u_k, h_o_all: Sequence, k: int,
              precision: str = "highest") -> List[jnp.ndarray]:
    """Eq. 10: the K - 1 missing representations of party k's rows, in
    party order."""
    return [ref.eq10(h_u_k, h_o_all[k], h_o_j, precision)
            for j, h_o_j in enumerate(h_o_all) if j != k]


def gate(aux_params: dict, joint_params: dict, h_u_k, missing: Sequence, k: int,
         threshold: float, precision: str = "highest"):
    """Eqs. 8-9: p̂ for each of party k's rows, given the estimates of the
    other parties' representations (``missing``, in party order)."""
    parts = list(missing)
    parts.insert(k, h_u_k)
    p_local = jax.nn.softmax(ref.dense_forward(aux_params, h_u_k, precision),
                             axis=-1)
    p_joint = jax.nn.softmax(ref.dense_forward(
        joint_params, jnp.concatenate(parts, axis=-1), precision), axis=-1)
    agree = jnp.argmax(p_local, -1) == jnp.argmax(p_joint, -1)
    conf = jnp.max(p_joint, -1)
    passed = agree & (jnp.max(p_local, -1) > threshold) & (conf > threshold)
    return jnp.where(passed, conf, 0.0)
