"""Pallas TPU kernel: flash-style blocked SDPA representation estimation.

The few-shot server evaluates Ĥ_u = softmax(H_u H_oᵀ/√d) H_o^B with
N_u ≫ N_o (every client's full private pool attends over the overlap set).
Materializing the (N_u, N_o) score matrix in HBM is the naive cost; the
kernel streams key/value blocks through VMEM with an online softmax so the
score tile only ever lives in VREGs/VMEM — the standard FlashAttention
recurrence adapted to this asymmetric (cross-attention, no causality, no
multi-head) shape.

Batch is a NATIVE leading grid dimension (DESIGN.md §15): the batched entry
runs a ``(B, N_u/BU, N_o/BO)`` grid. TPU grids iterate row-major with the
LAST axis fastest, so for every fixed (b, i) the o-axis programs
``j = 0 … nj−1`` still run back-to-back — the m/l/acc scratch recurrence
(init at ``j == 0``, write-out at ``j == nj−1``) is untouched by the extra
leading axis. One launch estimates a whole stacked seed fold (or a served
partial-party batch) instead of B sequential launches. The single-entry
grid is literally the ``B = 1`` case.

Grid: (B, N_u/BU, N_o/BO); b and the u-axis are parallel, the o-axis is a
sequential reduction carried in VMEM scratch (m, l, acc). The batch block
width is 1, so per-instance VMEM is identical to the unbatched grid. Block
shapes are MXU-aligned multiples of (8, 128); ops.py pads inputs and picks
BU/BO under the VMEM budget.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30


def _sdpa_kernel(no_valid: int,
                 q_ref, k_ref, v_ref, o_ref,
                 m_ref, l_ref, acc_ref):
    """q is pre-scaled by 1/√d in ops.py (python-float closure constants are
    rejected by pallas_call, and pre-scaling saves a VPU pass anyway)."""
    j = pl.program_id(2)
    nj = pl.num_programs(2)
    bo = k_ref.shape[1]

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)                    # (BU, d)
    k = k_ref[0].astype(jnp.float32)                    # (BO, d)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            precision=jax.lax.Precision.HIGHEST,
                            preferred_element_type=jnp.float32)  # (BU, BO)
    col = j * bo + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(col < no_valid, s, _NEG_INF)

    m_prev = m_ref[..., :1]                             # (BU, 1)
    l_prev = l_ref[..., :1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)                              # (BU, BO)
    alpha = jnp.exp(m_prev - m_new)                     # (BU, 1)
    l_new = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
    pv = jax.lax.dot_general(p, v_ref[0].astype(jnp.float32),
                             (((1,), (0,)), ((), ())),
                             precision=jax.lax.Precision.HIGHEST,
                             preferred_element_type=jnp.float32)  # (BU, db)
    acc_ref[...] = acc_ref[...] * alpha + pv
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(j == nj - 1)
    def _finish():
        o_ref[0, ...] = (acc_ref[...] / l_ref[..., :1]).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("no_valid", "block_u", "block_o", "interpret"))
def sdpa_estimate_batched_padded(h_u: jnp.ndarray, h_o_a: jnp.ndarray,
                                 h_o_b: jnp.ndarray, no_valid: int,
                                 block_u: int = 256, block_o: int = 256,
                                 interpret: bool = False) -> jnp.ndarray:
    """h_u (B, N_u, d), h_o_a (B, N_o, d), h_o_b (B, N_o, d_b) → (B, N_u, d_b).

    h_u must already be scaled by 1/√d_true; all B entries share one
    ``no_valid`` (ops.py pads every entry to a common plan)."""
    b, nu, d = h_u.shape
    _, no, db = h_o_b.shape
    assert nu % block_u == 0 and no % block_o == 0
    grid = (b, nu // block_u, no // block_o)
    kernel = functools.partial(_sdpa_kernel, no_valid)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_u, d), lambda bi, i, j: (bi, i, 0)),
            pl.BlockSpec((1, block_o, d), lambda bi, i, j: (bi, j, 0)),
            pl.BlockSpec((1, block_o, db), lambda bi, i, j: (bi, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_u, db), lambda bi, i, j: (bi, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, nu, db), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((block_u, 128), jnp.float32),   # m
            pltpu.VMEM((block_u, 128), jnp.float32),   # l
            pltpu.VMEM((block_u, db), jnp.float32),    # acc
        ],
        interpret=interpret,
    )(h_u, h_o_a, h_o_b)


@functools.partial(jax.jit,
                   static_argnames=("no_valid", "block_u", "block_o", "interpret"))
def sdpa_estimate_padded(h_u: jnp.ndarray, h_o_a: jnp.ndarray, h_o_b: jnp.ndarray,
                         no_valid: int,
                         block_u: int = 256, block_o: int = 256,
                         interpret: bool = False) -> jnp.ndarray:
    """The width-1 case of the batched grid. h_u pre-scaled by 1/√d_true."""
    return sdpa_estimate_batched_padded(
        h_u[None], h_o_a[None], h_o_b[None], no_valid=no_valid,
        block_u=block_u, block_o=block_o, interpret=interpret)[0]
