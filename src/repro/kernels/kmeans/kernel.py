"""Pallas TPU kernel: blocked pairwise-distance + argmin cluster assignment.

Tiling: the grid walks row-blocks of x; each program instance loads an
(BN, d) tile of points and the full (C, d) center matrix into VMEM (C is the
class count — ≤ a few hundred — so centers fit), forms the distance
tile with one MXU matmul (‖x‖² − 2·x·μᵀ + ‖μ‖²) and reduces the argmin across
the padded C lanes in VREGs.

Batch is a NATIVE leading grid dimension (DESIGN.md §15): the batched entry
runs a ``(B, N/BN)`` grid in which program ``(b, i)`` assigns row-block ``i``
of batch entry ``b`` against that entry's own center matrix — one launch for
a whole stacked S·C·K fold instead of B sequential launches or a ``vmap``
replay of the single-entry program. The single-entry grid is literally the
``B = 1`` case.

VMEM budget per instance (f32): BN·d + C·d + BN·C floats — the leading batch
axis contributes nothing per program (its block width is 1).
With BN=256, d≤4096, C≤1024: 256·4096·4 + 1024·4096·4 + 256·1024·4 ≈ 21.3 MB
worst case — ops.py clamps BN down when d·C is large so the working set stays
within the default scoped VMEM limit (and raises that limit when even the
smallest block does not fit). Alignment: BN a multiple of 128, d padded to
a multiple of 128 and C to a multiple of 8 by ops.py.

The assignments are written as a ``(B, 1, N)`` array in ``(1, 1, BN)``
blocks: the TPU lowering wants the last two block dims divisible by
(8, 128) or equal to the array's, which a ``(1, BN)`` block of a ``(B, N)``
array breaks for every B ≥ 2.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kmeans_assign_kernel(x_ref, c_ref, out_ref):
    x = x_ref[0].astype(jnp.float32)            # (BN, d)
    cen = c_ref[0].astype(jnp.float32)          # (C, d)
    x2 = jnp.sum(x * x, axis=1, keepdims=True)                   # (BN, 1)
    c2 = jnp.sum(cen * cen, axis=1)[None, :]                     # (1, C)
    # MXU: (BN, d) @ (d, C), f32-exact (see ops.py on precision)
    dots = jax.lax.dot_general(x, cen, (((1,), (1,)), ((), ())),
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)
    dist = x2 - 2.0 * dots + c2                                  # (BN, C)
    out_ref[0, 0, :] = jnp.argmin(dist, axis=1).astype(jnp.int32)


@functools.partial(jax.jit,
                   static_argnames=("block_n", "vmem_limit", "interpret"))
def kmeans_assign_batched_padded(x: jnp.ndarray, centers: jnp.ndarray,
                                 block_n: int = 256,
                                 vmem_limit: Optional[int] = None,
                                 interpret: bool = False) -> jnp.ndarray:
    """x (B, N, d), centers (B, C, d) → (B, N) int32; N % block_n == 0,
    d/C already padded. ``vmem_limit`` (bytes) overrides the compiler's
    default scoped VMEM limit; ``None`` keeps the default.

    Padded center rows must be filled with +inf-distance sentinels by ops.py
    (i.e. rows of large magnitude) so they never win the argmin.
    """
    b, n, d = x.shape
    _, c, _ = centers.shape
    assert n % block_n == 0, (n, block_n)
    grid = (b, n // block_n)
    params = (None if vmem_limit is None
              else pltpu.CompilerParams(vmem_limit_bytes=vmem_limit))
    out = pl.pallas_call(
        _kmeans_assign_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_n, d), lambda bi, i: (bi, i, 0)),
            pl.BlockSpec((1, c, d), lambda bi, i: (bi, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_n), lambda bi, i: (bi, 0, i)),
        out_shape=jax.ShapeDtypeStruct((b, 1, n), jnp.int32),
        compiler_params=params,
        interpret=interpret,
    )(x, centers)
    return out[:, 0, :]


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def kmeans_assign_padded(x: jnp.ndarray, centers: jnp.ndarray,
                         block_n: int = 256, interpret: bool = False) -> jnp.ndarray:
    """x (N, d), centers (C, d); the width-1 case of the batched grid."""
    return kmeans_assign_batched_padded(x[None], centers[None],
                                        block_n=block_n,
                                        interpret=interpret)[0]
