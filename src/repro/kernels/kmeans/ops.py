"""Public jit'd wrappers: padding, VMEM-budget block sizing, dtype plumbing.

When does this beat the XLA reference?  The jnp oracle materializes the full
(N, C) distance matrix in HBM before the argmin; the kernel fuses distance
formation and the argmin reduction in VMEM, so it wins once N·C is large
enough that the distance matrix spills past cache — in this repo, the
one-shot step-③ shape (N_o gradient rows × C classes) with N_o ≥ ~2k.
For tiny N (few hundred rows) the launch overhead makes XLA's fused
expansion just as fast; that's why ``use_kernels`` defaults to off in
``ProtocolConfig`` and tests pin the jnp path as the numerical oracle.

The batched entry (``kmeans_assign_batched``) folds a stacked S·C·K axis
into the grid itself — ONE launch for the whole fold versus B sequential
width-1 launches or a vmap replay: one dispatch, one pad plan, one trace
instead of B of each. Measured on the bench shapes (B=8, N=2048, d=128,
C=10; CPU interpret mode, ``benchmarks/kernels_bench.py`` /
BENCH_kernels.json): the batched grid is bit-equal to the vmapped jnp
oracle, but interpret-mode wall-clock does NOT show the win — the
interpreter's per-grid-step cost dominates, so the B-grid launch times
about the same as B sequential launches (grid_vs_seq ≈ 0.7×) and the
XLA reference is ~20× faster outright. That is expected: under
interpretation Pallas is strictly overhead (the KernelRouter routes it
off everywhere on CPU). The batched grid's payoff is on TPU, where the
per-launch dispatch/pad cost it amortizes is real and the distance tile
never leaves VMEM; the roofline note above governs when to flip
``use_kernels``.

VMEM budget per grid instance (f32), mirroring kmeans/kernel.py — the
leading batch axis has block width 1 and adds NOTHING per instance, so
block sizing is batch-independent. The pipeline double-buffers both input
tiles:

  tile              shape        bytes (BN=256, d=4096, C=1024 worst case)
  x row-tile        (BN, d)      256·4096·4 ≈ 4.2 MB   (×2 buffers)
  centers           (C,  d)      1024·4096·4 ≈ 16.8 MB (×2 buffers)
  distance tile     (BN, C)      256·1024·4 ≈ 1.0 MB

``_pick_block_n`` takes the largest BN in (512, 256, 128) whose working set
fits ``_VMEM_BUDGET`` (12 MB, headroom under the 16 MB default scoped VMEM
limit of TPU v5e) and never a block wider than the padded row count. When
even BN=128 does not fit — the worst case above — the plan raises the
kernel's scoped VMEM limit to the working set plus headroom instead (v5e
has 128 MiB of VMEM per core). BN stays a multiple of 128 because it is
the lane dim of the output block; d is padded to 128 lanes, C to 8
sublanes.

Precision: the kernel's dot and the jnp oracles (``ref.py``,
``core/clustering.py``) all run at ``Precision.HIGHEST``. At a TPU's
default precision an f32 matmul — XLA's and the Pallas kernel's alike — is
one bf16 pass: on a v5e both sides then flipped 64 of 32768 assignments of
unit rows against a float64 reference, and the two disagreed with each
other wherever their roundings differed. At HIGHEST both match float64, so
the kernel stays bit-equal to the oracle on the chip as in interpret mode.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels import interpret_mode
from repro.kernels.kmeans.kernel import (kmeans_assign_batched_padded,
                                         kmeans_assign_padded)

_LANE = 128     # MXU/VREG lane width
_SUBLANE = 8
_VMEM_BUDGET = 12 * 2**20   # headroom under the 16 MB default scoped limit

assert kmeans_assign_padded is not None  # width-1 entry, re-exported


def _round_up(v: int, m: int) -> int:
    return (v + m - 1) // m * m


def _vmem_bytes(bn: int, d_pad: int, c_pad: int) -> int:
    """Working set of one grid instance: double-buffered x/centers tiles,
    the distance tile and the output row, f32."""
    return 4 * (2 * (bn * d_pad + c_pad * d_pad) + bn * c_pad + 2 * bn)


def _pick_block_n(n: int, d_pad: int, c_pad: int) -> int:
    cap = _round_up(max(n, 1), _LANE)
    for bn in (512, 256, 128):
        if bn <= cap and _vmem_bytes(bn, d_pad, c_pad) <= _VMEM_BUDGET:
            return bn
    return _LANE


def _pad_plan(n: int, d: int, c: int):
    """(n_pad, d_pad, c_pad, block_n, vmem_limit) for one assignment call;
    ``vmem_limit`` is None unless the working set needs more than the
    default scoped VMEM."""
    d_pad = _round_up(max(d, _LANE), _LANE)
    c_pad = _round_up(max(c, _SUBLANE), _SUBLANE)
    bn = _pick_block_n(n, d_pad, c_pad)
    n_pad = _round_up(max(n, bn), bn)
    need = _vmem_bytes(bn, d_pad, c_pad)
    vmem_limit = (None if need <= _VMEM_BUDGET
                  else _round_up(need + need // 4, 2**20))
    return n_pad, d_pad, c_pad, bn, vmem_limit


def kmeans_assign_batched(x: jnp.ndarray, centers: jnp.ndarray) -> jnp.ndarray:
    """argmin_c ‖x_{b,i} − μ_{b,c}‖² per batch entry, ONE (B, N/BN) grid.

    x (B, N, d), centers (B, C, d) → (B, N) int32. Any N, d, C; the batch
    axis is the stacked fold axis (seeds × scenarios × parties upstream)."""
    b, n, d = x.shape
    c = centers.shape[1]
    n_pad, d_pad, c_pad, bn, vmem_limit = _pad_plan(n, d, c)

    xp = jnp.zeros((b, n_pad, d_pad), jnp.float32
                   ).at[:, :n, :d].set(x.astype(jnp.float32))
    # Sentinel rows: huge coordinates → huge distance → never the argmin.
    cp = jnp.zeros((b, c_pad, d_pad), jnp.float32
                   ).at[:, :c, :d].set(centers.astype(jnp.float32))
    if c_pad > c:
        cp = cp.at[:, c:, 0].set(3e18)

    out = kmeans_assign_batched_padded(xp, cp, block_n=bn,
                                       vmem_limit=vmem_limit,
                                       interpret=interpret_mode())
    return out[:, :n]


def kmeans_assign(x: jnp.ndarray, centers: jnp.ndarray) -> jnp.ndarray:
    """argmin_c ‖x_i − μ_c‖² via the Pallas kernel. Any N, d, C.

    The width-1 case of :func:`kmeans_assign_batched` — same padding plan,
    same grid program."""
    return kmeans_assign_batched(x[None], centers[None])[0]
