"""Public wrappers: padding, block sizing, the √d scale from the TRUE dim.

When does this beat the XLA reference?  The jnp oracle materializes the
(N_u, N_o) score matrix plus its softmax in HBM; the flash-style kernel
keeps score tiles in VMEM with an online-softmax recurrence, so it wins in
the few-shot regime the paper targets — N_u ≫ N_o (every client's private
pool attending over the overlap set), where the score matrix is the
dominant HBM traffic.  With both N_u and N_o small (≲1k) XLA's fusion is
already roofline-bound on the matmuls and the kernel only breaks even.

The batched entry (``sdpa_estimate_batched``) folds a stacked seed axis (or
a served partial-party batch) into the grid itself — ONE
(B, N_u/BU, N_o/BO) launch versus B sequential launches: one dispatch, one
padding plan, one trace instead of B of each. Measured on the bench shapes
(B=8, N_u=4096, N_o=256, d=128; CPU interpret mode,
``benchmarks/kernels_bench.py`` / BENCH_kernels.json): the batched grid
matches the vmapped jnp oracle to ≤1e-5 (maxerr ~1e-6), but — as with
kmeans — interpret-mode wall-clock does NOT show the win: the
interpreter's per-grid-step cost dominates, B sequential launches time
about the same as the one B-grid launch (grid_vs_seq ≈ 0.5×), and the
vmapped XLA reference is ~3× faster outright. Under interpretation Pallas
is strictly overhead (the KernelRouter routes it off on CPU); the batched
grid's payoff is on TPU, where the amortized dispatch/pad cost is real and
the (N_u, N_o) score tile never touches HBM. ``KernelRouter`` in
``launch/vfl_serve.py`` encodes the B·N_u·N_o roofline rule.

VMEM budget per grid instance (f32) — the leading batch axis has block
width 1, so per-instance VMEM is identical to the unbatched grid and
``_pick_blocks`` is batch-independent:

  tile              shape        purpose
  q row-tile        (BU, d)      H_u block (pre-scaled by 1/√d_true)
  k tile            (BO, d)      H_o^A block (sequential reduction axis)
  v tile            (BO, d_b)    H_o^B block
  acc / out         (BU, d_b)    online-softmax accumulator + output
  m, l scratch      (BU, 128)    running max / normalizer lanes
  score tile        (BU, BO)     lives only in VREGs/VMEM, never HBM

``_pick_blocks`` shrinks BU=BO from 512 down until the sum fits the 12 MB
``_VMEM_BUDGET`` (headroom under ~16 MB/core). Blocks are MXU-aligned
multiples of (8, 128); d and d_b are padded to 128 lanes.

Precision: both kernel dots and the jnp oracles (``ref.py``,
``core/estimator.py``) run at ``Precision.HIGHEST``. At a TPU's default
precision each f32 matmul is one bf16 pass: on a v5e the kernel was then
8e-3 and the oracle 1e-2 off a float64 reference, and 5e-3 apart. At
HIGHEST both are within ~1.3e-6 of float64, and the 1e-5 parity holds on
the chip as in interpret mode.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels import interpret_mode
from repro.kernels.sdpa_estimator.kernel import (sdpa_estimate_batched_padded,
                                                 sdpa_estimate_padded)

_LANE = 128
_VMEM_BUDGET = 12 * 2**20

assert sdpa_estimate_padded is not None  # width-1 entry, re-exported


def _round_up(v: int, m: int) -> int:
    return (v + m - 1) // m * m


def _pick_blocks(d_pad: int, db_pad: int):
    for b in (512, 256, 128, 64, 32, 16, 8):
        # q + k + v + acc + m/l + out tiles, f32
        vmem = 4 * (b * d_pad + b * d_pad + b * db_pad + b * db_pad
                    + 2 * b * 128 + b * db_pad + b * b)
        if vmem <= _VMEM_BUDGET:
            return b, b
    return 8, 8


def sdpa_estimate_batched(h_u: jnp.ndarray, h_o_a: jnp.ndarray,
                          h_o_b: jnp.ndarray) -> jnp.ndarray:
    """Eq. 10 per batch entry as ONE batched grid launch.

    h_u (B, N_u, d), h_o_a (B, N_o, d), h_o_b (B, N_o, d_b) →
    (B, N_u, d_b) f32. Any shapes; all entries share one padding plan (they
    already share shapes — the batch axis is a stacked fold axis)."""
    b, nu, d = h_u.shape
    _, no, d2 = h_o_a.shape
    assert d == d2, (d, d2)
    db = h_o_b.shape[2]
    assert h_o_b.shape[1] == no

    d_pad = _round_up(max(d, _LANE), _LANE)
    db_pad = _round_up(max(db, _LANE), _LANE)
    bu, bo = _pick_blocks(d_pad, db_pad)
    nu_pad = _round_up(max(nu, bu), bu)
    no_pad = _round_up(max(no, bo), bo)

    scale = 1.0 / (d ** 0.5)   # √d of the TRUE dim, not the padded one
    qp = jnp.zeros((b, nu_pad, d_pad), jnp.float32).at[:, :nu, :d].set(
        h_u.astype(jnp.float32) * scale)
    kp = jnp.zeros((b, no_pad, d_pad), jnp.float32
                   ).at[:, :no, :d].set(h_o_a.astype(jnp.float32))
    vp = jnp.zeros((b, no_pad, db_pad), jnp.float32
                   ).at[:, :no, :db].set(h_o_b.astype(jnp.float32))

    out = sdpa_estimate_batched_padded(qp, kp, vp, no_valid=no,
                                       block_u=bu, block_o=bo,
                                       interpret=interpret_mode())
    return out[:, :nu, :db]


def sdpa_estimate(h_u: jnp.ndarray, h_o_a: jnp.ndarray, h_o_b: jnp.ndarray
                  ) -> jnp.ndarray:
    """Eq. 10 via the Pallas kernel. Any shapes; returns (N_u, d_b) f32.

    The width-1 case of :func:`sdpa_estimate_batched` — same padding plan,
    same grid program."""
    return sdpa_estimate_batched(h_u[None], h_o_a[None], h_o_b[None])[0]
