"""Pallas TPU kernels for the paper's compute hot-spots.

* ``kmeans``          — pairwise-distance + argmin assignment (step ③).
* ``sdpa_estimator``  — flash-style blocked SDPA representation estimation
                        (Eq. 10, the few-shot server hot-spot: N_u ≫ N_o).
* ``decode_attention`` — GQA flash-decode for the serving stack of the
                        assigned architectures.
* ``rmsnorm``         — fused RMSNorm (two per layer in every assigned
                        arch; memory-bound floor of 1R+1W per element).

Each kernel directory has kernel.py (pl.pallas_call + BlockSpec), ops.py
(jit'd public wrapper with padding/dtype plumbing) and ref.py (pure-jnp
oracle used by the tests' assert_allclose sweeps).

Kernels run in interpret mode exactly when no TPU is present; on a TPU
they always compile natively, so a chip run can never interpret by
accident.
"""
import jax


def interpret_mode() -> bool:
    """True iff the default backend is not a TPU (CPU tests, rehearsals)."""
    return jax.default_backend() != "tpu"
