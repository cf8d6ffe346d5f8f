"""Compile the protocol's Pallas kernels for a TPU v5e chip, with no chip.

The TPU compiler ships with libtpu, so ``jax.jit(...).lower(...).compile()``
against a *described* ``v5e:2x2`` topology raises whatever the chip's
compiler would: block shapes the Mosaic lowering refuses, VMEM overruns.
Interpret mode (every other kernel test) cannot see either. Each case
asserts the compiled program really contains the kernel
(``tpu_custom_call``).

The topology is described inside a fixture, never at import: only one
process may load libtpu at a time, and a test worker that loads it at
collection would change which tests the other workers collect. All cases
stay in this one file for the same reason.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.kmeans import ops as km_ops
from repro.kernels.sdpa_estimator import ops as sdpa_ops


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    with pytest.MonkeyPatch.context() as mp:
        # libtpu would otherwise write its logs under the temp directory
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
        except Exception as e:  # no libtpu, or it cannot describe the chip
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compiled_kernels(monkeypatch):
    """Force the Mosaic-compiled route (this host's backend is a CPU)."""
    monkeypatch.setattr(km_ops, "interpret_mode", lambda: False)
    monkeypatch.setattr(sdpa_ops, "interpret_mode", lambda: False)


def _spec(shape, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)


@pytest.mark.parametrize(
    "b,n,d,c",
    [
        (1, 100, 32, 10),  # width 1
        (8, 64, 16, 2),  # the smoke run's step-3 fold: S·C·K = 8, 64-row capacity
        (3, 1000, 130, 7),  # ragged N, d past one lane tile
        (2, 512, 4096, 1024),  # the VMEM worst case of kmeans/ops.py
    ],
    ids=["width1", "step3-fold", "ragged", "vmem-worst"],
)
def test_kmeans_assign_batched_compiles_for_v5e(one_chip, compiled_kernels, b, n, d, c):
    compiled = (
        jax.jit(km_ops.kmeans_assign_batched)
        .lower(_spec((b, n, d), one_chip), _spec((b, c, d), one_chip))
        .compile()
    )
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize(
    "b,nu,no,d,db",
    [
        (4, 1168, 64, 16, 16),  # few-shot ③' fold: S·C = 4, one party's private pool
        (1, 16384, 64, 16, 16),  # serving partial-party query, B = K−1 = 1
    ],
    ids=["fewshot-fold", "serving"],
)
def test_sdpa_estimate_batched_compiles_for_v5e(one_chip, compiled_kernels, b, nu, no, d, db):
    compiled = (
        jax.jit(sdpa_ops.sdpa_estimate_batched)
        .lower(
            _spec((b, nu, d), one_chip),
            _spec((b, no, d), one_chip),
            _spec((b, no, db), one_chip),
        )
        .compile()
    )
    assert "tpu_custom_call" in compiled.as_text()
