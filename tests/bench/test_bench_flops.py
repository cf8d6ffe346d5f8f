"""The benchmark's operation counts and peaks table (bench/flops.py,
bench/peaks.json): hand counts at small shapes, XLA's own cost analysis of
the program's extractors, and refusal of an unknown device."""
import os
import sys

import jax
import jax.numpy as jnp
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))

from bench import flops  # noqa: E402
from repro.models.extractors import (make_classifier,  # noqa: E402
                                     make_cnn_extractor, make_mlp_extractor)


def _xla_flops(model, x):
    p = model.init(jax.random.PRNGKey(0), x)
    ca = jax.jit(model.apply).lower(p, x).cost_analysis()
    return (ca[0] if isinstance(ca, list) else ca)["flops"]


def test_cnn_hand_count():
    # 4x4x2 input, widths (3, 5), one block per stage, rep 7. Along an axis
    # of 4 a stride-1 3x3 SAME conv reads 10 real taps, a stride-2 one 5, a
    # stride-2 1x1 one 2; along an axis of 2 a stride-1 3x3 conv reads 4.
    stem = 2 * 10 * 10 * 2 * 3
    block0 = 2 * (2 * 10 * 10 * 3 * 3)
    block1 = 2 * 5 * 5 * 3 * 5 + 2 * 2 * 2 * 3 * 5 + 2 * 4 * 4 * 5 * 5
    head = 2 * 5 * 7
    assert flops.cnn_forward(4, 4, 2, (3, 5), 1, 7) == (stem + block0 + block1
                                                         + head) == 6540


def test_dense_hand_count():
    assert flops.dense_forward([13, 64, 16]) == 2 * (13 * 64 + 64 * 16)


@pytest.mark.parametrize("widths, blocks, hw, lo", [
    ((16, 32, 64), 1, (16, 8), 0.95),
    ((32, 64, 128), 4, (32, 16), 0.98),     # WRN-28-2 on a CIFAR half
])
def test_cnn_against_xla_cost_analysis(widths, blocks, hw, lo):
    """XLA also counts GroupNorm and ReLU, which model FLOPs leave out."""
    model = make_cnn_extractor(rep_dim=128, widths=widths,
                               blocks_per_stage=blocks)
    x = jnp.zeros((4,) + hw + (3,))
    mine = 4 * flops.cnn_forward(hw[0], hw[1], 3, widths, blocks, 128)
    xla = _xla_flops(model, x)
    assert lo * xla <= mine <= xla


def test_mlp_and_head_against_xla_cost_analysis():
    x = jnp.zeros((8, 13))
    xla = _xla_flops(make_mlp_extractor(rep_dim=16, hidden=(64,)), x)
    mine = 8 * flops.dense_forward([13, 64, 16])
    assert mine <= xla <= mine + 8 * (64 + 64 + 16)   # + bias adds, ReLU
    xla = _xla_flops(make_classifier(10), jnp.zeros((8, 256)))
    assert 8 * flops.dense_forward([256, 10]) <= xla


def test_kernel_work_counts():
    ops, nbytes = flops.sdpa(2, 100, 50, 16, 8)
    assert ops == 2 * 2 * 100 * 50 * (16 + 8)
    assert nbytes == 2 * 4 * (100 * 16 + 50 * 16 + 50 * 8 + 100 * 8)
    ops, nbytes = flops.kmeans_assign(3, 512, 10, 128)
    assert ops == 3 * 2 * 512 * 10 * 128
    assert nbytes == 3 * 4 * (512 * 128 + 10 * 128 + 512)


def test_protocol_call_counts_every_phase():
    cfg = {"scenario": {"modality": "tabular", "num_samples": 30000,
                        "overlap": 512, "num_parties": 2,
                        "feature_sizes": [10, 13], "hidden": [64],
                        "rep_dim": 16, "gen_params": {"num_classes": 2}},
           "protocol": {"client_epochs": 8, "server_epochs": 30,
                        "batch_size": 32},
           "test_fraction": 0.2}
    one = flops.protocol_call(cfg, "one_shot", 1)
    few = flops.protocol_call(cfg, "few_shot", 8)
    assert set(one) < set(few)
    assert few["ssl"] > 8 * one["ssl"]          # 5' trains on N_o + N_u rows
    assert flops.split_sizes(cfg) == {"test": 6000, "overlap": 512,
                                      "pool": 11744, "parties": 2}


def test_peaks_known_and_unknown_devices():
    v5e = flops.peaks("TPU v5 lite")
    assert v5e["flops_per_s"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        flops.peaks("cpu")
