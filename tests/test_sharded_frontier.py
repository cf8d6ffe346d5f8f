"""Device-parallel frontier (DESIGN.md §14): the stacked S·C·K axis shards
over the launch mesh and must be indistinguishable from the single-device
fold:

* shard ≡ single-device parity at 1e-5 on the metric AND every client
  parameter leaf — for one-shot, few-shot, and the iterative scan fold —
  with byte-identical ledgers (logged host-side from real entries only);
* the padding rule: a stacked axis not divisible by the device count pads
  with dummy entries device-side and strips them host-side, so a 3-entry
  batch on a 2-device mesh matches the unsharded run exactly;
* mesh-keyed cache discipline: mesh identity (axis names + shape) IS part
  of every session key — the first sharded run takes one mesh-keyed miss
  per session kind, after which re-running at ANY batch width (sharded or
  single-device) adds ZERO fresh builds;
* ``device_fold`` diagnostics record the width the heavy stage actually
  folded over (mesh size on the folded paths, 1 otherwise).

This module needs >= 2 visible devices. It forces 8 host devices via
``launch.mesh.forced_host_devices`` — which only works when the jax
backend has not yet initialized, i.e. when the module runs in its own
process (the CI multi-device leg sets ``XLA_FLAGS`` instead). Inside a
full tier-1 run another module usually wins backend init first, and this
one skips.
"""
import copy

from repro.launch.mesh import forced_host_devices

forced_host_devices(8)

import jax                     # noqa: E402
import jax.numpy as jnp        # noqa: E402
import pytest                  # noqa: E402

if jax.device_count() < 2:
    pytest.skip("needs >= 2 devices (run with "
                "XLA_FLAGS=--xla_force_host_platform_device_count=8, or as "
                "its own process)", allow_module_level=True)

from repro import engine                                      # noqa: E402
from repro.core import (IterativeConfig, ProtocolConfig,      # noqa: E402
                        SSLConfig, run_few_shot, run_one_shot,
                        run_vanilla)
from repro.core.protocol import run_seeds                     # noqa: E402
from repro.data import (make_tabular_credit,                  # noqa: E402
                        make_vfl_partition)
from repro.models import make_mlp_extractor                   # noqa: E402

_FAST = ProtocolConfig(client_epochs=2, server_epochs=3)
_ITER = IterativeConfig(iterations=60, eval_every=30)
_SSL = [SSLConfig(modality="tabular")] * 2


def _ext():
    return [make_mlp_extractor(rep_dim=8, hidden=(16,)) for _ in range(2)]


def _splits(seeds, overlap=48):
    x, y = make_tabular_credit(jax.random.PRNGKey(5000), 700)
    return [make_vfl_partition(x[:, :22], y, overlap_size=overlap,
                               feature_sizes=[11, 11], seed=s)
            for s in seeds]


def _run(runner, seeds, cfg, splits=None):
    splits = _splits(seeds) if splits is None else splits
    return run_seeds(runner, [jax.random.PRNGKey(s) for s in seeds], splits,
                     [_ext() for _ in seeds], [_SSL for _ in seeds], cfg)


def _assert_parity(sharded, single):
    for a, b in zip(sharded, single):
        assert abs(float(a.metric) - float(b.metric)) < 1e-5, \
            (float(a.metric), float(b.metric))
        assert a.ledger.total_bytes() == b.ledger.total_bytes()
        assert a.ledger.comm_times() == b.ledger.comm_times()
        assert a.ledger.by_tag() == b.ledger.by_tag()
        for ca, cb in zip(a.clients, b.clients):
            for la, lb in zip(jax.tree_util.tree_leaves(ca.params),
                              jax.tree_util.tree_leaves(cb.params)):
                assert jnp.allclose(la, lb, atol=1e-5), \
                    float(jnp.max(jnp.abs(la - lb)))


@pytest.mark.parametrize("runner,cfg", [
    (run_one_shot, _FAST),
    (run_few_shot, _FAST),
    (run_vanilla, _ITER),
], ids=["one_shot", "few_shot", "vanilla"])
def test_sharded_matches_single_device(runner, cfg):
    """The tentpole parity: a 2-device mesh over S=2 seeds reproduces the
    single-device fold at 1e-5 on metric and every parameter leaf, with
    byte-identical ledgers (communication is logged host-side from the
    real entries — dummy padding rows never reach the ledger)."""
    seeds = (0, 1)
    single = _run(runner, seeds, cfg)
    import dataclasses
    sharded = _run(runner, seeds, dataclasses.replace(cfg, mesh=2))
    _assert_parity(sharded, single)
    for r in single:
        assert r.diagnostics["device_fold"] == 1
    for r in sharded:
        assert r.diagnostics["device_fold"] == 2


@pytest.mark.parametrize("devices", [2, 4],
                         ids=["pad-3-to-4", "pad-3x2-to-8"])
def test_non_divisible_batch_pads_and_strips(devices):
    """3 seeds on a 2-device mesh (stacked width 3 → padded 4) and on a
    4-device mesh (the SSL stack's S·K = 6 → padded 8): dummy entries are
    repeats of entry 0, stripped host-side, and must not perturb any real
    entry — parity holds entry by entry."""
    import dataclasses
    seeds = (0, 1, 2)
    for runner, cfg in ((run_one_shot, _FAST), (run_vanilla, _ITER)):
        single = _run(runner, seeds, cfg)
        sharded = _run(runner, seeds,
                       dataclasses.replace(cfg, mesh=devices))
        _assert_parity(sharded, single)
        for r in sharded:
            assert r.diagnostics["device_fold"] == devices


def test_mesh_keyed_cache_discipline():
    """Mesh identity is part of every session key: against a warm
    single-device cache the FIRST sharded run takes fresh mesh-keyed
    misses, after which (a) a sharded re-run at a DIFFERENT batch width
    and (b) a single-device re-run both add ZERO fresh builds — the keys
    carry the mesh but never the batch width."""
    import dataclasses
    engine.clear_session_cache()
    _run(run_one_shot, (0, 1), _FAST)
    warm = copy.deepcopy(engine.session_cache_stats_by_domain())

    sharded_cfg = dataclasses.replace(_FAST, mesh=2)
    _run(run_one_shot, (0, 1), sharded_cfg)
    first = copy.deepcopy(engine.session_cache_stats_by_domain())
    fresh = {d: first[d]["misses"] - warm.get(d, {"misses": 0})["misses"]
             for d in first}
    assert any(v > 0 for v in fresh.values()), fresh   # mesh IS in the key

    _run(run_one_shot, (0, 1, 2), sharded_cfg)         # new width, same mesh
    second = engine.session_cache_stats_by_domain()
    for d in second:
        assert second[d]["misses"] == first[d]["misses"], (d, first, second)

    _run(run_one_shot, (0, 1), _FAST)                  # single-device again
    third = engine.session_cache_stats_by_domain()
    for d in third:
        assert third[d]["misses"] == second[d]["misses"], (d, second, third)


def test_device_fold_diagnostic_pins():
    """``device_fold`` records the width the heavy stage actually folded:
    the mesh size."""
    import dataclasses
    seeds = (0, 1)
    sharded = _run(run_vanilla, seeds, dataclasses.replace(_ITER, mesh=2))
    for r in sharded:
        assert r.diagnostics["device_fold"] == 2
