"""The serving subsystem's contracts (ISSUE 7, DESIGN.md §13).

* artifact save/load round-trips parameters AND apply identity;
* the fused batched forward matches the unbatched reference at 1e-5,
  through padding, chunking, and heterogeneous party zoos;
* the fused program is session-cached under a width-free key: serving at
  new batch shapes adds ZERO fresh "serving"-domain misses;
* the runner registry is the ONLY dispatch surface (`_batched_impls`
  deleted) and still rejects per-seed state kwargs;
* the typed row builders validate shape and feed both gates.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import (ExtractorSpec, TrainedVFLModel, load_artifact,
                              save_artifact)
from repro.checkpoint.artifact import from_state
from repro.core import rows as result_rows
from repro.core import runners as runner_registry
from repro.core.protocol import ProtocolConfig, run_one_shot, run_seeds
from repro.data import make_tabular_credit, make_vfl_partition
from repro.engine import session_cache_stats
from repro.engine.local_ssl import PartyParams
from repro.launch import batching
from repro.launch.vfl_serve import KernelRouter, ServingEngine
from repro.models.extractors import make_classifier, make_mlp_extractor

_FAST = ProtocolConfig(client_epochs=2, server_epochs=3)


# ---------------------------------------------------------------- fixtures
def _split(seed=0):
    x, y = make_tabular_credit(jax.random.PRNGKey(1000 + seed), 700)
    return make_vfl_partition(x[:, :22], y, overlap_size=64,
                              feature_sizes=[11, 11], seed=seed)


def _mk_artifact(seed=0, with_split=True):
    """Train one fast one-shot run on a synthetic homogeneous scenario and
    export it through the real scenario registry."""
    from repro import scenarios

    spec = scenarios.get("hard/overlap-32")
    bundle = scenarios.build(spec, seed=seed, smoke=True)
    res = run_one_shot(jax.random.PRNGKey(seed), bundle.split,
                       bundle.extractors, bundle.ssl_cfgs, _FAST)
    art = res.to_artifact(spec, cfg=_FAST,
                          split=bundle.split if with_split else None)
    return art, bundle


@pytest.fixture(scope="module")
def trained():
    return _mk_artifact(seed=0)


# ----------------------------------------------------------- artifact layer
def test_artifact_roundtrip_parity(trained, tmp_path):
    art, bundle = trained
    save_artifact(str(tmp_path / "art"), art)
    art2 = load_artifact(str(tmp_path / "art"))
    assert art2.scenario == art.scenario
    assert art2.extractor_specs == art.extractor_specs
    assert art2.feature_shapes == art.feature_shapes
    assert art2.version == art.version
    assert art2.protocol_config().client_epochs == _FAST.client_epochs
    xs = [x[:9] for x in bundle.split.aligned]
    np.testing.assert_allclose(np.asarray(art.predict_logits(xs)),
                               np.asarray(art2.predict_logits(xs)),
                               atol=1e-6)
    # overlap reps (Eq. 10 keys/values) survive the round trip
    for a, b in zip(art.overlap_reps, art2.overlap_reps):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


def test_artifact_matches_training_server_forward(trained):
    """to_artifact must export EXACTLY the trained forward: the artifact's
    reference logits equal the live server's on the aligned rows."""
    art, bundle = trained
    from repro.core.protocol import run_one_shot as _  # noqa: F401

    # recompute through the live objects
    res = run_one_shot(jax.random.PRNGKey(0), bundle.split,
                       bundle.extractors, bundle.ssl_cfgs, _FAST)
    xs = [x[:16] for x in bundle.split.aligned]
    reps = [c.extract(x) for c, x in zip(res.clients, xs)]
    live = res.server.predict_logits(reps)
    np.testing.assert_allclose(np.asarray(art.predict_logits(xs)),
                               np.asarray(live), atol=1e-5)


def test_artifact_version_gate(tmp_path):
    art, _ = _mk_artifact(seed=1, with_split=True)
    d = str(tmp_path / "art")
    save_artifact(d, art)
    import json
    import numpy as onp

    # forge a future-version artifact: the loader must refuse, not guess
    path = d + "/ckpt_00000000.npz"
    blob = dict(onp.load(path))
    meta = json.loads(bytes(blob["__meta__"]).decode())
    meta["artifact_version"] = 99
    blob["__meta__"] = onp.frombuffer(json.dumps(meta).encode(),
                                      dtype=onp.uint8)
    onp.savez(path, **blob)
    with pytest.raises(ValueError, match="newer than supported"):
        load_artifact(d)


def test_from_state_without_split_recovers_mlp_shapes(trained):
    art, bundle = trained
    res = run_one_shot(jax.random.PRNGKey(0), bundle.split,
                       bundle.extractors, bundle.ssl_cfgs, _FAST)
    from repro import scenarios

    art2 = from_state(res.clients, res.server,
                      scenarios.get("hard/overlap-32"), cfg=_FAST)
    assert art2.feature_shapes == art.feature_shapes
    assert art2.overlap_reps is None


# ------------------------------------------------------------ fused forward
def test_batched_matches_sequential_1e5(trained, tmp_path):
    """The acceptance bar: batched predictions from a LOADED artifact match
    the unbatched reference forward at 1e-5 — across chunking and
    padding."""
    art, bundle = trained
    save_artifact(str(tmp_path / "art"), art)
    engine = ServingEngine(load_artifact(str(tmp_path / "art")), capacity=8)
    xs = [x[:21] for x in bundle.split.aligned]      # 3 chunks, last ragged
    fused = engine.predict_logits(xs)
    # sequential: one row at a time through the unbatched oracle
    rows = [art.predict_logits([x[i:i + 1] for x in xs])
            for i in range(21)]
    np.testing.assert_allclose(np.asarray(fused),
                               np.asarray(jnp.concatenate(rows, axis=0)),
                               atol=1e-5)


def test_fused_uses_vmap_party_fold_when_homogeneous(trained):
    art, _ = trained
    assert art.parties_are_homogeneous
    engine = ServingEngine(art, capacity=4)
    assert jax.tree_util.tree_structure(engine._ext_params) \
        == jax.tree_util.tree_structure(art.client_params[0].extractor)
    stacked_leaf = jax.tree_util.tree_leaves(engine._ext_params)[0]
    assert stacked_leaf.shape[0] == art.num_parties


def test_heterogeneous_parties_compose_and_match(tmp_path):
    """Unequal per-party feature widths force the composition path; parity
    must hold there too."""
    split = _split(seed=2)
    # two different MLP architectures ⇒ not homogeneous
    exts = [make_mlp_extractor(rep_dim=8, hidden=(16,)),
            make_mlp_extractor(rep_dim=8, hidden=(12, 12))]
    specs = (ExtractorSpec(kind="mlp", rep_dim=8, hidden=(16,)),
             ExtractorSpec(kind="mlp", rep_dim=8, hidden=(12, 12)))
    key = jax.random.PRNGKey(0)
    client_params = []
    for e, x in zip(exts, split.aligned):
        p = e.init(key, x[:2])
        head = make_classifier(2).init(key, e.apply(p, x[:1]))
        client_params.append(PartyParams(p, head))
    clf = make_classifier(2)
    server_params = clf.init(
        key, jnp.zeros((1, sum(e.rep_dim for e in exts))))
    art = TrainedVFLModel(
        scenario="synthetic/hetero", num_classes=2,
        feature_shapes=tuple(tuple(x.shape[1:]) for x in split.aligned),
        extractor_specs=specs, client_params=client_params,
        server_params=server_params)
    assert not art.parties_are_homogeneous
    d = str(tmp_path / "het")
    save_artifact(d, art)
    art2 = load_artifact(d)
    engine = ServingEngine(art2, capacity=8)
    xs = [x[:13] for x in split.aligned]
    np.testing.assert_allclose(np.asarray(engine.predict_logits(xs)),
                               np.asarray(art.predict_logits(xs)),
                               atol=1e-5)


def test_zero_fresh_serving_misses_after_first_shape(trained):
    """The recompile-regression contract: ONE serving-session build per
    deployed model — new capacities, new engines, new batch sizes all
    re-serve it."""
    art, bundle = trained
    xs = [x[:3] for x in bundle.split.aligned]
    ServingEngine(art, capacity=4).predict_logits(xs)     # first shape
    misses0 = session_cache_stats("serving")["misses"]
    for capacity in (1, 16, 64):
        engine = ServingEngine(art, capacity=capacity)
        engine.predict_logits([x[:capacity] for x in bundle.split.aligned])
    assert session_cache_stats("serving")["misses"] == misses0
    assert session_cache_stats("serving")["hits"] >= 3


def test_partial_party_queries_serve_via_estimation(trained):
    art, bundle = trained
    engine = ServingEngine(art, capacity=8)
    logits = engine.predict_logits_partial(bundle.split.aligned[0][:6], 0)
    assert logits.shape == (6, art.num_classes)
    art_bare = dataclasses.replace(art)
    art_bare.overlap_reps = None
    with pytest.raises(ValueError, match="overlap_reps"):
        ServingEngine(art_bare, capacity=8).predict_logits_partial(
            bundle.split.aligned[0][:6], 0)


def test_kernel_router_roofline_rules():
    cpu = KernelRouter(backend="cpu", interpret=True)
    assert not cpu.use_sdpa(1 << 20, 1 << 10, 64)      # never under interpret
    assert not cpu.use_rmsnorm(4096, 4096)
    tpu = KernelRouter(backend="tpu", interpret=False)
    assert tpu.use_sdpa(1 << 12, 1 << 10, 64)          # 16 MB score matrix
    assert not tpu.use_sdpa(64, 32, 64)                # XLA fuses small
    # the batched-grid width scales the roofline: one slice of a K−1-wide
    # partial-party launch sits under the crossover, the whole launch is
    # the real score volume and clears it
    assert not tpu.use_sdpa(1 << 10, 1 << 9, 64)             # 2 MB slice
    assert tpu.use_sdpa(1 << 10, 1 << 9, 64, batch=3)        # 6 MB launch
    assert not cpu.use_sdpa(1 << 10, 1 << 9, 64, batch=64)   # interpret: never
    assert tpu.use_rmsnorm(2048, 4096)                 # ops.py's own example
    assert not tpu.use_rmsnorm(8, 128)
    assert tpu.use_decode_attention(8192)
    assert not tpu.use_decode_attention(512)


# ----------------------------------------------------------------- batcher
def test_masked_batcher_pads_and_masks():
    xs = (jnp.ones((3, 5)), jnp.ones((3, 2)))
    b = batching.pad_to_capacity(xs, 8)
    assert b.xs[0].shape == (8, 5) and b.xs[1].shape == (8, 2)
    assert b.n == 3 and int(b.mask.sum()) == 3
    with pytest.raises(ValueError, match="exceeds capacity"):
        batching.pad_to_capacity((jnp.ones((9, 2)),), 8)
    with pytest.raises(ValueError, match="same rows"):
        batching.pad_to_capacity((jnp.ones((3, 2)), jnp.ones((4, 2))), 8)
    chunks = batching.chunk_requests((jnp.ones((10, 2)),), 4)
    assert [c[0].shape[0] for c in chunks] == [4, 4, 2]


def test_latency_recorder_percentiles():
    rec = batching.LatencyRecorder()
    for ms in range(1, 101):
        rec.record(ms / 1e3, rows=2)
    s = rec.summary()
    assert s["batches"] == 100 and s["rows"] == 200
    assert 50.0 <= s["p50_ms"] <= 51.0
    assert 99.0 <= s["p99_ms"] <= 100.0
    assert s["rows_per_s"] > 0


# ------------------------------------------------------- registry + rows
def test_registry_is_the_only_dispatch_surface():
    from repro.core import protocol

    assert not hasattr(protocol, "_batched_impls")
    assert not hasattr(protocol, "_reject_stateful_kwargs")
    # every catalog method the frontier drives resolves
    for name in ("one_shot", "few_shot", "iterative", "fedcvt"):
        entry = runner_registry.get(name)
        assert callable(entry.runner) and callable(entry.seeds_impl)
        assert entry.kind in ("protocol", "iterative")
    # alias and canonical name resolve to one entry
    assert runner_registry.get("iterative") is runner_registry.get("vanilla")
    # runner-callable lookup agrees with name lookup
    e = runner_registry.get("one_shot")
    assert runner_registry.resolve(e.runner) is e
    with pytest.raises(KeyError, match="unknown runner"):
        runner_registry.get("nope")


def test_run_seeds_still_rejects_state_kwargs_via_registry():
    split = _split(seed=3)
    exts = [[make_mlp_extractor(rep_dim=8, hidden=(16,)) for _ in range(2)]]
    from repro.core.ssl import SSLConfig

    with pytest.raises(ValueError, match="state kwargs"):
        run_seeds(run_one_shot, [jax.random.PRNGKey(0)], [split], exts,
                  [[SSLConfig(), SSLConfig()]], _FAST, ledger=object())


def test_row_builders_validate_and_unify():
    row = result_rows.serving_row("p50_ms", 1.25, batch=64, rows_per_s=9.0)
    assert row["kind"] == "serving" and row["metric_name"] == "p50_ms"
    assert row["metric"] == 1.25 and row["batch"] == 64
    with pytest.raises(ValueError, match="shadow"):
        result_rows.serving_row("p50_ms", 1.0, comm_bytes=7)
    with pytest.raises(ValueError, match="kind"):
        result_rows.ResultRow(kind="bogus", metric_name="x", metric=0.0)

    class FakeResult:
        metric_name = "auc"
        metric = 0.9
        diagnostics = {"seed_fold": 2}

        class ledger:  # noqa: N801 — duck-typed CommLedger
            @staticmethod
            def total_bytes():
                return 123

            @staticmethod
            def comm_times():
                return 3

    trow = result_rows.training_row(FakeResult(), scenario="s", seed=0)
    assert trow["kind"] == "train" and trow["comm_bytes"] == 123
    assert trow["seed_fold"] == 2 and trow["scenario"] == "s"
    with pytest.raises(ValueError, match="collide"):
        result_rows.training_row(FakeResult(), seed_fold=1)


def test_serving_gate_consumes_typed_rows(tmp_path):
    import json
    import sys

    sys.path.insert(0, str(__import__("pathlib").Path(__file__).parent.parent))
    from benchmarks import serving as serving_bench

    base = tmp_path / "base.json"
    base.write_text(json.dumps({
        "parity_atol": 1e-5,
        "max_p50_ms": {"1": 10.0},
        "min_rows_per_s": {"1": 100.0},
    }))
    ok = result_rows.serving_row("p50_ms", 1.0, batch=1, rows_per_s=500.0,
                                 parity_max_abs=1e-7, cache_misses=1,
                                 first_shape=True)
    assert serving_bench.check_serving_gate([ok], str(base)) == []
    bad = result_rows.serving_row("p50_ms", 99.0, batch=1, rows_per_s=1.0,
                                  parity_max_abs=1e-2, cache_misses=2,
                                  first_shape=False)
    problems = serving_bench.check_serving_gate([bad], str(base))
    assert len(problems) == 4        # parity, recompile, p50, throughput
    assert serving_bench.check_serving_gate([], str(base)) \
        == ["no serving rows to gate"]
