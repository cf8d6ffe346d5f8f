"""The party extractor's compiled forward (``core.client.extract_program``):
the same numbers as the eager ``Model.apply``, and one cached program per
architecture that later protocol calls re-serve (session domain
``"extract"``, DESIGN.md §9)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import engine
from repro.core import ProtocolConfig, SSLConfig, run_one_shot
from repro.core.client import extract_program, make_client
from repro.data import make_tabular_credit, make_vfl_partition
from repro.engine import sessions
from repro.models import Model, make_cnn_extractor, make_mlp_extractor

K = 2


@pytest.fixture(scope="module")
def split():
    x, y = make_tabular_credit(jax.random.PRNGKey(0), 600)
    return make_vfl_partition(x, y, overlap_size=64, feature_sizes=[10, 13],
                              seed=1)


@pytest.mark.parametrize("kind,rows", [("cnn", 6), ("mlp", 6),
                                       ("cnn", 1030), ("mlp", 1100)])
def test_compiled_extract_matches_eager(kind, rows):
    """Rows past ``_BLOCK_ROWS`` run in equal padded blocks: 1030 and 1100
    rows take three blocks, with two and one rows of padding."""
    if kind == "cnn":
        model = make_cnn_extractor(rep_dim=8, widths=(4, 8),
                                   blocks_per_stage=1)
        x = jax.random.normal(jax.random.PRNGKey(1), (rows, 8, 4, 3))
    else:
        model = make_mlp_extractor(rep_dim=8, hidden=(16,))
        x = jax.random.normal(jax.random.PRNGKey(1), (rows, 10))
    client = make_client(jax.random.PRNGKey(2), 0, model, 2, x[:2],
                         SSLConfig(modality="image" if kind == "cnn"
                                   else "tabular"))
    eager = model.apply(client.params.extractor, x)
    compiled = client.extract(x)
    assert compiled.dtype == jnp.float32
    assert compiled.shape == eager.shape
    np.testing.assert_allclose(np.asarray(compiled), np.asarray(eager),
                               rtol=1e-5, atol=1e-6)


def test_second_one_shot_call_adds_no_extract_misses(split):
    """One program per architecture serves ①, ⑤ and the evaluation of every
    party: a second call builds nothing and re-serves it at each site."""
    ssl = [SSLConfig(modality="tabular")] * K
    cfg = ProtocolConfig(client_epochs=1, server_epochs=2)

    def extractors():
        return [make_mlp_extractor(rep_dim=8, hidden=(16,)) for _ in range(K)]

    engine.clear_session_cache()
    run_one_shot(jax.random.PRNGKey(0), split, extractors(), ssl, cfg)
    first = engine.session_cache_stats("extract")
    assert first["misses"] == 1, first
    run_one_shot(jax.random.PRNGKey(1), split, extractors(), ssl, cfg)
    second = engine.session_cache_stats("extract")
    assert second["misses"] == first["misses"], (first, second)
    assert second["hits"] - first["hits"] >= 3 * K, (first, second)


def test_undigestable_closure_misses_once_per_model():
    """``model_key`` gives a closure it cannot digest (here a dict) a fresh
    token; the forward is then keyed on the ``Model`` itself, so repeated
    calls re-serve one program instead of building one per call."""
    widths = {"hidden": 16}
    base = make_mlp_extractor(rep_dim=8, hidden=(widths["hidden"],))

    def apply(params, x, train=False):
        assert widths["hidden"] == 16
        return base.apply(params, x, train)

    model = Model(init=base.init, apply=apply, rep_dim=base.rep_dim)
    assert not sessions.is_digested(sessions.model_key(model))
    x = jax.random.normal(jax.random.PRNGKey(0), (5, 10))
    client = make_client(jax.random.PRNGKey(1), 0, model, 2, x[:2],
                         SSLConfig(modality="tabular"))
    engine.clear_session_cache()
    first = client.extract(x)
    second = client.extract(x[:3])
    assert engine.session_cache_stats("extract") == {"hits": 1, "misses": 1}
    np.testing.assert_allclose(np.asarray(second), np.asarray(first[:3]),
                               rtol=1e-6, atol=1e-6)
    assert extract_program(model) is extract_program(model)
