"""Per-kernel shape/dtype sweeps against the pure-jnp oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.decode_attention import ops as dec_ops, ref as dec_ref
from repro.kernels.kmeans import ops as km_ops, ref as km_ref
from repro.kernels.sdpa_estimator import ops as sdpa_ops, ref as sdpa_ref


# ----------------------------------------------------------------- kmeans --
@pytest.mark.parametrize("n,d,c", [
    (100, 32, 10), (257, 130, 7), (1024, 128, 10), (33, 5, 3),
    (8, 1, 2), (512, 256, 100),
])
def test_kmeans_assign_matches_ref(n, d, c):
    k1, k2 = jax.random.split(jax.random.PRNGKey(n + d + c))
    x = jax.random.normal(k1, (n, d))
    cen = jax.random.normal(k2, (c, d))
    assert np.array_equal(np.asarray(km_ops.kmeans_assign(x, cen)),
                          np.asarray(km_ref.kmeans_assign(x, cen)))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_kmeans_dtypes(dtype):
    x = jax.random.normal(jax.random.PRNGKey(0), (64, 16)).astype(dtype)
    cen = jax.random.normal(jax.random.PRNGKey(1), (4, 16)).astype(dtype)
    got = km_ops.kmeans_assign(x, cen)
    want = km_ref.kmeans_assign(x, cen)
    assert float(np.mean(np.asarray(got) == np.asarray(want))) > 0.98


# ------------------------------------------------------------------- sdpa --
@pytest.mark.parametrize("nu,no,d,db", [
    (100, 50, 32, 48), (513, 200, 128, 128), (7, 3, 5, 9),
    (1000, 64, 64, 96), (256, 256, 256, 32),
])
def test_sdpa_matches_ref(nu, no, d, db):
    ks = jax.random.split(jax.random.PRNGKey(nu + no), 3)
    hu = jax.random.normal(ks[0], (nu, d))
    hoa = jax.random.normal(ks[1], (no, d))
    hob = jax.random.normal(ks[2], (no, db))
    got = sdpa_ops.sdpa_estimate(hu, hoa, hob)
    want = sdpa_ref.sdpa_estimate(hu, hoa, hob)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_sdpa_dtypes(dtype):
    hu = jax.random.normal(jax.random.PRNGKey(0), (65, 32)).astype(dtype)
    hoa = jax.random.normal(jax.random.PRNGKey(1), (33, 32)).astype(dtype)
    hob = jax.random.normal(jax.random.PRNGKey(2), (33, 16)).astype(dtype)
    got = sdpa_ops.sdpa_estimate(hu, hoa, hob)
    want = sdpa_ref.sdpa_estimate(hu, hoa, hob)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=3e-2, rtol=3e-2)


def test_sdpa_large_asymmetric():
    """The few-shot regime: N_u ≫ N_o."""
    hu = jax.random.normal(jax.random.PRNGKey(0), (4096, 128))
    hoa = jax.random.normal(jax.random.PRNGKey(1), (128, 128))
    hob = jax.random.normal(jax.random.PRNGKey(2), (128, 128))
    got = sdpa_ops.sdpa_estimate(hu, hoa, hob)
    want = sdpa_ref.sdpa_estimate(hu, hoa, hob)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


# ----------------------------------------- batched grids (DESIGN.md §15) --
def _km_batch(b, n, d, c, seed=0):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed + b + n))
    return (jax.random.normal(k1, (b, n, d)),
            jax.random.normal(k2, (b, c, d)))


def _sdpa_batch(b, nu, no, d, db, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed + b + nu), 3)
    return (jax.random.normal(ks[0], (b, nu, d)),
            jax.random.normal(ks[1], (b, no, d)),
            jax.random.normal(ks[2], (b, no, db)))


@pytest.mark.parametrize("b,n,d,c", [
    (1, 100, 32, 10), (5, 300, 17, 10), (3, 257, 130, 7), (2, 8, 1, 2),
])
def test_kmeans_batched_grid_matches_vmapped_ref(b, n, d, c):
    """One (B, N/BN) grid launch ≡ jax.vmap of the jnp oracle, bit-equal."""
    x, cen = _km_batch(b, n, d, c)
    got = km_ops.kmeans_assign_batched(x, cen)
    want = jax.vmap(km_ref.kmeans_assign)(x, cen)
    assert got.shape == (b, n)
    assert np.array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("b,n,d,c", [(4, 200, 24, 6)])
def test_kmeans_batched_grid_matches_per_call_kernel(b, n, d, c):
    """Batched grid ≡ B width-1 kernel launches (the fold changes the grid,
    never the program each instance runs)."""
    x, cen = _km_batch(b, n, d, c, seed=7)
    got = km_ops.kmeans_assign_batched(x, cen)
    per = np.stack([np.asarray(km_ops.kmeans_assign(x[i], cen[i]))
                    for i in range(b)])
    assert np.array_equal(np.asarray(got), per)


def test_kmeans_width1_is_batched_grid():
    """The single-entry public op IS the width-1 batched grid."""
    x, cen = _km_batch(1, 150, 20, 5, seed=3)
    a = km_ops.kmeans_assign(x[0], cen[0])
    b_ = km_ops.kmeans_assign_batched(x, cen)[0]
    assert np.array_equal(np.asarray(a), np.asarray(b_))


@pytest.mark.parametrize("b,nu,no,d,db", [
    (1, 100, 50, 32, 48), (4, 333, 70, 19, 23), (2, 513, 200, 128, 128),
    (3, 7, 3, 5, 9),
])
def test_sdpa_batched_grid_matches_vmapped_ref(b, nu, no, d, db):
    """One (B, N_u/BU, N_o/BO) grid launch ≡ jax.vmap of the jnp oracle."""
    hu, hoa, hob = _sdpa_batch(b, nu, no, d, db)
    got = sdpa_ops.sdpa_estimate_batched(hu, hoa, hob)
    want = jax.vmap(sdpa_ref.sdpa_estimate)(hu, hoa, hob)
    assert got.shape == (b, nu, db)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


def test_sdpa_batched_grid_matches_per_call_kernel():
    """Batched grid ≡ B width-1 kernel launches, bit-equal (identical
    per-instance program, identical padding plan)."""
    b, nu, no, d, db = 3, 120, 40, 16, 24
    hu, hoa, hob = _sdpa_batch(b, nu, no, d, db, seed=11)
    got = np.asarray(sdpa_ops.sdpa_estimate_batched(hu, hoa, hob))
    per = np.stack([np.asarray(sdpa_ops.sdpa_estimate(hu[i], hoa[i], hob[i]))
                    for i in range(b)])
    assert np.array_equal(got, per)


def test_batched_grids_vmap_directly():
    """jax.vmap over the batched public entries composes (the stacked-axis
    contract the engine's mesh sharding relies on): vmapping the width-1
    call must agree with the native batched grid."""
    x, cen = _km_batch(3, 64, 12, 4, seed=5)
    native = km_ops.kmeans_assign_batched(x, cen)
    vmapped = jax.vmap(km_ops.kmeans_assign)(x, cen)
    assert np.array_equal(np.asarray(native), np.asarray(vmapped))


# ------------------------------------------------------------ decode attn --
@pytest.mark.parametrize("b,h,hkv,s,dh", [
    (2, 8, 2, 128, 64), (1, 16, 16, 300, 128), (3, 12, 4, 1024, 32),
    (2, 4, 1, 77, 80),
])
def test_decode_attention_matches_ref(b, h, hkv, s, dh):
    ks = jax.random.split(jax.random.PRNGKey(b * h + s), 3)
    q = jax.random.normal(ks[0], (b, h, dh))
    kc = jax.random.normal(ks[1], (b, hkv, s, dh))
    vc = jax.random.normal(ks[2], (b, hkv, s, dh))
    got = dec_ops.decode_attention(q, kc, vc)
    want = dec_ref.decode_attention(q, kc, vc)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_decode_attention_bf16_cache():
    q = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 64))
    kc = jax.random.normal(jax.random.PRNGKey(1), (2, 2, 256, 64)).astype(jnp.bfloat16)
    vc = jax.random.normal(jax.random.PRNGKey(2), (2, 2, 256, 64)).astype(jnp.bfloat16)
    got = dec_ops.decode_attention(q, kc, vc)
    want = dec_ref.decode_attention(q, kc, vc)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=3e-2, rtol=3e-2)
