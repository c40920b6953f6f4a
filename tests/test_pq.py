"""Product quantization: codebooks, ADC, scalar residual, whitening."""

import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import pq


@pytest.fixture(scope="module")
def gauss():
    rng = np.random.default_rng(0)
    return rng.normal(size=(3000, 32)).astype(np.float32)


def test_train_codebooks_compiles_one_kmeans_program(gauss):
    """The per-subspace seed is traced, not static: a build with many
    subspaces compiles ONE k-means program, and the per-subspace keys still
    make the codebooks deterministic under ``seed``."""
    x = jnp.asarray(gauss)
    pq._kmeans_one_subspace.clear_cache()
    a = pq.train_codebooks(x, num_subspaces=16, num_codes=16, iters=3,
                           seed=5)
    assert pq._kmeans_one_subspace._cache_size() == 1
    b = pq.train_codebooks(x, num_subspaces=16, num_codes=16, iters=3,
                           seed=5)
    np.testing.assert_array_equal(np.asarray(a.centers),
                                  np.asarray(b.centers))
    assert not np.array_equal(np.asarray(a.centers[0]),
                              np.asarray(a.centers[1]))


def test_encode_decode_reduces_mse(gauss):
    x = jnp.asarray(gauss)
    cb = pq.train_codebooks(x, num_subspaces=16, num_codes=16, iters=8)
    rec = pq.pq_decode(pq.pq_encode(x, cb), cb)
    mse = float(((rec - x) ** 2).mean())
    assert mse < 0.5 * float(x.var())


def test_more_codes_less_error(gauss):
    x = jnp.asarray(gauss)
    errs = []
    for l in (4, 16):
        cb = pq.train_codebooks(x, num_subspaces=16, num_codes=l, iters=8)
        rec = pq.pq_decode(pq.pq_encode(x, cb), cb)
        errs.append(float(((rec - x) ** 2).mean()))
    assert errs[1] < errs[0]


def test_adc_equals_decode_dot(gauss):
    x = jnp.asarray(gauss[:500])
    cb = pq.train_codebooks(x, num_subspaces=8, num_codes=16, iters=5)
    codes = pq.pq_encode(x, cb)
    q = jnp.asarray(np.random.default_rng(1).normal(size=(6, 32)),
                    jnp.float32)
    lut = pq.adc_lut(q, cb)
    scores = pq.adc_scores_ref(codes, lut)
    exact = q @ pq.pq_decode(codes, cb).T
    np.testing.assert_allclose(np.asarray(scores), np.asarray(exact),
                               rtol=1e-4, atol=1e-4)


def test_adc_single_query(gauss):
    x = jnp.asarray(gauss[:200])
    cb = pq.train_codebooks(x, num_subspaces=8, num_codes=16, iters=4)
    codes = pq.pq_encode(x, cb)
    q = jnp.asarray(gauss[0])
    lut = pq.adc_lut(q, cb)
    assert lut.shape == (8, 16)
    s = pq.adc_scores_ref(codes, lut)
    assert s.shape == (200,)


def test_codes_in_range(gauss):
    x = jnp.asarray(gauss[:256])
    cb = pq.train_codebooks(x, num_subspaces=4, num_codes=16, iters=3)
    codes = np.asarray(pq.pq_encode(x, cb))
    assert codes.min() >= 0 and codes.max() < 16
    assert codes.dtype == np.uint8


def test_scalar_quant_roundtrip(gauss):
    sq = pq.scalar_quantize(jnp.asarray(gauss))
    rec = np.asarray(pq.scalar_dequantize(sq))
    rng_per_dim = gauss.max(0) - gauss.min(0)
    err = np.abs(rec - gauss)
    assert (err <= rng_per_dim[None, :] / 255.0 + 1e-5).all()


def test_whitening_preserves_inner_products(gauss):
    p, p_inv_t = pq.whitening_transform(gauss[:1000])
    x = gauss[:50]
    q = gauss[50:60]
    lhs = (q @ np.asarray(p_inv_t)) @ (x @ np.asarray(p)).T
    rhs = q @ x.T
    np.testing.assert_allclose(lhs, rhs, rtol=2e-2, atol=2e-2)


def test_proposition1_rate_distortion_order(gauss):
    """More bits per dim => lower bound decreases; empirical k-means MSE
    should track the 2^{-2b/d} ordering (Prop. 1)."""
    x = jnp.asarray(gauss)
    mse = {}
    for k in (8, 16):           # 8 subspaces = 1 bit/dim, 16 = 2 bits/dim
        cb = pq.train_codebooks(x, num_subspaces=k, num_codes=16, iters=8)
        rec = pq.pq_decode(pq.pq_encode(x, cb), cb)
        mse[k] = float(((rec - x) ** 2).mean())
    assert mse[16] < mse[8]


@settings(max_examples=10, deadline=None)
@given(st.integers(1, 4), st.integers(0, 1000))
def test_property_adc_linear_in_query(k, seed):
    """ADC score is linear in q: score(aq) = a*score(q)."""
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(64, 8 * k)), jnp.float32)
    cb = pq.train_codebooks(x, num_subspaces=k, num_codes=8, iters=2)
    codes = pq.pq_encode(x, cb)
    q = jnp.asarray(rng.normal(size=(1, 8 * k)), jnp.float32)
    s1 = pq.adc_scores_ref(codes, pq.adc_lut(q, cb))
    s2 = pq.adc_scores_ref(codes, pq.adc_lut(2.0 * q, cb))
    np.testing.assert_allclose(np.asarray(2.0 * s1), np.asarray(s2),
                               rtol=1e-4, atol=1e-4)
