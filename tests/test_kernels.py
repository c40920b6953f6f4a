"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret mode)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.block_sparse import (block_sparse_matmul_pallas,
                                        dense_to_bcsr)
from repro.kernels.lut16 import default_interpret, lut16_adc_pallas
from repro.kernels.ops import block_sparse_matmul, lut16_adc
from repro.kernels.ref import (bcsr_to_dense_ref, block_sparse_ref,
                               lut16_adc_ref)

RNG = np.random.default_rng(42)


# ---------------------------------------------------------------------------
# LUT16 ADC
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,k,l,q", [
    (512, 16, 16, 8),
    (1024, 32, 16, 8),
    (512, 8, 8, 16),
    (2048, 64, 16, 4),
    (512, 16, 4, 8),
])
def test_lut16_shapes(n, k, l, q):
    codes = jnp.asarray(RNG.integers(0, l, (n, k)).astype(np.uint8))
    lut = jnp.asarray(RNG.normal(size=(q, k, l)).astype(np.float32))
    want = lut16_adc_ref(codes, lut)
    got = lut16_adc_pallas(codes, lut, bq=min(8, q), bn=256,
                           interpret=default_interpret())
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_lut16_compute_dtypes(dtype):
    codes = jnp.asarray(RNG.integers(0, 16, (512, 16)).astype(np.uint8))
    lut = jnp.asarray(RNG.normal(size=(8, 16, 16)).astype(np.float32))
    want = np.asarray(lut16_adc_ref(codes, lut))
    got = np.asarray(lut16_adc_pallas(codes, lut, bq=8, bn=256,
                                      interpret=default_interpret(),
                                      compute_dtype=dtype))
    tol = 1e-4 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol * 10)


def test_split_bf16x3_is_exact():
    """The f32 LUT's three bf16 terms add back to it bit for bit: random
    LUTs of both signs over many scales, and the extremes that keep the
    last term a normal number (down to 1e-30, up to the largest f32)."""
    from repro.kernels.lut16 import split_bf16x3
    f32 = np.finfo(np.float32)
    x = (RNG.normal(size=(4, 100, 16))
         * 10.0 ** RNG.integers(-25, 30, (4, 100, 1))).astype(np.float32)
    extremes = np.array([f32.max, -f32.max, 1e-30, -1e-30, 2.0 ** -100,
                         np.nextafter(np.float32(1), np.float32(2)), -1.0,
                         0.0, 3.0e38, np.float32(np.pi)], np.float32)
    for v in (x, extremes):
        terms = split_bf16x3(jnp.asarray(v))
        assert all(t.dtype == jnp.bfloat16 for t in terms)
        hi, mid, lo = (np.asarray(t, np.float32) for t in terms)
        np.testing.assert_array_equal((hi + mid) + lo, v)
        np.testing.assert_array_equal(hi + (mid + lo), v)
    assert (np.asarray(split_bf16x3(jnp.asarray(x))[0], np.float32)
            != x).mean() > 0.9                 # one bf16 term is not enough


@pytest.mark.parametrize("k", [100, 384])
@pytest.mark.parametrize("packed", [False, True], ids=["unpacked", "packed"])
def test_lut16_f32_matches_float64(k, packed):
    """compute_dtype=float32 keeps f32: every score is within 1e-6 of the
    float64 sum, relative to the sum of |terms| (a score whose terms cancel
    keeps no relative digits below that scale), at QuerySim's K=100 and the
    passage embeddings' K=384.  The bfloat16 path stays measurably coarser."""
    from repro.kernels.lut16 import pack_codes
    n, q = 1000, 3
    codes = RNG.integers(0, 16, (n, k)).astype(np.uint8)
    lut = RNG.normal(size=(q, k, 16)).astype(np.float32)
    terms = np.take_along_axis(
        lut[:, None].astype(np.float64),
        codes[None, :, :, None].astype(np.int64), axis=3)[..., 0]
    want, scale = terms.sum(-1), np.abs(terms).sum(-1)
    codes_in = jnp.asarray(pack_codes(codes) if packed else codes)
    err = {}
    for dt in (jnp.float32, jnp.bfloat16):
        got = np.asarray(lut16_adc(codes_in, jnp.asarray(lut), packed=packed,
                                   compute_dtype=dt), np.float64)
        err[dt] = np.max(np.abs(got - want) / scale)
    assert err[jnp.float32] <= 1e-6, err
    assert err[jnp.bfloat16] > 1e-4, err


@pytest.mark.parametrize("k", [100, 384])
def test_resolve_lut16_blocks_takes_the_whole_batch(k):
    """bq follows Q: the service's 1-, 8- and 32-query buckets each run as
    one query block at K=100 and K=384 packed; an explicit bq is kept; a
    batch past the VMEM budget splits into the fewest blocks of a multiple
    of 8 rows that fit."""
    from repro.kernels.lut16 import VMEM_BUDGET, vmem_bytes
    from repro.kernels.ops import _resolve_lut16_blocks
    kc, n, bn = k // 2, 1_000_000, 1024
    kw = dict(l=16, packed=True, cbuf=256)
    for q in (1, 8, 32):
        assert _resolve_lut16_blocks(q, n, kc, bq=None, bn=bn, **kw) == (q, bn)
    assert _resolve_lut16_blocks(32, n, kc, bq=8, bn=bn, **kw) == (8, bn)
    assert _resolve_lut16_blocks(5, n, kc, bq=8, bn=bn, **kw) == (5, bn)
    assert _resolve_lut16_blocks(32, 300, kc, bq=None, bn=bn, **kw) == (32, 384)
    bq, bn = _resolve_lut16_blocks(4096, n, kc, bq=None, bn=bn, **kw)
    assert bq % 8 == 0 and 8 <= bq < 4096
    assert vmem_bytes(bq, bn, kc, **kw) <= VMEM_BUDGET
    assert -(-4096 // bq) * bq - 4096 < 8 * -(-4096 // bq)    # little padding


def test_lut16_explicit_query_block_matches_whole_batch():
    """Q=32 split into four 8-query blocks (24-row bf16 LUT blocks) scores
    exactly like the one 32-query block the default takes."""
    from repro.kernels.lut16 import pack_codes
    codes = jnp.asarray(pack_codes(RNG.integers(0, 16, (700, 20))
                                   .astype(np.uint8)))
    lut = jnp.asarray(RNG.normal(size=(32, 20, 16)).astype(np.float32))
    whole = lut16_adc(codes, lut, packed=True)
    split = lut16_adc(codes, lut, packed=True, bq=8)
    np.testing.assert_array_equal(np.asarray(split), np.asarray(whole))


def test_lut16_padding_wrapper():
    """Non-multiple shapes go through ops.lut16_adc padding."""
    codes = jnp.asarray(RNG.integers(0, 16, (777, 13)).astype(np.uint8))
    lut = jnp.asarray(RNG.normal(size=(5, 13, 16)).astype(np.float32))
    want = lut16_adc_ref(codes, lut)
    got = lut16_adc(codes, lut)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-4)


def test_lut16_single_query_2d_lut():
    codes = jnp.asarray(RNG.integers(0, 16, (256, 8)).astype(np.uint8))
    lut = jnp.asarray(RNG.normal(size=(8, 16)).astype(np.float32))
    got = lut16_adc(codes, lut)
    want = lut16_adc_ref(codes, lut[None])[0]
    assert got.shape == (256,)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-4)


def test_lut16_packed_4bit():
    """Paper §6.1.1 storage: two 4-bit codes per byte — half the HBM stream,
    same scores."""
    from repro.kernels.lut16 import pack_codes
    codes = RNG.integers(0, 16, (512, 16)).astype(np.uint8)
    lut = jnp.asarray(RNG.normal(size=(8, 16, 16)).astype(np.float32))
    want = lut16_adc_ref(jnp.asarray(codes), lut)
    packed = jnp.asarray(pack_codes(codes))
    assert packed.shape == (512, 8)
    got = lut16_adc_pallas(packed, lut, bq=8, bn=256, packed=True,
                           interpret=default_interpret())
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("k", [12, 13])
def test_pack_unpack_roundtrip(k):
    """pack_codes/unpack_codes invert each other for even AND odd K; odd K
    zero-pads a phantom high nibble that unpack slices off."""
    from repro.kernels.lut16 import pack_codes, unpack_codes
    codes = RNG.integers(0, 16, (64, k)).astype(np.uint8)
    packed = pack_codes(codes)
    assert packed.shape == (64, (k + 1) // 2)
    if k % 2:
        assert (packed[:, -1] >> 4 == 0).all()      # phantom nibble is zero
    np.testing.assert_array_equal(np.asarray(unpack_codes(packed, k)), codes)


def test_pack_codes_rejects_wide_codes():
    """Codes outside [0, 16) would corrupt the neighbouring nibble — the old
    silent-misbehavior case must now raise."""
    from repro.kernels.lut16 import pack_codes, unpack_codes
    with pytest.raises(ValueError, match="4-bit"):
        pack_codes(np.full((4, 8), 16, np.uint8))
    with pytest.raises(ValueError, match="4-bit"):
        pack_codes(np.full((4, 8), -1, np.int32))
    with pytest.raises(ValueError):
        unpack_codes(np.zeros((4, 4), np.uint8), 6)   # 4 bytes can't hold 6


@pytest.mark.parametrize("n,k,q", [(512, 16, 8), (777, 13, 5), (300, 1, 3)])
def test_lut16_packed_via_ops_wrapper(n, k, q):
    """ops.lut16_adc(packed=True): block padding + the odd-K phantom
    subspace (zero LUT column) both handled in the wrapper."""
    from repro.kernels.lut16 import pack_codes
    codes = RNG.integers(0, 16, (n, k)).astype(np.uint8)
    lut = jnp.asarray(RNG.normal(size=(q, k, 16)).astype(np.float32))
    want = lut16_adc_ref(jnp.asarray(codes), lut)
    got = lut16_adc(jnp.asarray(pack_codes(codes)), lut, packed=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-4)


def test_lut16_packed_shape_mismatch_raises():
    from repro.kernels.lut16 import pack_codes
    packed = jnp.asarray(pack_codes(RNG.integers(0, 16, (64, 8))
                                    .astype(np.uint8)))      # (64, 4)
    lut16 = jnp.asarray(RNG.normal(size=(2, 16, 16)).astype(np.float32))
    with pytest.raises(ValueError, match="cannot hold"):
        lut16_adc(packed, lut16, packed=True)                # 16 != 2*4
    lut_l8 = jnp.asarray(RNG.normal(size=(2, 8, 8)).astype(np.float32))
    with pytest.raises(ValueError, match="l == 16"):
        lut16_adc(packed, lut_l8, packed=True)


# ---------------------------------------------------------------------------
# block-sparse tile-skipping matmul
# ---------------------------------------------------------------------------

def _random_block_sparse(n, d, br, bc, density):
    x = RNG.normal(size=(n, d)).astype(np.float32)
    mask = RNG.random((n // br, d // bc)) < density
    return x * np.kron(mask, np.ones((br, bc), np.float32))


@pytest.mark.parametrize("n,d,br,bc,density", [
    (256, 256, 64, 64, 0.3),
    (512, 128, 128, 128, 0.5),
    (384, 256, 128, 128, 0.1),
    (256, 512, 64, 128, 0.0),     # fully-empty matrix
    (256, 256, 64, 64, 1.0),      # fully-dense
])
def test_block_sparse_shapes(n, d, br, bc, density):
    xm = _random_block_sparse(n, d, br, bc, density)
    tiles, ptr, col = dense_to_bcsr(xm, br, bc)
    q = jnp.asarray(RNG.normal(size=(8, d)).astype(np.float32))
    want = block_sparse_ref(q, jnp.asarray(xm))
    ms = int(np.max(ptr[1:] - ptr[:-1], initial=1))
    got = block_sparse_matmul_pallas(q, jnp.asarray(tiles), jnp.asarray(ptr),
                                     jnp.asarray(col), bq=8,
                                     max_steps=max(ms, 1),
                                     interpret=default_interpret())
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-4)


def test_bcsr_roundtrip():
    xm = _random_block_sparse(256, 256, 64, 64, 0.4)
    tiles, ptr, col = dense_to_bcsr(xm, 64, 64)
    back = np.asarray(bcsr_to_dense_ref(tiles, ptr, col, 256))
    np.testing.assert_allclose(back, xm, atol=0)


def test_bcsr_tile_count_is_skip_metric():
    """Stored tiles == nonzero tiles: what cache sorting minimizes."""
    xm = _random_block_sparse(256, 256, 64, 64, 0.25)
    tiles, ptr, col = dense_to_bcsr(xm, 64, 64)
    nz_tiles = int((np.abs(xm.reshape(4, 64, 4, 64)).max(axis=(1, 3)) > 0)
                   .sum())
    assert tiles.shape[0] == max(nz_tiles, 1)


def test_block_sparse_through_head_wrapper():
    import scipy.sparse as sp
    from repro.core.sparse_index import build_tile_sparse_head, score_head_ref
    xm = _random_block_sparse(256, 256, 128, 128, 0.4)
    head = build_tile_sparse_head(sp.csr_matrix(xm), np.arange(256),
                                  block_rows=128, block_cols=128)
    q = jnp.asarray(RNG.normal(size=(5, head.block.shape[1]))
                    .astype(np.float32))
    got = block_sparse_matmul(q, head)
    want = score_head_ref(head, q)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-4)


# ---------------------------------------------------------------------------
# Fused scan-and-select (DESIGN.md §2.5)
# ---------------------------------------------------------------------------

def _topk_oracle(scores: np.ndarray, k: int):
    import jax
    s, i = jax.lax.top_k(jnp.asarray(scores), k)
    return np.asarray(s), np.asarray(i)


@pytest.mark.parametrize("n,k_sub,l,q", [
    (1000, 8, 16, 4),      # non-multiple N
    (4000, 7, 16, 12),     # odd K (phantom nibble when packed)
    (300, 5, 8, 3),        # small l, tiny N
    (2048, 16, 16, 8),     # exact-multiple shapes
    (1000, 8, 16, 32),     # the 32-query bucket: one block, bq = 32
])
@pytest.mark.parametrize("packed", [False, True])
@pytest.mark.parametrize("topk", [5, 37, 128])
def test_fused_topk_matches_materialize(n, k_sub, l, q, packed, topk):
    """Fused scan-and-select ≡ materialize-then-topk, bit for bit: the two
    paths share the per-block partial sums and the bias-at-select ordering,
    so both scores AND ids must be exactly equal."""
    from repro.kernels.ops import lut16_adc_topk
    if packed and l != 16:
        pytest.skip("packed kernel requires l == 16")
    codes = RNG.integers(0, l, (n, k_sub)).astype(np.uint8)
    lut = jnp.asarray(RNG.normal(size=(q, k_sub, l)).astype(np.float32))
    if packed:
        from repro.kernels.lut16 import pack_codes
        codes_in = jnp.asarray(pack_codes(codes))
    else:
        codes_in = jnp.asarray(codes)
    bias = jnp.asarray(RNG.normal(size=(q, n)).astype(np.float32))
    for b in (None, bias):
        sf, idf = lut16_adc_topk(codes_in, lut, topk, bias=b,
                                 packed=packed, fused=True)
        sm, idm = lut16_adc_topk(codes_in, lut, topk, bias=b,
                                 packed=packed, fused=False)
        np.testing.assert_array_equal(np.asarray(idf), np.asarray(idm))
        np.testing.assert_array_equal(np.asarray(sf), np.asarray(sm))


def test_fused_topk_matches_ref_oracle():
    """Against the pure-jnp oracle: same ids as ref-scores + lax.top_k (the
    deterministic lowest-index tie-break), scores within fp32 tolerance."""
    from repro.kernels.ops import lut16_adc_topk
    n, k_sub, l, q, topk = 1500, 12, 16, 6, 64
    codes = RNG.integers(0, l, (n, k_sub)).astype(np.uint8)
    lut = jnp.asarray(RNG.normal(size=(q, k_sub, l)).astype(np.float32))
    ref = np.asarray(lut16_adc_ref(jnp.asarray(codes), lut))
    want_s, want_i = _topk_oracle(ref, topk)
    got_s, got_i = lut16_adc_topk(jnp.asarray(codes), lut, topk, fused=True)
    np.testing.assert_array_equal(np.asarray(got_i), want_i)
    np.testing.assert_allclose(np.asarray(got_s), want_s,
                               rtol=1e-5, atol=1e-4)


def test_fused_topk_tombstones_never_surface():
    """-inf row_mask rows must never appear as finite-score candidates, and
    slots the live pool can't fill get id -1 (merge_topk_host's contract)."""
    from repro.kernels.ops import lut16_adc_topk
    n, k_sub, l, q = 900, 6, 16, 5
    codes = jnp.asarray(RNG.integers(0, l, (n, k_sub)).astype(np.uint8))
    lut = jnp.asarray(RNG.normal(size=(q, k_sub, l)).astype(np.float32))
    mask = np.zeros(n, np.float32)
    dead = RNG.choice(n, 300, replace=False)
    mask[dead] = -np.inf
    sf, idf = lut16_adc_topk(codes, lut, 64, row_mask=jnp.asarray(mask),
                             fused=True)
    sm, idm = lut16_adc_topk(codes, lut, 64, row_mask=jnp.asarray(mask),
                             fused=False)
    np.testing.assert_array_equal(np.asarray(idf), np.asarray(idm))
    np.testing.assert_array_equal(np.asarray(sf), np.asarray(sm))
    sf, idf = np.asarray(sf), np.asarray(idf)
    assert not (set(idf[np.isfinite(sf)].ravel().tolist())
                & set(dead.tolist()))
    # more candidates than live rows: the overflow slots are (-inf, -1)
    mask2 = np.full(n, -np.inf, np.float32)
    mask2[:10] = 0.0
    s2, i2 = lut16_adc_topk(codes, lut, 32, row_mask=jnp.asarray(mask2),
                            fused=True)
    s2, i2 = np.asarray(s2), np.asarray(i2)
    assert np.isfinite(s2[:, :10]).all()
    assert set(i2[:, :10].ravel().tolist()) <= set(range(10))
    assert (i2[~np.isfinite(s2)] == -1).all()


def test_fused_topk_buffer_overflow_falls_back(monkeypatch):
    """k above the VMEM candidate buffer cap must route to the materialize
    fallback — same results, no fused kernel."""
    import repro.kernels.ops as ops
    n, k_sub, l, q = 600, 6, 16, 4
    codes = jnp.asarray(RNG.integers(0, l, (n, k_sub)).astype(np.uint8))
    lut = jnp.asarray(RNG.normal(size=(q, k_sub, l)).astype(np.float32))
    want_s, want_i = ops.lut16_adc_topk(codes, lut, 40, fused=True)
    monkeypatch.setattr(ops, "MAX_FUSED_CANDIDATES", 16)
    got_s, got_i = ops.lut16_adc_topk(codes, lut, 40, fused=True)
    np.testing.assert_array_equal(np.asarray(got_i), np.asarray(want_i))
    np.testing.assert_array_equal(np.asarray(got_s), np.asarray(want_s))
    # and the fallback it routed to materializes (structurally observable)
    import functools
    assert ops.dense_scores_materialized(
        functools.partial(ops.lut16_adc_topk, k=40, fused=True), codes, lut)
    monkeypatch.undo()
    assert not ops.dense_scores_materialized(
        functools.partial(ops.lut16_adc_topk, k=40, fused=True), codes, lut)


def test_fused_topk_rejects_bad_k():
    from repro.kernels.ops import lut16_adc_topk
    codes = jnp.asarray(RNG.integers(0, 16, (128, 4)).astype(np.uint8))
    lut = jnp.asarray(RNG.normal(size=(2, 4, 16)).astype(np.float32))
    with pytest.raises(ValueError, match="top-k needs"):
        lut16_adc_topk(codes, lut, 0)
    with pytest.raises(ValueError, match="top-k needs"):
        lut16_adc_topk(codes, lut, 129)


def test_fused_jaxpr_has_no_dense_materialization():
    """The structural half of the packed-speedup floor (ISSUE 6): in the
    no-bias fused path, NO fp32 tensor of shape (Q>1, >=N) exists anywhere
    in the jaxpr — the (Q, N) score matrix is provably absent.  The
    materialize path trips the same detector, proving it detects."""
    import functools
    from repro.kernels.ops import dense_scores_materialized, lut16_adc_topk
    codes = jnp.asarray(RNG.integers(0, 16, (512, 8)).astype(np.uint8))
    lut = jnp.asarray(RNG.normal(size=(4, 8, 16)).astype(np.float32))
    mask = jnp.zeros(512, jnp.float32)
    for kwargs in ({}, {"row_mask": mask}):
        assert not dense_scores_materialized(
            functools.partial(lut16_adc_topk, k=32, fused=True, **kwargs),
            codes, lut)
    assert dense_scores_materialized(
        functools.partial(lut16_adc_topk, k=32, fused=False), codes, lut)


def test_candidate_buffer_width():
    from repro.kernels.lut16 import candidate_buffer_width
    assert candidate_buffer_width(1) == 128
    assert candidate_buffer_width(128) == 128
    assert candidate_buffer_width(129) == 256
    assert candidate_buffer_width(400) == 512


# ---------------------------------------------------------------------------
# Value-forward inverted scoring (SINDI; DESIGN.md §2.5)
# ---------------------------------------------------------------------------

def _toy_sparse_problem(n, d, qn, *, density=0.01, q_density=0.02, seed=0,
                        nq_max=32):
    import scipy.sparse as sp
    from repro.core.sparse_index import (build_compact_columns,
                                         build_padded_inverted_index,
                                         sparse_queries_to_padded)
    x = sp.random(n, d, density=density, random_state=seed, format="csr")
    cols, xc = build_compact_columns(x)
    inv = build_padded_inverted_index(xc)
    qs = sp.random(qn, d, density=q_density, random_state=seed + 1,
                   format="csr")
    qd, qv = sparse_queries_to_padded(qs, cols, nq_max=nq_max)
    return inv, qd, qv


@pytest.mark.parametrize("n,d,qn", [
    (700, 500, 9),         # non-multiple N, non-multiple Q
    (512, 200, 8),         # exact multiples
    (50, 80, 3),           # tiny: single row block
])
def test_value_forward_matches_score_inverted(n, d, qn):
    from repro.core.sparse_index import score_inverted
    from repro.kernels.ops import score_inverted_vf
    inv, qd, qv = _toy_sparse_problem(n, d, qn, seed=n)
    ref = np.asarray(score_inverted(inv, jnp.asarray(qd), jnp.asarray(qv)))
    got = np.asarray(score_inverted_vf(inv, qd, qv))
    assert got.shape == ref.shape
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-5)


def test_value_forward_duplicate_dims_and_empty_rows():
    """A query repeating a dim accumulates twice; an all-pad query row
    scores exactly zero everywhere."""
    from repro.core.sparse_index import score_inverted
    from repro.kernels.ops import score_inverted_vf
    inv, qd, qv = _toy_sparse_problem(300, 150, 4, seed=9)
    qd = np.asarray(qd).copy()
    qv = np.asarray(qv).copy()
    qd[0, 1] = qd[0, 0]                      # duplicate dim in query 0
    qv[0, 1] = 0.5
    d_active = int(np.asarray(inv.rows).shape[0])
    qd[2, :] = d_active                      # query 2: empty (all pad)
    qv[2, :] = 0.0
    ref = np.asarray(score_inverted(inv, jnp.asarray(qd), jnp.asarray(qv)))
    got = np.asarray(score_inverted_vf(inv, qd, qv))
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-5)
    assert np.all(got[2] == 0.0)


def test_value_forward_stream_layout():
    """Planner invariants the kernel's index maps rely on: chunk-aligned
    ptr in chunk units, block-local row ids, pad rows == bn."""
    from repro.core.sparse_index import build_value_forward_stream
    inv, qd, qv = _toy_sparse_problem(700, 500, 9, seed=5)
    st = build_value_forward_stream(inv, qd, qv, bq=8, bn=256, chunk=64)
    rows = np.asarray(st.rows)
    ptr = np.asarray(st.ptr)
    assert rows.shape[1] % st.chunk == 0
    assert rows.min() >= 0 and rows.max() <= st.bn
    qb = rows.shape[0]
    nb1 = st.num_row_blocks + 1
    assert ptr.shape == (qb * nb1,)
    for b in range(qb):
        seg = ptr[b * nb1:(b + 1) * nb1]
        assert (np.diff(seg) >= 0).all()
        assert seg[-1] * st.chunk <= rows.shape[1]
