"""Streaming mutable index (core/streaming.py, DESIGN.md §6).

Two headline properties, one per compaction policy (DESIGN.md §6.2):

* ``compact(retrain=True)`` — for ARBITRARY insert/delete/search
  interleavings the rebuilt index is indistinguishable — bit-identical
  top-k ids AND scores — from a from-scratch ``HybridIndex.build`` on the
  same surviving rows, because the rebuild re-runs the deterministic batch
  build on the retained corpus in canonical order.
* ``compact(retrain=False)`` (merge compaction) — the folded index keeps
  the FROZEN codebooks / scalar grid / column space, so equivalence is
  RELAXED: every row's refined score must match the host-side
  frozen-encoding oracle to float tolerance, and the top-k id sets must
  agree with a scratch rebuild up to the measured encoding tolerance
  (perturbation bound on the exact scores).

Both hold across backends {ref, pallas, pallas-packed} and odd/even PQ
subspace counts (the packed odd-K case exercises the phantom-nibble
append); the property tests are what keep those contracts honest as the
delta/merge machinery evolves.

Plus unit coverage of the delta machinery: tombstone masks, capacity
doubling, posting-list growth, frozen-artifact encoding, upserts, and the
out-of-compact-space dim buffering rule.
"""

import numpy as np
import pytest
import scipy.sparse as sp

from hypothesis import given, settings, strategies as st

from repro.core.engine import ScoringEngine, tombstone_mask
from repro.core.hybrid import HybridIndex, HybridIndexParams
from repro.core.pq import (encode_rows, pack_codes, pq_decode, pq_encode,
                           scalar_quantize, scalar_quantize_rows)
from repro.core.sparse_index import DeltaPostings
from repro.data import make_hybrid_dataset

# -- shared tiny workload ----------------------------------------------------

N0, N_POOL, NQ = 240, 300, 3
D_SPARSE, NNZ = 360, 12


def _dataset(d_dense):
    return make_hybrid_dataset(num_points=N_POOL, num_queries=NQ,
                               d_sparse=D_SPARSE, d_dense=d_dense,
                               nnz_per_row=NNZ, seed=11)


_DS_CACHE = {}


def _cached_dataset(d_dense):
    if d_dense not in _DS_CACHE:
        _DS_CACHE[d_dense] = _dataset(d_dense)
    return _DS_CACHE[d_dense]


def _params(backend, k):
    return HybridIndexParams(keep_top=24, head_dims=12, kmeans_iters=3,
                             backend=backend, pq_subspaces=k)


def _build_mutable(ds, params):
    return HybridIndex.build(ds.x_sparse[:N0], ds.x_dense[:N0], params,
                             mutable=True)


# -- incremental-vs-rebuild equivalence property -----------------------------

def _check_equivalence(backend: str, k: int, d_dense: int, seed: int):
    """Random interleaving of inserts (incl. upserts), deletes and searches;
    after compaction the streaming index must equal a scratch build on the
    surviving rows, bit for bit, and every intermediate search must respect
    the tombstones."""
    ds = _cached_dataset(d_dense)
    params = _params(backend, k)
    idx = _build_mutable(ds, params)

    rng = np.random.default_rng(seed)
    # model of the logical contents: ext id -> corpus pool row feeding it
    live = {i: i for i in range(N0)}
    deleted: set[int] = set()
    pool = list(range(N0, N_POOL))        # rows never used twice as-new
    n_inserts, n_deletes = 20, 16
    ops = ["ins"] * n_inserts + ["del"] * n_deletes
    rng.shuffle(ops)

    def check_search():
        r = idx.search(ds.q_sparse, ds.q_dense, h=8)
        for row in r.ids:
            real = row[row >= 0]
            assert len(set(real)) == len(real), "duplicate ids in one result"
            for e in real:
                assert e not in deleted, "tombstoned id served"
                assert int(e) in live, "unknown id served"

    upserts = 0
    for t, op in enumerate(ops):
        if op == "ins":
            src = pool.pop(0)
            if upserts < 4 and live and rng.random() < 0.3:
                ext = int(rng.choice(sorted(live)))   # upsert an existing id
                upserts += 1
            else:
                ext = None
            got = idx.insert(ds.x_sparse[src], ds.x_dense[src], ids=ext)
            live[int(got[0])] = src
        else:
            ext = int(rng.choice(sorted(live)))
            assert idx.delete([ext]) == 1
            del live[ext]
            deleted.add(ext)
        if t % 9 == 0:
            check_search()
    check_search()

    # fold down and rebuild from scratch on the same survivors (retrain=True
    # pins the full-rebuild policy: merge compaction keeps frozen encodings
    # and is only RELAXED-equivalent — covered by its own suite below)
    compacted = idx.compact(retrain=True)
    xs, xd, ids = idx.mutable_state.survivors()
    assert set(ids) == set(live)
    scratch = HybridIndex.build(xs, xd, params)

    r_stream = compacted.search(ds.q_sparse, ds.q_dense, h=10)
    r_scratch = scratch.search(ds.q_sparse, ds.q_dense, h=10)
    np.testing.assert_array_equal(r_stream.ids, ids[r_scratch.ids])
    np.testing.assert_array_equal(r_stream.scores, r_scratch.scores)


@settings(max_examples=3, deadline=None)
@given(st.integers(0, 9999))
def test_equivalence_ref_even_k(seed):
    """compact() ≡ rebuild: ref backend, even K."""
    _check_equivalence("ref", 4, 8, seed)


@settings(max_examples=3, deadline=None)
@given(st.integers(0, 9999))
def test_equivalence_ref_odd_k(seed):
    """compact() ≡ rebuild: ref backend, odd K."""
    _check_equivalence("ref", 3, 12, seed)


@settings(max_examples=3, deadline=None)
@given(st.integers(0, 9999))
def test_equivalence_pallas_even_k(seed):
    """compact() ≡ rebuild: pallas backend, even K."""
    _check_equivalence("pallas", 4, 8, seed)


@settings(max_examples=3, deadline=None)
@given(st.integers(0, 9999))
def test_equivalence_pallas_odd_k(seed):
    """compact() ≡ rebuild: pallas backend, odd K."""
    _check_equivalence("pallas", 3, 12, seed)


@settings(max_examples=3, deadline=None)
@given(st.integers(0, 9999))
def test_equivalence_packed_even_k(seed):
    """compact() ≡ rebuild: packed 4-bit codes, even K."""
    _check_equivalence("pallas-packed", 4, 8, seed)


@settings(max_examples=3, deadline=None)
@given(st.integers(0, 9999))
def test_equivalence_packed_odd_k(seed):
    """compact() ≡ rebuild: packed codes with the odd-K phantom nibble."""
    _check_equivalence("pallas-packed", 3, 12, seed)


# -- merge-compaction relaxed-equivalence property ---------------------------

def _frozen_oracle(qs, qd, xs, xd, merged):
    """Host-side oracle for the merged generation's full-refinement score of
    every survivor row: the exact sparse dot RESTRICTED to the frozen
    compact column space, plus the dot against the frozen PQ + int8-residual
    dense reconstruction.  Recomputes the encode exactly as merge compaction
    does (deterministic argmin over unchanged codebooks, frozen scalar
    grid), so any gap beyond float-accumulation noise is a merge bug."""
    cols = np.asarray(merged.cols.global_ids)
    sparse = np.asarray((qs[:, cols] @ xs[:, cols].T).todense())
    codes = encode_rows(xd, merged.codebooks, pack=False)
    recon = np.asarray(pq_decode(codes, merged.codebooks))
    scale = np.asarray(merged.dense_residual.scale)
    zero = np.asarray(merged.dense_residual.zero)
    resq = scalar_quantize_rows(xd - recon, scale, zero)
    deq = (resq.astype(np.float32) + 128.0) * scale + zero
    return sparse + qd @ (recon + deq).T                      # (Q, n)


def _check_merge_equivalence(backend: str, k: int, d_dense: int, seed: int):
    """Random insert/upsert/delete interleaving with MERGE compactions
    (``retrain=False``) folded mid-stream; every intermediate search must
    respect tombstones, and the final merged generation must be
    RELAXED-equivalent to a scratch rebuild on the same survivors:

    * full-depth refined scores match the frozen-encoding oracle to float
      tolerance (the merge represents every row losslessly WITHIN the
      frozen artifact space);
    * with tau = the measured max |refined - exact| per index, every id
      whose exact score clears the h-th exact score by 2*tau appears in
      that index's top-h, and every served id's exact score is within
      2*tau of the h-th (the standard perturbation bound — "same top-k ids
      modulo ties within encoding tolerance").
    """
    ds = _cached_dataset(d_dense)
    params = _params(backend, k)
    idx = _build_mutable(ds, params)

    rng = np.random.default_rng(seed)
    live = {i: i for i in range(N0)}
    deleted: set[int] = set()
    pool = list(range(N0, N_POOL))
    n_inserts, n_deletes, n_merges, n_upserts = 14, 10, 2, 3
    ops = ["ins"] * n_inserts + ["del"] * n_deletes + ["merge"] * n_merges
    rng.shuffle(ops)
    # exactly n_upserts of the inserts re-use a live id, so the survivor
    # count is the same for every seed (keeps engine shapes stable)
    upsert_at = set(rng.choice(n_inserts, size=n_upserts, replace=False))

    def check_search():
        r = idx.search(ds.q_sparse, ds.q_dense, h=8)
        for row in r.ids:
            real = row[row >= 0]
            assert len(set(real)) == len(real), "duplicate ids in one result"
            for e in real:
                assert e not in deleted, "tombstoned id served"
                assert int(e) in live, "unknown id served"

    ins_seen = 0
    for t, op in enumerate(ops):
        if op == "merge":
            idx = idx.compact(retrain=False)
            check_search()
        elif op == "ins":
            src = pool.pop(0)
            ext = (int(rng.choice(sorted(live)))
                   if ins_seen in upsert_at else None)
            ins_seen += 1
            got = idx.insert(ds.x_sparse[src], ds.x_dense[src], ids=ext)
            live[int(got[0])] = src
        else:
            ext = int(rng.choice(sorted(live)))
            assert idx.delete([ext]) == 1
            del live[ext]
            deleted.add(ext)
        if t % 7 == 0:
            check_search()
    check_search()

    merged = idx.compact(retrain=False)
    xs, xd, ids = idx.mutable_state.survivors()
    assert set(ids) == set(live)
    scratch = HybridIndex.build(xs, xd, params)

    n = xs.shape[0]
    assert n == N0 + n_inserts - n_upserts - n_deletes   # shape-stable
    qs, qd = ds.q_sparse, ds.q_dense
    xd32 = np.asarray(xd, np.float32)
    exact = np.asarray((qs @ xs.T).todense()) + qd @ xd32.T
    pred = _frozen_oracle(qs, qd, xs, xd32, merged)
    id_to_col = {int(e): j for j, e in enumerate(ids)}

    # full refinement depth: every survivor's refined score comes back
    r_m = merged.search(qs, qd, h=n)
    r_s = scratch.search(qs, qd, h=n)
    m_ids = np.asarray(r_m.ids)
    s_ids = ids[np.asarray(r_s.ids)]

    h = 10
    for q in range(qs.shape[0]):
        assert {int(e) for e in m_ids[q]} == set(id_to_col), \
            "merged full-depth search lost or duplicated rows"
        assert {int(e) for e in s_ids[q]} == set(id_to_col), \
            "scratch full-depth search lost or duplicated rows"
        cols_m = [id_to_col[int(e)] for e in m_ids[q]]
        cols_s = [id_to_col[int(e)] for e in s_ids[q]]
        sm = np.asarray(r_m.scores[q])
        ss = np.asarray(r_s.scores[q])
        # merge represents rows losslessly within the frozen space
        np.testing.assert_allclose(sm, pred[q, cols_m], rtol=2e-3, atol=2e-2)
        # perturbation-bound top-k agreement against the exact scores
        kth = np.sort(exact[q])[::-1][h - 1]
        for got_ids, got_scores, got_cols, label in (
                (m_ids[q], sm, cols_m, "merged"),
                (s_ids[q], ss, cols_s, "scratch")):
            tau = np.abs(got_scores - exact[q, got_cols]).max()
            tol = 2.0 * tau + 1e-3
            top = {int(e) for e in got_ids[:h]}
            must = {int(ids[j])
                    for j in np.flatnonzero(exact[q] > kth + tol)}
            assert must <= top, \
                f"{label}: clear exact top-{h} id missing (tau={tau})"
            for e in top:
                assert exact[q, id_to_col[e]] >= kth - tol, \
                    f"{label}: served id {e} not justified (tau={tau})"


@settings(max_examples=2, deadline=None)
@given(st.integers(0, 9999))
def test_merge_equivalence_ref_even_k(seed):
    """merge compact ≈ rebuild (relaxed): ref backend, even K."""
    _check_merge_equivalence("ref", 4, 8, seed)


@settings(max_examples=2, deadline=None)
@given(st.integers(0, 9999))
def test_merge_equivalence_ref_odd_k(seed):
    """merge compact ≈ rebuild (relaxed): ref backend, odd K."""
    _check_merge_equivalence("ref", 3, 12, seed)


@settings(max_examples=2, deadline=None)
@given(st.integers(0, 9999))
def test_merge_equivalence_pallas_even_k(seed):
    """merge compact ≈ rebuild (relaxed): pallas backend, even K."""
    _check_merge_equivalence("pallas", 4, 8, seed)


@settings(max_examples=2, deadline=None)
@given(st.integers(0, 9999))
def test_merge_equivalence_pallas_odd_k(seed):
    """merge compact ≈ rebuild (relaxed): pallas backend, odd K."""
    _check_merge_equivalence("pallas", 3, 12, seed)


@settings(max_examples=2, deadline=None)
@given(st.integers(0, 9999))
def test_merge_equivalence_packed_even_k(seed):
    """merge compact ≈ rebuild (relaxed): packed 4-bit codes, even K."""
    _check_merge_equivalence("pallas-packed", 4, 8, seed)


@settings(max_examples=2, deadline=None)
@given(st.integers(0, 9999))
def test_merge_equivalence_packed_odd_k(seed):
    """merge compact ≈ rebuild (relaxed): packed codes, odd-K phantom
    nibble."""
    _check_merge_equivalence("pallas-packed", 3, 12, seed)


def test_merge_compact_preserves_main_rows_and_ids():
    """Main-resident survivors re-encode IDENTICALLY under merge (frozen
    deterministic encode): codes and residuals of the new generation match
    a retrained rebuild only on the rows the original build encoded — and
    external ids, next_id, and the frozen artifacts all carry over."""
    ds = _cached_dataset(8)
    idx = _build_mutable(ds, _params("ref", 4))
    idx.insert(ds.x_sparse[N0:N0 + 4], ds.x_dense[N0:N0 + 4])
    assert idx.delete([0, 5]) == 2
    merged = idx.compact(retrain=False)
    ms = merged.mutable_state
    assert merged.num_points == N0 + 4 - 2
    assert ms.next_id == idx.mutable_state.next_id
    assert set(np.asarray(ms.ids_built)) == \
        (set(range(N0 + 4)) - {0, 5})
    # frozen artifacts are the SAME objects, not retrained copies
    assert merged.codebooks is idx.codebooks
    assert merged.cols is idx.cols
    # and the merged index still serves mutations
    got = merged.insert(ds.x_sparse[N0 + 4], ds.x_dense[N0 + 4])
    assert int(got[0]) == ms.next_id - 1
    r = merged.search(ds.q_sparse, ds.q_dense, h=5)
    assert (np.asarray(r.ids) >= 0).all()


def test_merge_compact_auto_policy_on_dropped_dims():
    """compact() auto-routes: merge when the frozen column space covered
    everything, full rebuild as soon as ANY mutation dropped sparse nnz
    (delta-buffered or folded by an earlier forced merge)."""
    ds = _cached_dataset(8)
    idx = _build_mutable(ds, _params("ref", 4))
    seen = set(np.asarray(idx.cols.global_ids))
    in_space = next(j for j in range(D_SPARSE) if j in seen)
    row = sp.csr_matrix(([1.0], ([0], [in_space])), shape=(1, D_SPARSE))
    idx.insert(row, np.zeros((1, 8), np.float32))
    auto = idx.compact()                       # nothing dropped -> merge
    assert auto.codebooks is idx.codebooks
    fresh = next(j for j in range(D_SPARSE) if j not in seen)
    row2 = sp.csr_matrix(([1.0], ([0], [fresh])), shape=(1, D_SPARSE))
    auto.insert(row2, np.zeros((1, 8), np.float32))
    assert auto.mutable_state.delta.dropped_nnz == 1
    retrained = auto.compact()                 # dropped nnz -> rebuild
    assert retrained.codebooks is not auto.codebooks
    assert retrained.mutable_state.main_dropped_nnz == 0
    # forced merge instead would carry the debt forward on the new state
    forced = auto.compact(retrain=False)
    assert forced.mutable_state.main_dropped_nnz == 1
    assert forced.mutable_state.delta.dropped_nnz == 0
    retrained2 = forced.compact()              # debt still forces rebuild
    assert retrained2.codebooks is not forced.codebooks


# -- delta shard unit coverage ----------------------------------------------

@pytest.fixture(scope="module")
def small_mutable():
    ds = _cached_dataset(8)
    return ds, _build_mutable(ds, _params("ref", 4))


def test_fresh_mutable_matches_plain_build(small_mutable):
    """An untouched mutable index returns the plain build's exact results
    (ids default to build-row positions)."""
    ds, idx = small_mutable
    plain = HybridIndex.build(ds.x_sparse[:N0], ds.x_dense[:N0],
                              _params("ref", 4))
    a = idx.search(ds.q_sparse, ds.q_dense, h=10)
    b = plain.search(ds.q_sparse, ds.q_dense, h=10)
    np.testing.assert_array_equal(a.ids, b.ids)
    np.testing.assert_array_equal(a.scores, b.scores)


def test_insert_is_searchable_and_delete_tombstones():
    """A dominant inserted row becomes top-1 immediately; deleting it (and a
    main row) removes both from every later result."""
    ds = _cached_dataset(8)
    idx = _build_mutable(ds, _params("ref", 4))
    new = idx.insert(ds.q_sparse[0] * 1e3, ds.q_dense[0])
    r = idx.search(ds.q_sparse, ds.q_dense, h=5)
    assert r.ids[0, 0] == new[0]
    victim = int(r.ids[0, 1])
    assert idx.delete([new[0], victim]) == 2
    r2 = idx.search(ds.q_sparse, ds.q_dense, h=5)
    assert new[0] not in r2.ids and victim not in r2.ids


def test_upsert_replaces_row():
    """Re-inserting an existing external id supersedes the old row — the new
    content is served under the same id, with no duplicates."""
    ds = _cached_dataset(8)
    idx = _build_mutable(ds, _params("ref", 4))
    ext = 7
    idx.insert(ds.q_sparse[1] * 1e3, ds.q_dense[1], ids=[ext])
    r = idx.search(ds.q_sparse, ds.q_dense, h=8)
    assert r.ids[1, 0] == ext
    for row in r.ids:
        assert len(set(row[row >= 0])) == len(row[row >= 0])
    # upsert the upsert: still exactly one copy, now dominant for query 2
    idx.insert(ds.q_sparse[2] * 1e3, ds.q_dense[2], ids=[ext])
    r2 = idx.search(ds.q_sparse, ds.q_dense, h=8)
    assert r2.ids[2, 0] == ext
    assert (r2.ids[1] == ext).sum() <= 1


def test_delta_capacity_doubles_and_preserves_rows():
    """Inserting past the initial capacity doubles the mirrors; every live
    row stays searchable and the capacity stays a power-of-two multiple."""
    ds = _cached_dataset(8)
    idx = _build_mutable(ds, _params("ref", 4))
    st_ = idx.mutable_state
    cap0 = st_.delta.capacity
    m = cap0 + 3
    rows = sp.vstack([ds.q_sparse[0] * 1e3] * m).tocsr()
    dense = np.tile(ds.q_dense[0], (m, 1))
    ids = idx.insert(rows, dense)
    assert st_.delta.capacity >= m
    assert st_.delta.capacity % cap0 == 0
    r = idx.search(ds.q_sparse, ds.q_dense, h=m + 2)
    assert set(ids) <= set(r.ids[0])


def test_failed_upsert_leaves_old_row_intact():
    """REGRESSION: a rejected insert (bad width, mismatched rows) must not
    tombstone the rows it would have upserted — retire-after-encode."""
    ds = _cached_dataset(8)
    idx = _build_mutable(ds, _params("ref", 4))
    before = idx.search(ds.q_sparse, ds.q_dense, h=5)
    ext = int(before.ids[0, 0])
    with pytest.raises(ValueError, match="dense width"):
        idx.insert(ds.q_sparse[0], np.zeros((1, 9), np.float32), ids=[ext])
    with pytest.raises(ValueError, match="row-count mismatch"):
        idx.insert(ds.q_sparse[0], ds.q_dense[:2], ids=[ext])
    assert idx.delta_version == 0
    after = idx.search(ds.q_sparse, ds.q_dense, h=5)
    np.testing.assert_array_equal(after.ids, before.ids)
    np.testing.assert_array_equal(after.scores, before.scores)


def test_delta_rejects_duplicate_batch_ids():
    ds = _cached_dataset(8)
    idx = _build_mutable(ds, _params("ref", 4))
    rows = sp.vstack([ds.q_sparse[0], ds.q_sparse[1]]).tocsr()
    with pytest.raises(ValueError, match="duplicate external ids"):
        idx.insert(rows, ds.q_dense[:2], ids=[5, 5])


def test_negative_ids_rejected():
    """-1 is the merge layer's empty-slot sentinel; external ids must not
    collide with it (insert and build paths both reject)."""
    ds = _cached_dataset(8)
    idx = _build_mutable(ds, _params("ref", 4))
    with pytest.raises(ValueError, match="non-negative"):
        idx.insert(ds.q_sparse[0], ds.q_dense[0], ids=[-1])
    with pytest.raises(ValueError, match="non-negative"):
        HybridIndex.build(ds.x_sparse[:40], ds.x_dense[:40],
                          _params("ref", 4), mutable=True,
                          ext_ids=np.arange(40) - 1)


def test_compaction_never_reuses_deleted_ids():
    """REGRESSION: the auto-id counter survives compaction — deleting the
    highest-assigned id then compacting must not re-mint it for the next
    insert (a resurrected tombstone)."""
    ds = _cached_dataset(8)
    idx = _build_mutable(ds, _params("ref", 4))
    new = idx.insert(ds.q_sparse[0], ds.q_dense[0])     # id N0
    assert idx.delete(new) == 1
    idx2 = idx.compact()
    again = idx2.insert(ds.q_sparse[1], ds.q_dense[1])
    assert again[0] > new[0]


def test_compact_empty_corpus_raises():
    """Deleting every row leaves nothing for the batch build (k-means needs
    data): compact() fails loudly instead of crashing deep in the build."""
    ds = _cached_dataset(8)
    idx = HybridIndex.build(ds.x_sparse[:N0], ds.x_dense[:N0],
                            _params("ref", 4), mutable=True)
    assert idx.delete(list(range(N0))) == N0
    assert idx.mutable_state.live_rows == 0
    with pytest.raises(ValueError, match="empty corpus"):
        idx.compact()


def test_delete_only_mutation_reuses_structural_arrays():
    """A tombstone-only mutation must not re-upload the delta: only the
    mask leaf changes between snapshots."""
    ds = _cached_dataset(8)
    idx = _build_mutable(ds, _params("ref", 4))
    ids = idx.insert(ds.q_sparse[:2] * 1e3, ds.q_dense[:2])
    st_ = idx.mutable_state
    snap1 = st_.delta.snapshot()
    idx.delete([ids[0]])
    snap2 = st_.delta.snapshot()
    assert snap2.arrays.codes is snap1.arrays.codes          # shared
    assert snap2.arrays.valid_mask is not snap1.arrays.valid_mask
    r = idx.search(ds.q_sparse, ds.q_dense, h=5)
    assert ids[0] not in r.ids and ids[1] == r.ids[1, 0]


def test_out_of_space_dims_buffer_until_compaction():
    """Sparse dims the main build never saw can't be scored by the delta
    (frozen compact column space) but live in the retained corpus, so
    compaction makes them searchable."""
    ds = _cached_dataset(8)
    idx = _build_mutable(ds, _params("ref", 4))
    seen = set(np.asarray(idx.cols.global_ids))
    fresh = next(j for j in range(D_SPARSE) if j not in seen)
    row = sp.csr_matrix(([100.0], ([0], [fresh])), shape=(1, D_SPARSE))
    new = idx.insert(row, np.zeros((1, 8), np.float32))
    assert idx.mutable_state.delta.dropped_nnz == 1
    q = row  # query exactly on the unseen dim
    r = idx.search(q, np.zeros((1, 8), np.float32), h=3)
    assert r.scores[0, 0] < 100.0 * 100.0   # not scorable pre-compaction
    idx2 = idx.compact()
    r2 = idx2.search(q, np.zeros((1, 8), np.float32), h=3)
    assert r2.ids[0, 0] == new[0]
    assert r2.scores[0, 0] == pytest.approx(100.0 * 100.0, rel=1e-3)


def test_tombstone_mask_values():
    m = np.asarray(tombstone_mask(8, 5, np.array(
        [False, True, False, False, True, False, False, False])))
    assert list(np.isneginf(m)) == [False, True, False, False, True,
                                    True, True, True]
    assert (m[~np.isneginf(m)] == 0.0).all()


def test_delta_postings_growth_padding_and_spill():
    dp = DeltaPostings(d_active=4, l_max=2, l_cap=4)
    assert dp.append(0, [1, 2], [0.5, 0.25])[0].size == 0
    dp.append(1, [1], [1.0])
    dp.append(2, [1], [2.0])          # dim 1 overflows l_max=2 -> doubles
    assert dp.l_max == 4
    dp.append(3, [1], [3.0])          # dim 1 now full at l_cap=4
    sd, sv = dp.append(4, [1, 3], [4.0, 0.5])   # dim 1 spills, dim 3 fits
    assert list(sd) == [1] and list(sv) == [4.0]
    assert dp.l_max == 4              # cap held: no further growth
    inv = dp.to_padded(num_points=8)
    rows = np.asarray(inv.rows)
    assert rows.shape == (4, 4)
    assert list(rows[1]) == [0, 1, 2, 3]
    assert rows[0, 0] == 8            # empty slots use the sentinel
    assert rows[3, 0] == 4
    assert inv.num_points == 8


def test_delta_spill_is_scored_exactly():
    """Entries past the postings cap flow through the pass-3 rows: a dim
    hot across many delta rows still scores exactly (h == capacity)."""
    ds = _cached_dataset(8)
    idx = _build_mutable(ds, _params("ref", 4))
    st_ = idx.mutable_state
    hot = int(np.asarray(idx.cols.global_ids)[0])
    m = st_.delta._postings.l_cap + 4         # force spill on the hot dim
    rows = sp.csr_matrix((np.full(m, 2.0), (np.arange(m), np.full(m, hot))),
                         shape=(m, D_SPARSE))
    ids = idx.insert(rows, np.zeros((m, 8), np.float32))
    assert st_.delta._rmax >= 1
    assert (np.asarray(st_.delta._row_cols[: st_.delta.count]) <
            idx.cols.num_active).any()        # something actually spilled
    q = sp.csr_matrix(([1.0], ([0], [hot])), shape=(1, D_SPARSE))
    r = idx.search(q, np.zeros((1, 8), np.float32), h=m)
    got = {int(e): s for e, s in zip(r.ids[0], r.scores[0]) if e in set(ids)}
    assert len(got) == m                      # every inserted row found
    for s in got.values():                    # 1.0 * 2.0 exactly, all rows
        assert s == pytest.approx(2.0, abs=1e-4)


def test_encode_rows_matches_batch_encode():
    """encode-on-insert against frozen codebooks == batch pq_encode, and the
    packed form == pack_codes of it (odd K -> phantom nibble)."""
    ds = _cached_dataset(12)
    for k in (3, 4):
        idx = _build_mutable(ds, _params("ref", k))
        xd = ds.x_dense[N0:N0 + 5]
        ref = np.asarray(pq_encode(xd, idx.codebooks))
        np.testing.assert_array_equal(
            encode_rows(xd, idx.codebooks, pack=False), ref)
        np.testing.assert_array_equal(
            encode_rows(xd, idx.codebooks, pack=True), pack_codes(ref))


def test_scalar_quantize_rows_matches_frozen_grid():
    """Row quantization with frozen scale/zero reproduces scalar_quantize
    bit-for-bit on the rows that defined the grid."""
    rng = np.random.default_rng(0)
    x = rng.normal(size=(32, 6)).astype(np.float32)
    sq = scalar_quantize(x)
    rows = scalar_quantize_rows(x, np.asarray(sq.scale), np.asarray(sq.zero))
    np.testing.assert_array_equal(rows, np.asarray(sq.q))


def test_valid_mask_blocks_dead_slots_in_engine():
    """The -inf mask keeps tombstoned/empty delta slots out of the top-k of
    EVERY pass — even when the requested h exceeds the live count."""
    ds = _cached_dataset(8)
    idx = _build_mutable(ds, _params("ref", 4))
    st_ = idx.mutable_state
    idx.insert(ds.q_sparse[0] * 1e3, ds.q_dense[0])
    idx.insert(ds.q_sparse[1] * 1e3, ds.q_dense[1])
    idx.delete([N0])                       # tombstone the first delta slot
    snap = st_.delta.snapshot()
    assert snap.live == 1 and snap.count == 2
    eng = ScoringEngine(arrays=snap.arrays, backend=idx.engine.backend)
    import jax.numpy as jnp
    from repro.core.sparse_index import sparse_queries_to_padded
    qd, qv = sparse_queries_to_padded(ds.q_sparse, idx.cols, nq_max=256)
    s, pos, _ = eng.search(jnp.asarray(qd), jnp.asarray(qv),
                           jnp.asarray(ds.q_dense), h=snap.capacity,
                           alpha=20, beta=5)
    s, pos = np.asarray(s), np.asarray(pos)
    finite = np.isfinite(s)
    assert finite.sum(axis=1).max() == 1       # only the live slot
    assert set(pos[finite]) == {1}
