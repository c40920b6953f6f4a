"""QueryService (serve/query_service.py; DESIGN.md §5, §6): bucketed
micro-batching bounds the jit cache, the LRU result cache counts exactly,
refresh() is consistent with exactly one index generation and donates the
retired buffers, the shard fan-out matches the single-device engine, and the
streaming mutation path (insert/delete/compaction) never serves tombstoned,
duplicated, or stale-cached results — including under threaded load."""

import dataclasses
import threading
import time

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.distributed import split_index_arrays
from repro.core.engine import query_fingerprint, release_index_arrays
from repro.core.hybrid import HybridIndex, HybridIndexParams
from repro.core.sparse_index import sparse_queries_to_padded
from repro.serve import QueryService

PARAMS = HybridIndexParams(keep_top=48, head_dims=48, kmeans_iters=6)


@pytest.fixture(scope="module")
def served(small_hybrid):
    ds = small_hybrid
    idx = HybridIndex.build(ds.x_sparse, ds.x_dense, PARAMS)
    q_dims, q_vals = sparse_queries_to_padded(ds.q_sparse, idx.cols,
                                              nq_max=idx.params.nq_max)
    return ds, idx, q_dims, q_vals, np.asarray(ds.q_dense, np.float32)


def test_service_matches_hybrid_index(served):
    """Bucketed/cached request path returns the engine's results: ids are
    bit-identical, scores within batch-padding reduction noise."""
    ds, idx, q_dims, q_vals, q_dense = served
    svc = QueryService(idx.engine, h=10, alpha=20, beta=5, id_map=idx.pi)
    s, ids = svc.search(q_dims, q_vals, q_dense)
    ref = idx.search(ds.q_sparse, ds.q_dense, h=10, alpha=20, beta=5)
    np.testing.assert_array_equal(ids, ref.ids)
    np.testing.assert_allclose(s, ref.scores, rtol=1e-6, atol=1e-6)


def test_single_query_1d_inputs(served):
    """A client sending one unbatched query gets the row-0 result back."""
    ds, idx, q_dims, q_vals, q_dense = served
    svc = QueryService(idx.engine, h=10, id_map=idx.pi)
    batch_s, batch_i = svc.search(q_dims, q_vals, q_dense)
    s, ids = svc.search(q_dims[0], q_vals[0], q_dense[0])
    assert s.shape == (1, 10) and ids.shape == (1, 10)
    np.testing.assert_array_equal(ids[0], batch_i[0])


def test_bucketing_bounds_jit_cache(served):
    """A ragged request stream (every batch size 1..max) never pads to more
    than len(buckets) distinct shapes — the declared jit-cache bound."""
    _, idx, q_dims, q_vals, q_dense = served
    svc = QueryService(idx.engine, h=5, buckets=(1, 4, 12), cache_size=0)
    rng = np.random.default_rng(0)
    for _ in range(8):
        q = int(rng.integers(1, q_dims.shape[0] + 1))
        rows = rng.choice(q_dims.shape[0], q, replace=False)
        svc.search(q_dims[rows], q_vals[rows], q_dense[rows])
    info = svc.jit_cache_info()
    assert set(info.batch_shapes) <= {1, 4, 12}
    assert len(info.batch_shapes) <= len(svc.buckets)
    assert info.entries <= info.bound == len(svc.buckets)


def test_oversized_batch_is_chunked(served):
    """Requests above the largest bucket split into largest-bucket chunks
    instead of minting a new shape."""
    ds, idx, q_dims, q_vals, q_dense = served
    svc = QueryService(idx.engine, h=10, buckets=(1, 4), cache_size=0,
                       id_map=idx.pi)
    s, ids = svc.search(q_dims, q_vals, q_dense)   # 12 queries > bucket 4
    assert svc.jit_cache_info().batch_shapes == (4,)
    ref = idx.search(ds.q_sparse, ds.q_dense, h=10, alpha=20, beta=5)
    np.testing.assert_array_equal(ids, ref.ids)


def test_cache_counters_exact_and_eviction(served):
    """LRU behavior to the letter: per-row hit/miss counts, capacity-bounded
    size, FIFO-of-least-recently-used eviction."""
    _, idx, q_dims, q_vals, q_dense = served
    svc = QueryService(idx.engine, h=5, cache_size=4)

    def one(i):
        return svc.search(q_dims[i:i + 1], q_vals[i:i + 1],
                          q_dense[i:i + 1])

    one(0), one(1), one(2)                       # 3 distinct queries: misses
    info = svc.cache_info()
    assert (info.hits, info.misses, info.size) == (0, 3, 3)

    one(1)                                       # repeat: pure hit
    info = svc.cache_info()
    assert (info.hits, info.misses) == (1, 3)

    one(3), one(4)                               # 5th distinct query evicts
    info = svc.cache_info()                      # the LRU entry (query 0)
    assert (info.size, info.capacity, info.evictions) == (4, 4, 1)

    one(0)                                       # evicted => miss again
    assert svc.cache_info().misses == 6
    one(4)                                       # still resident => hit
    assert svc.cache_info().hits == 2
    assert svc.cache_info().hit_rate == 2 / 8


def test_cache_disabled(served):
    """cache_size=0 bypasses the cache entirely (misses still counted)."""
    _, idx, q_dims, q_vals, q_dense = served
    svc = QueryService(idx.engine, h=5, cache_size=0)
    svc.search(q_dims, q_vals, q_dense)
    svc.search(q_dims, q_vals, q_dense)
    info = svc.cache_info()
    assert info.hits == 0 and info.size == 0
    assert info.misses == 2 * q_dims.shape[0]


def test_fingerprint_distinguishes_params(served):
    """The cache key covers search params and index generation — h=5 and
    h=10 results for the same query must not collide."""
    _, idx, q_dims, q_vals, q_dense = served
    a = query_fingerprint(q_dims[0], q_vals[0], q_dense[0], 5, 20, 5, 0)
    b = query_fingerprint(q_dims[0], q_vals[0], q_dense[0], 10, 20, 5, 0)
    c = query_fingerprint(q_dims[0], q_vals[0], q_dense[0], 5, 20, 5, 1)
    assert len({a, b, c}) == 3
    svc = QueryService(idx.engine, cache_size=16)
    s5, _ = svc.search(q_dims[:1], q_vals[:1], q_dense[:1], h=5)
    s10, _ = svc.search(q_dims[:1], q_vals[:1], q_dense[:1], h=10)
    assert s5.shape == (1, 5) and s10.shape == (1, 10)
    assert svc.cache_info().hits == 0


def test_sharded_fanout_matches_single_device(served):
    """Fan-out over 4 per-shard engines with full per-shard refinement
    returns bit-identical top-k ids to the unsharded engine (scores to
    kernel-accumulation noise) — the §7.2 merge done on host."""
    ds, idx, q_dims, q_vals, q_dense = served
    # alpha*h covers every local row => per-shard refinement is exact
    n_local = idx.num_points // 4
    alpha = beta = n_local // 10 + 1
    ref_svc = QueryService(idx.engine, h=10, alpha=alpha, beta=beta,
                           cache_size=0, id_map=idx.pi)
    fan = QueryService(idx.engine, h=10, alpha=alpha, beta=beta,
                       cache_size=0, num_shards=4, id_map=idx.pi)
    ref_s, ref_i = ref_svc.search(q_dims, q_vals, q_dense)
    s, ids = fan.search(q_dims, q_vals, q_dense)
    np.testing.assert_array_equal(ids, ref_i)
    np.testing.assert_allclose(s, ref_s, rtol=1e-5, atol=1e-5)


def test_split_index_arrays_shapes(served):
    """The fan-out entry point slices every row-parallel structure and
    localizes the inverted index; column-space structures are shared."""
    _, idx, *_ = served
    arr = idx.engine.arrays
    shards, offsets = split_index_arrays(arr, 4)
    n_local = arr.num_points // 4
    assert list(offsets) == [0, n_local, 2 * n_local, 3 * n_local]
    for s in shards:
        assert s.num_points == n_local
        assert s.codes.shape[0] == n_local
        assert s.dense_residual.q.shape[0] == n_local
        assert s.sparse_residual.cols.shape[0] == n_local
        assert int(s.inv_index.rows.max()) <= n_local
        assert s.codebooks is arr.codebooks
        assert s.head_pos is arr.head_pos
    with pytest.raises(ValueError, match="equal shards"):
        split_index_arrays(arr, 7)


def test_refresh_mid_stream_consistency(small_hybrid):
    """Results during a refresh are consistent with exactly ONE of the two
    index generations; requests after refresh() returns see the new one;
    the retired generation's buffers are donated once idle."""
    ds = small_hybrid
    idx_a = HybridIndex.build(ds.x_sparse, ds.x_dense, PARAMS)
    idx_b = HybridIndex.build(ds.x_sparse, ds.x_dense,
                              dataclasses.replace(PARAMS, seed=11))
    q_dims, q_vals = sparse_queries_to_padded(ds.q_sparse, idx_a.cols,
                                              nq_max=idx_a.params.nq_max)
    q_dense = np.asarray(ds.q_dense, np.float32)

    # deterministic per-generation references through identical bucketing
    ref_a = QueryService(idx_a.engine, h=10, cache_size=0).search(
        q_dims, q_vals, q_dense)
    ref_b = QueryService(idx_b.engine, h=10, cache_size=0).search(
        q_dims, q_vals, q_dense)
    assert not np.array_equal(ref_a[0], ref_b[0])   # generations distinguishable

    svc = QueryService(idx_a.engine, h=10, cache_size=64)
    futures = [svc.submit(q_dims, q_vals, q_dense) for _ in range(4)]
    svc.refresh(idx_b.engine)
    futures += [svc.submit(q_dims, q_vals, q_dense) for _ in range(2)]
    results = [f.result() for f in futures]
    for s, ids in results:
        from_a = np.array_equal(s, ref_a[0]) and np.array_equal(ids, ref_a[1])
        from_b = np.array_equal(s, ref_b[0]) and np.array_equal(ids, ref_b[1])
        assert from_a != from_b                     # exactly one generation
    # post-refresh submissions (and any later search) see generation B only
    s, ids = svc.search(q_dims, q_vals, q_dense)
    np.testing.assert_array_equal(s, ref_b[0])
    for s, ids in results[4:]:
        np.testing.assert_array_equal(s, ref_b[0])

    # donation: retired generation's device buffers are gone, new ones alive
    assert idx_a.engine.arrays.codes.is_deleted()
    assert not idx_b.engine.arrays.codes.is_deleted()
    svc.close()


def test_release_index_arrays_keep(small_hybrid):
    """The donation hook skips every leaf shared with a kept pytree."""
    ds = small_hybrid
    idx = HybridIndex.build(ds.x_sparse, ds.x_dense,
                            dataclasses.replace(PARAMS, kmeans_iters=2))
    arr = idx.engine.arrays
    shards, _ = split_index_arrays(arr, 2)
    # shards share codebooks with the parent: keeping the parent must
    # protect those leaves while the shard's own slices are freed
    deleted = release_index_arrays(shards[0], keep=[arr])
    assert deleted > 0
    assert shards[0].codes.is_deleted()
    assert not arr.codes.is_deleted()
    assert not shards[0].codebooks.centers.is_deleted()   # shared => kept


# -- streaming mutation (DESIGN.md §6) ---------------------------------------

MUT_PARAMS = HybridIndexParams(keep_top=32, head_dims=24, kmeans_iters=4)


@pytest.fixture()
def mut_served():
    """Small mutable index + service (fresh per test: mutation is stateful)."""
    from repro.data import make_hybrid_dataset
    ds = make_hybrid_dataset(num_points=800, num_queries=8, d_sparse=2000,
                             d_dense=16, nnz_per_row=24, seed=21)
    idx = HybridIndex.build(ds.x_sparse, ds.x_dense, MUT_PARAMS,
                            mutable=True)
    return ds, idx


def test_insert_invalidates_result_cache(mut_served):
    """REGRESSION (ISSUE 4 satellite): the cache fingerprint must cover the
    delta-shard mutation version, not just the main generation — a warm
    query re-executes after insert() instead of serving pre-insert results."""
    ds, idx = mut_served
    svc = QueryService(index=idx, h=5, cache_size=64, auto_compact=False)
    s0, i0 = svc.search_sparse(ds.q_sparse[:1], ds.q_dense[:1])
    svc.search_sparse(ds.q_sparse[:1], ds.q_dense[:1])
    assert svc.cache_info().hits == 1
    new = svc.insert(ds.q_sparse[0] * 1e3, ds.q_dense[0])
    s1, i1 = svc.search_sparse(ds.q_sparse[:1], ds.q_dense[:1])
    info = svc.cache_info()
    assert (info.hits, info.misses) == (1, 2)     # post-insert lookup missed
    assert i1[0, 0] == new[0] and new[0] not in i0
    # delete must invalidate too
    svc.delete(new)
    s2, i2 = svc.search_sparse(ds.q_sparse[:1], ds.q_dense[:1])
    assert svc.cache_info().misses == 3
    assert new[0] not in i2
    svc.close()


def test_service_mutation_matches_core_index(mut_served):
    """The service's delta fan-out + host merge returns exactly what the
    core mutable search returns — single-engine and 4-shard fan-out alike."""
    ds, idx = mut_served
    svc = QueryService(index=idx, h=10, cache_size=0, auto_compact=False)
    svc.insert(ds.q_sparse[:3] * 1e3, ds.q_dense[:3])
    svc.delete([1, 2, 3])
    ref = idx.search(ds.q_sparse, ds.q_dense, h=10)
    s, ids = svc.search_sparse(ds.q_sparse, ds.q_dense)
    np.testing.assert_array_equal(ids, ref.ids)
    np.testing.assert_array_equal(s, ref.scores)
    fan = QueryService(index=idx, h=10, cache_size=0, num_shards=4,
                       auto_compact=False)
    s4, i4 = fan.search_sparse(ds.q_sparse, ds.q_dense)
    np.testing.assert_array_equal(i4, ref.ids)
    np.testing.assert_allclose(s4, ref.scores, rtol=1e-5, atol=1e-5)
    svc.close(); fan.close()


def test_service_compact_preserves_results_and_resets_delta(mut_served):
    """compact() folds the delta through refresh(): same logical results
    (dominant inserts stay top-1, deletes stay gone), delta/tombstone
    counters reset, generation bumped, old buffers donated."""
    ds, idx = mut_served
    svc = QueryService(index=idx, h=5, cache_size=16, auto_compact=False)
    new = svc.insert(ds.q_sparse[:2] * 1e3, ds.q_dense[:2])
    svc.delete([5, 6])
    old_arrays = idx.engine.arrays
    v = svc.compact()
    assert v == svc.version > 0
    st = svc.stats()
    assert st["compactions"] == 1
    assert st["delta_rows"] == 0 and st["deleted_pending"] == 0
    s, ids = svc.search_sparse(ds.q_sparse, ds.q_dense)
    assert ids[0, 0] == new[0] and ids[1, 0] == new[1]
    assert 5 not in ids and 6 not in ids
    assert old_arrays.codes.is_deleted()          # retired gen donated
    # compacting an unmutated index is a no-op
    assert svc.compact() == v
    svc.close()


def test_auto_compaction_triggers_in_background(mut_served):
    """Crossing the compact_min_rows floor spawns the background rebuild;
    the service keeps serving and ends up on a fresh generation with an
    empty delta."""
    ds, idx = mut_served
    svc = QueryService(index=idx, h=5, cache_size=0, auto_compact=True,
                       compact_min_rows=8, compact_ratio=0.0)
    new = svc.insert(ds.x_sparse[:8], ds.x_dense[:8] * 0 + ds.q_dense[0])
    deadline = time.time() + 120
    while svc.stats()["compactions"] == 0 and time.time() < deadline:
        svc.search_sparse(ds.q_sparse[:1], ds.q_dense[:1])  # keep serving
        time.sleep(0.05)
    st = svc.stats()
    assert st["compactions"] >= 1 and st["delta_rows"] == 0
    s, ids = svc.search_sparse(ds.q_sparse, ds.q_dense, h=20)
    assert set(new) <= set(np.asarray(ids).ravel()) | set()
    svc.close()


def test_refresh_rejected_on_mutable_service(mut_served):
    """External refresh() would pair the live delta (sharing the retired
    generation's device buffers and column space) with a foreign main
    index — the mutable path must route through compact() instead."""
    ds, idx = mut_served
    svc = QueryService(index=idx, h=5, cache_size=0, auto_compact=False)
    svc.insert(ds.q_sparse[0], ds.q_dense[0])
    with pytest.raises(ValueError, match="compact"):
        svc.refresh(idx.engine)
    svc.close()


def test_mutation_under_load(mut_served):
    """Stress: threaded searches racing insert()/delete()/background
    compaction must never observe a tombstoned id (deleted before the
    search started), a duplicate id within one result row, or a
    non-monotone score row (the mixed-generation smell) — extends the
    refresh old-xor-new consistency test to continuous mutation."""
    ds, idx = mut_served
    svc = QueryService(index=idx, h=10, cache_size=0, auto_compact=True,
                       compact_min_rows=20, compact_ratio=0.0)
    deleted_log: set[int] = set()
    log_lock = threading.Lock()
    stop = threading.Event()
    failures: list[str] = []

    def searcher():
        qi = 0
        while not stop.is_set():
            with log_lock:
                dead_before = set(deleted_log)
            s, ids = svc.search_sparse(ds.q_sparse[qi % 8: qi % 8 + 1],
                                       ds.q_dense[qi % 8: qi % 8 + 1])
            qi += 1
            row = ids[0]
            real = row[row >= 0]
            if len(set(real)) != len(real):
                failures.append(f"duplicate ids: {row}")
            if set(int(e) for e in real) & dead_before:
                failures.append(f"tombstoned id served: {row}")
            srow = s[0][np.isfinite(s[0])]
            if np.any(np.diff(srow) > 1e-4):
                failures.append(f"non-monotone scores: {s[0]}")

    threads = [threading.Thread(target=searcher) for _ in range(3)]
    for t in threads:
        t.start()
    rng = np.random.default_rng(3)
    known = list(range(800))
    try:
        for i in range(30):
            src = int(rng.integers(0, 800))
            new = svc.insert(ds.x_sparse[src], ds.x_dense[src])
            known.append(int(new[0]))
            if i % 4 == 3 and known:
                victim = known.pop(int(rng.integers(0, len(known))))
                if svc.delete([victim]):
                    with log_lock:
                        deleted_log.add(victim)
            time.sleep(0.01)
        svc.compact()
    finally:
        stop.set()
        for t in threads:
            t.join()
        svc.close()
    assert not failures, failures[:5]
    # post-quiesce: every tombstoned id stays gone
    s, ids = svc.search_sparse(ds.q_sparse, ds.q_dense, h=20)
    assert not (set(np.asarray(ids).ravel()) & deleted_log)
    assert svc.stats()["compactions"] >= 1


def test_service_merge_compact_preserves_results_and_resets_delta(mut_served):
    """compact(retrain=False) folds the delta via MERGE compaction through
    the same refresh() swap: dominant inserts stay served, deletes stay
    gone, counters reset, the frozen codebooks carry over unchanged, and
    the retired generation's own buffers are donated while the leaves the
    merged generation shares (codebooks, scalar grid) survive."""
    ds, idx = mut_served
    svc = QueryService(index=idx, h=5, cache_size=16, auto_compact=False)
    new = svc.insert(ds.q_sparse[:2] * 1e3, ds.q_dense[:2])
    svc.delete([5, 6])
    old_arrays = idx.engine.arrays
    old_codebooks = idx.codebooks
    v = svc.compact(retrain=False)
    assert v == svc.version > 0
    st = svc.stats()
    assert st["compactions"] == 1
    assert st["delta_rows"] == 0 and st["deleted_pending"] == 0
    assert svc._index.codebooks is old_codebooks   # frozen artifacts kept
    s, ids = svc.search_sparse(ds.q_sparse, ds.q_dense)
    assert ids[0, 0] == new[0] and ids[1, 0] == new[1]
    assert 5 not in ids and 6 not in ids
    assert old_arrays.codes.is_deleted()           # retired gen donated...
    assert not old_arrays.codebooks.centers.is_deleted()   # ...shared kept
    svc.close()


def test_mutation_under_load_with_merge_compaction(mut_served):
    """Stress (mirrors test_mutation_under_load, merge policy): threaded
    searches racing insert()/delete()/background MERGE compaction
    (compact_retrain=False) must never observe a tombstoned id (deleted
    before the search started), a duplicate id within one result row, or a
    non-monotone score row (the mixed-generation smell) — and the folds
    that happened must really have taken the merge path (frozen codebooks
    identical across every generation swap)."""
    ds, idx = mut_served
    codebooks0 = idx.codebooks
    svc = QueryService(index=idx, h=10, cache_size=0, auto_compact=True,
                       compact_min_rows=20, compact_ratio=0.0,
                       compact_retrain=False)
    deleted_log: set[int] = set()
    log_lock = threading.Lock()
    stop = threading.Event()
    failures: list[str] = []

    def searcher():
        qi = 0
        while not stop.is_set():
            with log_lock:
                dead_before = set(deleted_log)
            s, ids = svc.search_sparse(ds.q_sparse[qi % 8: qi % 8 + 1],
                                       ds.q_dense[qi % 8: qi % 8 + 1])
            qi += 1
            row = ids[0]
            real = row[row >= 0]
            if len(set(real)) != len(real):
                failures.append(f"duplicate ids: {row}")
            if set(int(e) for e in real) & dead_before:
                failures.append(f"tombstoned id served: {row}")
            srow = s[0][np.isfinite(s[0])]
            if np.any(np.diff(srow) > 1e-4):
                failures.append(f"non-monotone scores: {s[0]}")

    threads = [threading.Thread(target=searcher) for _ in range(3)]
    for t in threads:
        t.start()
    rng = np.random.default_rng(7)
    known = list(range(800))
    try:
        for i in range(30):
            src = int(rng.integers(0, 800))
            new = svc.insert(ds.x_sparse[src], ds.x_dense[src])
            known.append(int(new[0]))
            if i % 4 == 3 and known:
                victim = known.pop(int(rng.integers(0, len(known))))
                if svc.delete([victim]):
                    with log_lock:
                        deleted_log.add(victim)
            time.sleep(0.01)
        svc.compact()
    finally:
        stop.set()
        for t in threads:
            t.join()
        svc.close()
    assert not failures, failures[:5]
    # post-quiesce: tombstones stay gone, folds happened, and every one of
    # them was a merge — the original codebooks object is still serving
    s, ids = svc.search_sparse(ds.q_sparse, ds.q_dense, h=20)
    assert not (set(np.asarray(ids).ravel()) & deleted_log)
    assert svc.stats()["compactions"] >= 1
    assert svc._index.codebooks is codebooks0


def test_refresh_version_invalidates_cache(small_hybrid):
    """Cache keys include the generation: a warm query re-executes (miss)
    after refresh instead of serving the old index's result."""
    ds = small_hybrid
    idx_a = HybridIndex.build(ds.x_sparse, ds.x_dense, PARAMS)
    idx_b = HybridIndex.build(ds.x_sparse, ds.x_dense,
                              dataclasses.replace(PARAMS, seed=11))
    q_dims, q_vals = sparse_queries_to_padded(ds.q_sparse, idx_a.cols,
                                              nq_max=idx_a.params.nq_max)
    q_dense = np.asarray(ds.q_dense, np.float32)
    svc = QueryService(idx_a.engine, h=10, cache_size=64)
    svc.search(q_dims[:1], q_vals[:1], q_dense[:1])
    svc.search(q_dims[:1], q_vals[:1], q_dense[:1])
    assert svc.cache_info().hits == 1
    assert svc.refresh(idx_b.engine) == 1
    svc.search(q_dims[:1], q_vals[:1], q_dense[:1])
    info = svc.cache_info()
    assert info.hits == 1 and info.misses == 2


def test_metrics_exact_under_threaded_search(served):
    """Registry-backed counters stay EXACT under threaded load (ISSUE 10
    satellite): N threads race single-row searches through one service;
    afterwards ``serve.requests`` equals the total rows served,
    hits + misses account for every cache lookup, and the span ring holds
    (at most ``keep_traces``) finished ``serve.search`` roots whose qn
    tags also sum to the total."""
    from repro.obs import Observability
    _, idx, q_dims, q_vals, q_dense = served
    svc = QueryService(idx.engine, h=5, cache_size=256,
                       obs=Observability(trace=True, keep_traces=4096))
    n_threads, n_iters = 4, 40
    errors: list[BaseException] = []

    def worker(tid):
        try:
            for i in range(n_iters):
                j = (tid + i) % q_dims.shape[0]
                svc.search(q_dims[j:j + 1], q_vals[j:j + 1],
                           q_dense[j:j + 1])
        except BaseException as e:          # pragma: no cover
            errors.append(e)

    threads = [threading.Thread(target=worker, args=(t,))
               for t in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors, errors
    total = n_threads * n_iters
    snap = svc.metrics()
    assert snap["serve.requests"] == total
    assert snap["serve.cache.hits"] + snap["serve.cache.misses"] == total
    info = svc.cache_info()
    assert (info.hits, info.misses) == (snap["serve.cache.hits"],
                                        snap["serve.cache.misses"])
    # misses are bounded by distinct fingerprints × racing threads (two
    # threads may miss the same cold query before either populates it)
    assert snap["serve.cache.misses"] <= q_dims.shape[0] * n_threads
    assert snap["serve.batches"] == snap["serve.cache.misses"]
    traces = svc.obs.tracer.take()
    roots = [t for t in traces if t["name"] == "serve.search"]
    assert len(roots) == total
    assert sum(t["tags"]["qn"] for t in roots) == total
    assert sum(t["tags"]["cache_hits"] for t in roots) == info.hits


def _children(span: dict, name: str) -> list[dict]:
    return [c for c in span["children"] if c["name"] == name]


@pytest.mark.parametrize("cache_size", [0, 64])
def test_traced_search_names_each_stage(served, cache_size):
    """With tracing on, a request's tree is serve.search -> serve.cache
    (only with the cache on) and one serve.batch per chunk, each batch with
    serve.pad / dispatch / device_wait / merge children in that order and
    inside the batch's time; a batch new to the service is tagged with its
    new programs."""
    from repro.obs import Observability
    _, idx, q_dims, q_vals, q_dense = served
    svc = QueryService(idx.engine, h=5, buckets=(1, 4), cache_size=cache_size,
                       obs=Observability(trace=True))
    svc.search(q_dims[:6], q_vals[:6], q_dense[:6])
    svc.search(q_dims[6:9], q_vals[6:9], q_dense[6:9])
    first, second = svc.obs.tracer.take()
    for root in (first, second):
        assert root["name"] == "serve.search"
        assert len(_children(root, "serve.cache")) == (1 if cache_size else 0)
        assert "queue_s" not in root["tags"]
    batches = _children(first, "serve.batch")
    assert [b["tags"]["rows"] for b in batches] == [4, 2]
    stages = ["serve.pad", "serve.dispatch", "serve.device_wait",
              "serve.merge"]
    for b in batches + _children(second, "serve.batch"):
        assert [c["name"] for c in b["children"]] == stages
        assert all(c["duration_s"] >= 0 for c in b["children"])
        assert sum(c["duration_s"] for c in b["children"]) <= \
            b["duration_s"]
    # the first batch runs (bucket 4, params) first; the second search
    # sends 3 rows to the same bucket
    assert batches[0]["tags"]["new_programs"] == 1
    assert "new_programs" not in _children(second, "serve.batch")[0]["tags"]


def test_submit_stamps_queue_wait_and_counters_add_up(served):
    """``submit`` tags each root with ``queue_s`` (queued -> a worker
    entering ``search``); ``serve.rows`` counts the rows sent to the engine
    and ``serve.bucket_rows`` the padded rows, never fewer; every first-seen
    (bucket, params) program counts once in ``serve.new_programs``."""
    from repro.obs import Observability
    _, idx, q_dims, q_vals, q_dense = served
    svc = QueryService(idx.engine, h=5, buckets=(1, 4, 8), cache_size=0,
                       max_workers=1,
                       obs=Observability(trace=True, keep_traces=64))
    sizes = [1, 3, 5, 3, 8]
    futs, lo = [], 0
    for n in sizes:
        rows = np.arange(lo, lo + n) % q_dims.shape[0]
        futs.append(svc.submit(q_dims[rows], q_vals[rows], q_dense[rows]))
        lo += n
    for f in futs:
        f.result(timeout=120)
    svc.close()
    roots = svc.obs.tracer.take()
    assert len(roots) == len(sizes)
    assert all(r["tags"]["queue_s"] >= 0 for r in roots)
    snap = svc.metrics()
    assert snap["serve.rows"] == sum(sizes)
    assert snap["serve.bucket_rows"] == 1 + 4 + 8 + 4 + 8
    assert snap["serve.bucket_rows"] >= snap["serve.rows"]
    assert snap["serve.new_programs"] == 3 == \
        svc.jit_cache_info().entries


def test_spans_reach_the_profiler_timeline(served, tmp_path):
    """Under ``jax.profiler.trace`` the service's context spans are host
    events of the capture, nested inside the caller's own annotation."""
    from jax.profiler import ProfileData, TraceAnnotation, trace
    from repro.obs import Observability
    _, idx, q_dims, q_vals, q_dense = served
    svc = QueryService(idx.engine, h=5, cache_size=16,
                       obs=Observability(trace=True))
    svc.search(q_dims[:4], q_vals[:4], q_dense[:4])        # compile first
    with trace(str(tmp_path)):
        with TraceAnnotation("bench.search"):
            svc.search(q_dims[4:8], q_vals[4:8], q_dense[4:8])
    path = next(tmp_path.rglob("*.xplane.pb"))
    events = [e for p in ProfileData.from_file(str(path)).planes
              for line in p.lines for e in line.events
              if e.name.startswith(("bench.", "serve."))]
    outer = [e for e in events if e.name == "bench.search"]
    assert len(outer) == 1
    lo, hi = outer[0].start_ns, outer[0].start_ns + outer[0].duration_ns
    inner = {e.name for e in events
             if e.name.startswith("serve.") and lo <= e.start_ns
             and e.start_ns + e.duration_ns <= hi}
    assert inner == {"serve.search", "serve.cache", "serve.batch",
                     "serve.pad", "serve.dispatch", "serve.device_wait",
                     "serve.merge"}
