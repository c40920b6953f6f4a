"""Pruning split (Eq. 6/7) and the TPU sparse scorers."""

import jax.numpy as jnp
import numpy as np
import scipy.sparse as sp
from hypothesis import given, settings, strategies as st

from repro.core import pruning
from repro.core import sparse_index as si


def test_split_is_partition(powerlaw_sparse):
    x = powerlaw_sparse
    ps = pruning.prune_split(x, keep_top=16)
    diff = np.abs(ps.index + ps.residual - x)
    assert diff.max() < 1e-6
    # no entry in both
    overlap = ps.index.multiply(ps.residual)
    assert overlap.nnz == 0


def test_keep_top_respected(powerlaw_sparse):
    ps = pruning.prune_split(powerlaw_sparse, keep_top=16)
    per_dim = np.diff(ps.index.tocsc().indptr)
    # ties at the threshold may exceed keep_top slightly; bound loosely
    assert per_dim.max() <= 16 + 8


def test_inverted_index_scoring_exact(powerlaw_sparse):
    x = powerlaw_sparse
    ps = pruning.prune_split(x, keep_top=32)
    cols, xc = si.build_compact_columns(ps.index)
    inv = si.build_padded_inverted_index(xc)
    rng = np.random.default_rng(0)
    q = sp.csr_matrix(
        (rng.random((4, x.shape[1])) < 0.05).astype(np.float32))
    qd, qv = si.sparse_queries_to_padded(q, cols, nq_max=64)
    scores = np.asarray(si.score_inverted(inv, jnp.asarray(qd),
                                          jnp.asarray(qv)))
    exact = np.asarray((q @ ps.index.T).todense())
    np.testing.assert_allclose(scores, exact, rtol=1e-5, atol=1e-5)


def test_head_block_plus_tail_equals_full(powerlaw_sparse):
    """Head tile block + tail inverted index must reproduce the full pruned
    score exactly (the two TPU paths partition the dims)."""
    from repro.core.hybrid import HybridIndex, HybridIndexParams
    from repro.core.sparse_index import queries_head_dense, score_head_ref

    x = powerlaw_sparse
    ps = pruning.prune_split(x, keep_top=32)
    cols, xc = si.build_compact_columns(x)
    idx_c = x.tocsc()[:, cols.global_ids].tocsr()
    # emulate hybrid.build's split
    from repro.core.cache_sort import dimension_activity
    pruned_c = ps.index.tocsc()[:, cols.global_ids].tocsr()
    act = dimension_activity(pruned_c)
    head_dims = np.sort(np.argsort(-act)[:16]).astype(np.int32)
    head = si.build_tile_sparse_head(pruned_c, head_dims, block_rows=64,
                                     block_cols=64)
    tail = pruned_c.tolil()
    tail[:, head_dims] = 0
    tail = tail.tocsr()
    tail.eliminate_zeros()
    inv = si.build_padded_inverted_index(tail)

    rng = np.random.default_rng(5)
    q = sp.csr_matrix((rng.random((3, x.shape[1])) < 0.05).astype(np.float32))
    qd, qv = si.sparse_queries_to_padded(q, cols, nq_max=64)
    q_head = queries_head_dense(qd, qv, np.asarray(head.head_dims),
                                head.block.shape[1])
    total = (np.asarray(si.score_inverted(inv, jnp.asarray(qd),
                                          jnp.asarray(qv)))
             + np.asarray(score_head_ref(head, jnp.asarray(q_head)))[
                 :, : x.shape[0]])
    exact = np.asarray((q @ ps.index.T).todense())
    np.testing.assert_allclose(total, exact, rtol=1e-4, atol=1e-4)


def test_padded_rows_scoring(powerlaw_sparse):
    x = powerlaw_sparse
    cols, xc = si.build_compact_columns(x)
    rows = si.build_padded_rows(xc)
    rng = np.random.default_rng(2)
    q = sp.csr_matrix((rng.random((2, x.shape[1])) < 0.05).astype(np.float32))
    qd, qv = si.sparse_queries_to_padded(q, cols, nq_max=64)
    # dense query over compact cols + pad slot
    qdense = np.zeros((2, cols.num_active + 1), np.float32)
    for i in range(2):
        for j, v in zip(qd[i], qv[i]):
            if j < cols.num_active:
                qdense[i, j] += v
    cand = jnp.asarray(rng.integers(0, x.shape[0], size=(2, 10)))
    got = np.asarray(si.score_rows(rows, cand, jnp.asarray(qdense)))
    exact_all = np.asarray((q[:, cols.global_ids] @ xc.T).todense())
    want = np.take_along_axis(exact_all, np.asarray(cand), axis=1)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


@settings(max_examples=10, deadline=None)
@given(st.integers(1, 30), st.integers(0, 5000))
def test_property_prune_monotone(keep, seed):
    """Larger keep_top => index keeps at least as many entries."""
    rng = np.random.default_rng(seed)
    x = sp.csr_matrix((rng.random((100, 40)) < 0.2).astype(np.float32)
                      * rng.random((100, 40)).astype(np.float32))
    a = pruning.prune_split(x, keep_top=keep).index.nnz
    b = pruning.prune_split(x, keep_top=keep + 5).index.nnz
    assert b >= a
