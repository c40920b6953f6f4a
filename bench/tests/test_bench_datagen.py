"""The benchmark's generator draws the program's data model.

``bench/datagen.py`` draws in O(nnz) what ``repro.data.make_hybrid_dataset``
draws one Bernoulli per (row, column); at a small size both must give the
same per-column activity, nonzeros per row, value and dense statistics,
within sampling error.
"""

from __future__ import annotations

import math
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [os.path.join(REPO, "bench"), os.path.join(REPO, "src")]

import datagen                                        # noqa: E402
from repro.data import make_hybrid_dataset            # noqa: E402

N, D, ALPHA, NNZ, DD, Q = 20000, 4000, 1.6, 40.0, 32, 400
SPEC = datagen.DataSpec(num_rows=N, d_sparse=D, alpha=ALPHA, nnz_per_row=NNZ,
                        d_dense=DD, dense_rank=8, dense_weight=2.0)


@pytest.fixture(scope="module")
def both():
    prog = make_hybrid_dataset(num_points=N, num_queries=Q, d_sparse=D,
                               d_dense=DD, alpha=ALPHA, nnz_per_row=NNZ,
                               dense_weight=2.0, dense_rank=8, seed=11)
    corpus = datagen.make_corpus(SPEC, 11)
    queries = datagen.make_queries(SPEC, corpus, Q, 11, 0)
    return prog, corpus, queries


def test_column_activity_matches_the_model(both):
    prog, corpus, _ = both
    p = datagen.column_probs(D, ALPHA, NNZ)
    expect = N * p
    sd = np.sqrt(N * p * (1 - p)) + 1e-9
    for x in (prog.x_sparse, corpus.x_sparse):
        counts = np.bincount(x.tocoo().col, minlength=D)
        top = expect > 50
        # full columns are exact; every other heavy column within 5 sd
        assert np.all(np.abs(counts[top] - expect[top]) <= 5 * sd[top] + 1e-6)
        rest, rest_e = counts[~top].sum(), expect[~top].sum()
        assert abs(rest - rest_e) <= 5 * math.sqrt(rest_e)
    # the two generators agree column by column on the heavy columns
    a = np.bincount(prog.x_sparse.tocoo().col, minlength=D)
    b = np.bincount(corpus.x_sparse.tocoo().col, minlength=D)
    top = expect > 50
    assert np.all(np.abs(a[top] - b[top]) <= 5 * np.sqrt(2) * sd[top] + 1e-6)


def test_rows_values_and_dense_match(both):
    prog, corpus, _ = both
    for f in (lambda x: np.diff(x.indptr).mean(),
              lambda x: np.diff(x.indptr).std()):
        a, b = f(prog.x_sparse), f(corpus.x_sparse)
        assert abs(a - b) <= 0.03 * a
    for x in (prog.x_sparse, corpus.x_sparse):
        logs = np.log(x.data)
        assert abs(np.median(logs) - math.log(0.054)) < 0.02
        assert abs(logs.std() - 1.1) < 0.02
        assert x.dtype == np.float32
    na = (prog.x_dense ** 2).sum(1).mean()
    nb = (corpus.x_dense ** 2).sum(1).mean()
    assert abs(na - nb) <= 0.1 * na
    assert corpus.x_dense.dtype == np.float32


def test_queries_match(both):
    prog, corpus, q = both
    a = np.diff(prog.q_sparse.indptr).mean()
    b = np.diff(q.q_sparse.indptr).mean()
    assert abs(a - b) <= 0.1 * a
    noise = q.q_dense - corpus.x_dense[q.src]
    assert abs(noise.std() - 0.2 * 2.0 / math.sqrt(DD)) < 0.01
    # a query keeps the sparse support of the row it perturbs
    for k in range(20):
        row = set(corpus.x_sparse[q.src[k]].indices)
        assert row <= set(q.q_sparse[k].indices)


def test_bernoulli_per_row_including_the_edges():
    """Every row of a column is active with its probability, the first and
    the last row too, and a probability of one fills the column."""
    hits = np.zeros(50)
    for k in range(2000):
        x = datagen.powerlaw_rows(k, 50, 3, 0.5, 1.2, groups=2)
        hits += np.asarray(x[:, 1].todense()).ravel() != 0
    p = datagen.column_probs(3, 0.5, 1.2)[1]
    sd = math.sqrt(2000 * p * (1 - p))
    assert np.all(np.abs(hits - 2000 * p) <= 5 * sd)
    full = datagen.powerlaw_rows(5, 1000, 5, 1.0, 10.0)
    assert full[:, 0].nnz == 1000


def test_same_seed_same_data_and_large_seeds():
    small = datagen.DataSpec(num_rows=500, d_sparse=300, alpha=1.5,
                             nnz_per_row=10, d_dense=8)
    seed = 2 ** 33 + 17
    a = datagen.make_corpus(small, seed)
    b = datagen.make_corpus(small, seed)
    assert (a.x_sparse != b.x_sparse).nnz == 0
    assert np.array_equal(a.x_dense, b.x_dense)
    c = datagen.make_corpus(small, seed + 1)
    assert not np.array_equal(a.x_dense, c.x_dense)


def test_query_keep_keeps_that_many_of_the_row():
    small = datagen.DataSpec(num_rows=2000, d_sparse=500, alpha=0.8,
                             nnz_per_row=60, d_dense=8, query_keep=12,
                             query_extra_nnz=0.0)
    corpus = datagen.make_corpus(small, 3)
    q = datagen.make_queries(small, corpus, 50, 3, 0)
    for k in range(50):
        row = set(corpus.x_sparse[q.src[k]].indices)
        kept = set(q.q_sparse[k].indices)
        assert kept <= row and len(kept) == min(12, len(row))
