"""The plain reference against brute force, and what ``compare`` counts."""

from __future__ import annotations

import os
import sys

import numpy as np
import pytest
import scipy.sparse as sp

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(REPO, "bench"))

import reference                                      # noqa: E402


@pytest.fixture(scope="module")
def data():
    rng = np.random.default_rng(0)
    x = sp.random(3000, 500, density=0.02, format="csr", random_state=1,
                  dtype=np.float32)
    q = sp.random(40, 500, density=0.05, format="csr", random_state=2,
                  dtype=np.float32)
    xd = rng.standard_normal((3000, 24)).astype(np.float32)
    qd = rng.standard_normal((40, 24)).astype(np.float32)
    exact = (q.astype(np.float64) @ x.T.astype(np.float64)).toarray() \
        + qd.astype(np.float64) @ xd.T.astype(np.float64)
    return x, xd, q, qd, exact


def test_exact_topk_is_brute_force(data):
    x, xd, q, qd, exact = data
    s, i = reference.exact_topk(x, xd, q, qd, 10, block_rows=512)
    want = np.argsort(-exact, axis=1)[:, :10]
    assert np.array_equal(i, want)
    assert np.allclose(s, np.take_along_axis(exact, want, 1), rtol=1e-5,
                       atol=1e-5)
    pair = reference.pair_scores(x, xd, q, qd, want)
    assert np.allclose(pair, np.take_along_axis(exact, want, 1), rtol=1e-12)


def test_int4_control_rounds_the_dense_side(data):
    x, xd, q, qd, exact = data
    x4 = reference.quantize_dense(xd, 4)
    for j in range(xd.shape[1]):
        assert len(np.unique(x4[:, j])) <= 16
    assert np.abs(x4 - xd).max() <= (xd.max(0) - xd.min(0)).max() / 30 + 1e-6
    s, i = reference.exact_topk(x, xd, q, qd, 10, dense_bits=4,
                                block_rows=512)
    rounded = (q.astype(np.float64) @ x.T.astype(np.float64)).toarray() \
        + qd.astype(np.float64) @ x4.T.astype(np.float64)
    assert np.allclose(s, np.take_along_axis(rounded, i, 1), rtol=1e-5,
                       atol=1e-5)
    assert np.abs(s - np.take_along_axis(exact, i, 1)).max() > 1e-2


def test_compare_counts_bad_answers(data):
    x, xd, q, qd, exact = data
    ex_s, ex_i = reference.exact_topk(x, xd, q, qd, 10, block_rows=512)
    served_i = ex_i.copy()
    served_s = ex_s.copy()
    served_i[0, 1] = served_i[0, 0]          # a repeated row
    served_s[1] = served_s[1][::-1]          # not in descending order
    served_i[2, 3] = -1                      # not a row
    pair = reference.pair_scores(x, xd, q, qd, served_i)
    nums = reference.compare(served_s, served_i, pair, ex_s, ex_i, failed=2)
    assert nums["malformed"] == 3
    assert nums["failed"] == 2
    assert nums["score_err_rms"] < 1e-5
    assert nums["recall_at_10"] == pytest.approx(1 - 2 / 400)


def test_fault_variants_are_brute_force(data):
    """The variants the planted faults use: part of the rows, no sparse
    half; and bfloat16 inputs stay near the exact scores."""
    x, xd, q, qd, exact = data
    corpus = reference.DeviceCorpus(x, xd, block_rows=512)
    s, i = corpus.topk(q, qd, 10, rows=1500)
    assert np.array_equal(i, np.argsort(-exact[:, :1500], axis=1)[:, :10])
    dense = qd.astype(np.float64) @ xd.T.astype(np.float64)
    s, i = corpus.topk(q, qd, 10, sparse=False)
    assert np.array_equal(i, np.argsort(-dense, axis=1)[:, :10])
    s, i = corpus.topk(q, qd, 10, bf16=True)
    got = np.take_along_axis(exact, i, 1)
    assert np.abs(s - got).max() < 0.05 * np.abs(exact).max()
    assert np.mean(i == np.argsort(-exact, axis=1)[:, :10]) > 0.8
