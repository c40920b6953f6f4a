"""Cache sorting (paper Algorithm 1) and the Eq. 4/5 cost model."""

import numpy as np
import pytest
import scipy.sparse as sp
from hypothesis import given, settings, strategies as st

import repro.core.cache_sort as cs


def test_permutation_valid(powerlaw_sparse):
    pi = cs.cache_sort(powerlaw_sparse)
    assert sorted(pi.tolist()) == list(range(powerlaw_sparse.shape[0]))


def test_sorted_reduces_measured_cost(powerlaw_sparse):
    x = powerlaw_sparse
    pi = cs.cache_sort(x)
    rng = np.random.default_rng(1)
    worse = 0
    for trial in range(8):
        qd = np.unique(rng.integers(0, x.shape[1], size=30))
        c_un = cs.measured_block_cost(x, 16, qd)
        c_so = cs.measured_block_cost(x, 16, qd, pi=pi)
        worse += int(c_so > c_un)
    assert worse == 0, "cache sorting increased block touches"


def test_sorted_cost_strictly_better_on_head_dims(powerlaw_sparse):
    """For the most-active dimensions the clustering effect must be large."""
    x = powerlaw_sparse
    pi = cs.cache_sort(x)
    head = np.argsort(-cs.dimension_activity(x))[:5]
    c_un = cs.measured_block_cost(x, 16, head)
    c_so = cs.measured_block_cost(x, 16, head, pi=pi)
    assert c_so < c_un


def test_eq4_matches_montecarlo():
    """Eq. 4 E[C_unsort] against brute-force expectation on iid data."""
    rng = np.random.default_rng(3)
    n, d, b = 512, 40, 16
    p = np.minimum(1.0, np.arange(1, d + 1, dtype=float) ** -1.2)
    qd = np.arange(d)
    costs = []
    for _ in range(30):
        x = sp.csr_matrix((rng.random((n, d)) < p[None, :]).astype(np.float32))
        costs.append(cs.measured_block_cost(x, b, qd))
    expected = cs.expected_cost_unsorted(p, np.ones(d), n, b)
    assert abs(np.mean(costs) - expected) / expected < 0.05


def test_eq5_upper_bounds_sorted_cost():
    rng = np.random.default_rng(4)
    n, d, b = 1024, 60, 16
    p = np.minimum(1.0, np.arange(1, d + 1, dtype=float) ** -1.5)
    x = sp.csr_matrix((rng.random((n, d)) < p[None, :]).astype(np.float32))
    pi = cs.cache_sort(x)
    measured = cs.measured_block_cost(x, b, np.arange(d), pi=pi)
    bound = cs.expected_cost_sorted_bound(p, np.ones(d), n, b)
    # Eq.5 is an expectation upper bound; allow small MC slack.
    assert measured <= bound * 1.25


def test_figure4_shape():
    """Fig 4a: sorted bound under unsorted expectation across alpha."""
    n, b, d = 1_000_000, 16, 1000
    for alpha in (1.5, 2.0, 3.0):
        p = cs.power_law_probs(d, alpha)
        un = cs.expected_cost_unsorted(p, p, n, b)
        so = cs.expected_cost_sorted_bound(p, p, n, b)
        assert so < un


@settings(max_examples=25, deadline=None)
@given(st.integers(10, 200), st.integers(5, 40), st.integers(0, 10_000))
def test_property_permutation(n, d, seed):
    rng = np.random.default_rng(seed)
    x = sp.csr_matrix((rng.random((n, d)) < 0.1).astype(np.float32))
    pi = cs.cache_sort(x)
    assert len(pi) == n
    assert sorted(pi.tolist()) == list(range(n))


@settings(max_examples=15, deadline=None)
@given(st.integers(2, 64), st.integers(0, 10_000))
def test_property_sorted_never_worse_total(b, seed):
    rng = np.random.default_rng(seed)
    n, d = 400, 50
    pj = np.minimum(1.0, np.arange(1, d + 1) ** -1.3)
    x = sp.csr_matrix(((rng.random((n, d)) < pj[None, :])
                       * rng.random((n, d))).astype(np.float32))
    pi = cs.cache_sort(x)
    all_dims = np.arange(d)
    assert (cs.measured_block_cost(x, b, all_dims, pi=pi)
            <= cs.measured_block_cost(x, b, all_dims))
