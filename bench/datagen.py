"""Hybrid sparse+dense corpora and query pools for the benchmark, from a seed.

The distribution is the program's synthetic data model
(``repro.data.make_hybrid_dataset``, paper §7.1.2 Fig. 5), copied here so
that the yardstick does not move with the program:

* sparse column ``j`` is active in a row with probability
  ``p_j = min(1, nnz * j**-alpha / sum_i i**-alpha)``, independently per
  (row, column); columns with ``p_j <= 1e-7`` are never active;
* nonzero values are log-normal (median ``value_median``, shape
  ``value_sigma``);
* dense rows are a random rank-``dense_rank`` mix plus 0.3 noise, scaled by
  ``dense_weight / sqrt(d_dense)``;
* a query perturbs a random row: its sparse values times U(0.7, 1.3), plus
  an extra power-law row of nominal ``query_extra_nnz`` nonzeros, and its
  dense row plus ``0.2 * dense_weight / sqrt(d_dense)`` noise.
  ``query_keep``, when set, keeps only that many of the row's nonzeros,
  chosen at random (the program's model keeps all of them).

The program draws one Bernoulli per (row, column), which is O(rows x
columns).  Here each column's active rows are drawn as a Bernoulli process by
geometric skips between hits, which is the same distribution in O(nnz).
The dense part is drawn on the device, one jitted call per block of rows.
"""

from __future__ import annotations

import dataclasses
import math
import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import scipy.sparse as sp

__all__ = ["DataSpec", "Corpus", "QueryPool", "column_probs",
           "powerlaw_rows", "make_corpus", "make_queries", "sub_seed"]


@dataclasses.dataclass(frozen=True)
class DataSpec:
    """The data-model numbers of one configuration file."""
    num_rows: int
    d_sparse: int
    alpha: float
    nnz_per_row: float
    d_dense: int
    dense_rank: int = 16
    dense_weight: float = 1.0
    value_median: float = 0.054
    value_sigma: float = 1.1
    query_extra_nnz: float | None = None   # None: 0.3 * nnz_per_row
    query_keep: int | None = None          # None: every nonzero of the row

    @classmethod
    def from_config(cls, cfg: dict) -> "DataSpec":
        names = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: cfg[k] for k in names if k in cfg})


@dataclasses.dataclass
class Corpus:
    x_sparse: sp.csr_matrix      # (N, d_sparse) float32
    x_dense: np.ndarray          # (N, d_dense) float32


@dataclasses.dataclass
class QueryPool:
    q_sparse: sp.csr_matrix      # (Q, d_sparse) float32
    q_dense: np.ndarray          # (Q, d_dense) float32
    src: np.ndarray              # (Q,) the row each query perturbs


def sub_seed(seed: int, *stream: int) -> int:
    """A 32-bit seed for one named stream of ``seed`` (any whole number)."""
    seq = np.random.SeedSequence([int(seed), *stream])
    return int(seq.generate_state(1)[0])


def column_probs(d: int, alpha: float, nnz: float) -> np.ndarray:
    """Per-column activity probabilities of the power-law model."""
    pj = np.arange(1, d + 1, dtype=np.float64) ** (-alpha)
    pj *= nnz / pj.sum()
    return np.minimum(pj, 1.0)


def powerlaw_rows(seed: int, n: int, d: int, alpha: float, nnz: float,
                  value_median: float = 0.054, value_sigma: float = 1.1,
                  groups: int = 16) -> sp.csr_matrix:
    """(n, d) CSR with Bernoulli(p_j) activity per (row, column) and
    log-normal values, from ``seed``.

    The columns are split into ``groups`` runs of about equal expected work,
    each drawn from its own stream on a thread of its own; the result
    depends on the seed and ``groups``, not on the number of threads."""
    pj = column_probs(d, alpha, nnz)
    cols = np.flatnonzero(pj > 1e-7)
    if not len(cols):
        return sp.csr_matrix((n, d), dtype=np.float32)
    p = pj[cols]
    work = np.cumsum(n * p + 16.0)
    cuts = np.searchsorted(work, work[-1] * np.arange(1, groups) / groups)
    bounds = np.concatenate([[0], cuts, [len(cols)]])

    def draw(g):
        rng = np.random.default_rng([seed, g])
        lo, hi = bounds[g], bounds[g + 1]
        rows, colv = _bernoulli_rows(rng, n, cols[lo:hi], p[lo:hi])
        z = rng.standard_normal(len(rows), dtype=np.float32)
        vals = np.exp(z * np.float32(value_sigma)
                      + np.float32(math.log(value_median)))
        return rows, colv, vals

    with ThreadPoolExecutor(min(groups, os.cpu_count() or 1)) as ex:
        parts = list(ex.map(draw, range(len(bounds) - 1)))
    rows, colv, vals = (np.concatenate([x[k] for x in parts])
                        for k in range(3))
    # entries come column by column with rows ascending: a CSC as it stands
    indptr = np.concatenate([[0], np.cumsum(np.bincount(colv, minlength=d))])
    return sp.csc_matrix((vals, rows.astype(np.int32), indptr),
                         shape=(n, d)).tocsr()


def _bernoulli_rows(rng, n: int, cols: np.ndarray, p: np.ndarray):
    """Active (rows, cols) of the given columns, column by column with rows
    ascending, by geometric skips: the gap between successive active rows
    of a column with probability p is Geometric(p).  Each column draws its
    expected hit count plus eight standard deviations of gaps at once, and
    the rare column whose gaps do not reach row n draws more until they
    do."""
    rows_out, cols_out = [], []
    start = np.full(len(cols), -1, np.int64)       # last row drawn per column
    todo = np.arange(len(cols))
    while len(todo):
        pt = p[todo]
        left = n - 1 - start[todo]
        m = np.ceil(left * pt + 8 * np.sqrt(left * pt) + 8).astype(np.int64)
        m = np.minimum(m, left)
        seg = np.repeat(np.arange(len(todo)), m)
        u = rng.random(int(m.sum()))
        # Geometric(p) >= 1 by inversion; p == 1 gives gaps of exactly 1
        with np.errstate(divide="ignore"):
            scale = np.where(pt < 1.0, 1.0 / np.log1p(-np.minimum(pt, 1.0)),
                             0.0)
        np.log1p(np.negative(u, out=u), out=u)
        u *= scale[seg]
        np.minimum(u, n, out=u)
        pos = np.cumsum(u.astype(np.int64) + 1)
        ends = np.cumsum(m)
        base = np.concatenate([[0], pos[ends[:-1] - 1]])
        rows = pos - np.repeat(base - start[todo], m)
        keep = rows < n
        rows_out.append(rows[keep])
        cols_out.append(cols[todo][seg[keep]])
        last = rows[ends - 1]
        start[todo] = np.minimum(last, n - 1)
        todo = todo[last < n - 1]
    rows = np.concatenate(rows_out) if rows_out else np.empty(0, np.int64)
    colv = np.concatenate(cols_out) if cols_out else np.empty(0, np.int64)
    if len(rows_out) > 1:       # a column's later rounds come after the rest
        order = np.argsort(colv, kind="stable")
        rows, colv = rows[order], colv[order]
    return rows, colv


def _dense_fns(rows: int, d: int, rank: int, weight: float):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def basis_of(key):
        return jax.random.normal(jax.random.fold_in(key, 0), (rank, d),
                                 jnp.float32)

    @jax.jit
    def block(key, b, basis):
        kc, kn = jax.random.split(jax.random.fold_in(key, b + 1))
        coef = jax.random.normal(kc, (rows, rank), jnp.float32)
        noise = jax.random.normal(kn, (rows, d), jnp.float32)
        x = jnp.dot(coef, basis, precision=jax.lax.Precision.HIGHEST)
        return (x + 0.3 * noise) * (weight / math.sqrt(d))
    return basis_of, block


def make_corpus(spec: DataSpec, seed: int,
                block_rows: int = 1 << 16) -> Corpus:
    """The corpus of one run: sparse rows on the host, dense rows on the
    device in blocks of rows (so that the device holds little of it at a
    time), both from ``seed``."""
    import jax
    x_sparse = powerlaw_rows(sub_seed(seed, 1), spec.num_rows, spec.d_sparse,
                             spec.alpha, spec.nnz_per_row, spec.value_median,
                             spec.value_sigma)
    basis_of, block = _dense_fns(block_rows, spec.d_dense, spec.dense_rank,
                                 spec.dense_weight)
    key = jax.random.key(sub_seed(seed, 2))
    basis = basis_of(key)
    x_dense = np.empty((spec.num_rows, spec.d_dense), np.float32)
    blocks = -(-spec.num_rows // block_rows)
    ahead = block(key, 0, basis)
    for b in range(blocks):
        done, ahead = ahead, (block(key, b + 1, basis) if b + 1 < blocks
                              else None)
        rows = x_dense[b * block_rows:(b + 1) * block_rows]
        rows[:] = np.asarray(done)[:len(rows)]
    return Corpus(x_sparse=x_sparse, x_dense=x_dense)


def make_queries(spec: DataSpec, corpus: Corpus, count: int, seed: int,
                 stream: int) -> QueryPool:
    """``count`` related queries, each perturbing a distinct random row."""
    rng = np.random.default_rng(sub_seed(seed, 3, stream))
    n = corpus.x_sparse.shape[0]
    src = rng.choice(n, size=count, replace=False)
    q = corpus.x_sparse[src].tocsr()
    if spec.query_keep is not None:
        q = _keep_random(rng, q, spec.query_keep)
    q.data = (q.data * rng.uniform(0.7, 1.3, size=q.nnz)).astype(np.float32)
    extra_nnz = (0.3 * spec.nnz_per_row if spec.query_extra_nnz is None
                 else spec.query_extra_nnz)
    extra = powerlaw_rows(sub_seed(seed, 3, stream, 1), count, spec.d_sparse,
                          spec.alpha, extra_nnz, spec.value_median,
                          spec.value_sigma)
    q_sparse = (q + extra).tocsr().astype(np.float32)
    noise = rng.standard_normal((count, spec.d_dense)).astype(np.float32)
    q_dense = (corpus.x_dense[src] + 0.2 * spec.dense_weight
               / math.sqrt(spec.d_dense) * noise).astype(np.float32)
    return QueryPool(q_sparse=q_sparse, q_dense=q_dense, src=src)


def _keep_random(rng, q: sp.csr_matrix, keep: int) -> sp.csr_matrix:
    """Keep ``keep`` nonzeros of each row, chosen uniformly at random."""
    lens = np.diff(q.indptr)
    order = np.lexsort((rng.random(q.nnz), np.repeat(np.arange(q.shape[0]),
                                                     lens)))
    rank = np.arange(q.nnz) - np.repeat(q.indptr[:-1], lens)
    sel = np.sort(order[rank < keep])
    row = np.repeat(np.arange(q.shape[0]), lens)[sel]
    return sp.csr_matrix((q.data[sel], (row, q.indices[sel])), shape=q.shape)
