"""The plain reference and the comparison that decides ``correct``.

The reference is exact maximum inner product search over the corpus as the
benchmark generated it: ``score(q, x) = q_sparse . x_sparse + q_dense .
x_dense``.  It imports nothing of the program and uses nothing the program
built (no codebooks, no quantizer scales, no pruned lists).

* ``DeviceCorpus.topk`` (``exact_topk`` for one pass) scores every row in
  float32 at ``Precision.HIGHEST`` on the device, in blocks of rows, and
  keeps the top ``h`` per query.  The sparse half is a dense matmul over
  the union of the queries' columns only; entries of other columns meet
  zeros.  Its variants serve the control (``dense_bits=4``: the dense rows
  rounded to 16 levels per dimension, the precision below the int8 the
  configurations state for the dense side, which the comparison must
  refuse) and the faults planted in the reference's place.
* ``pair_scores`` scores given (query, row) pairs in float64 on the host.
* ``compare`` turns served answers into the numbers compared with limits.
"""

from __future__ import annotations

import math

import numpy as np
import scipy.sparse as sp

__all__ = ["DeviceCorpus", "exact_topk", "pair_scores", "compare", "CHECKS"]

# every number ``compare`` reports that is held to a limit; the limits are
# the configuration's (``"limits"`` in its file)
CHECKS = ("score_err_rms", "recall_miss", "malformed", "failed")


def _pow2(n: int) -> int:
    return 1 << max(int(n) - 1, 1).bit_length()


def quantize_dense(x: np.ndarray, bits: int) -> np.ndarray:
    """Round each column of ``x`` to ``2**bits`` levels between its
    minimum and maximum, back in float32."""
    x = np.asarray(x, np.float32)
    lo, hi = x.min(axis=0), x.max(axis=0)
    step = np.maximum(hi - lo, 1e-12) / (2 ** bits - 1)
    return (np.round((x - lo) / step) * step + lo).astype(np.float32)


class DeviceCorpus:
    """The corpus on the device, as it was generated, for one or more
    passes of ``topk``.

    Each block of rows is scattered into a dense (rows, columns of the
    queries) slab on the device and multiplied.  Shapes are rounded up to
    powers of two, so that runs of one configuration share compiled
    programs."""

    def __init__(self, x_sparse: sp.csr_matrix, x_dense: np.ndarray, *,
                 block_rows: int = 4096):
        import jax.numpy as jnp

        x = x_sparse.tocsr()
        self.n, self.d = x.shape
        self.block_rows = block_rows
        self.nb = -(-self.n // block_rows)
        self.first = x.indptr[np.minimum(np.arange(self.nb + 1) * block_rows,
                                         self.n)]
        self.span = _pow2(np.max(np.diff(self.first), initial=1))
        pad = np.zeros(self.span, np.int32)
        x_dense = np.asarray(x_dense, np.float32)
        self.lo, self.hi = x_dense.min(axis=0), x_dense.max(axis=0)
        self.idx = jnp.asarray(np.concatenate([x.indices.astype(np.int32),
                                               pad]))
        self.val = jnp.asarray(np.concatenate([x.data.astype(np.float32),
                                               pad.astype(np.float32)]))
        self.row = jnp.asarray(np.concatenate([
            np.repeat(np.arange(self.n, dtype=np.int32), np.diff(x.indptr)),
            pad]))
        self.xd = jnp.asarray(np.concatenate([
            x_dense, np.zeros((self.nb * block_rows - self.n,
                               x_dense.shape[1]), np.float32)]))

    def topk(self, q_sparse: sp.csr_matrix, q_dense: np.ndarray, h: int, *,
             dense_bits: int | None = None, bf16: bool = False,
             rows: int | None = None, sparse: bool = True):
        """Top-``h`` rows by exact hybrid inner product, per query: float32
        at ``Precision.HIGHEST``.  Returns ``(scores (S, h) float32, ids
        (S, h) int64)``, best first.

        The variants the control and the planted faults need:
        ``dense_bits`` rounds the dense rows to ``2**bits`` levels per
        dimension (``quantize_dense``); ``bf16`` rounds every input to
        bfloat16 and accumulates in float32; ``rows`` scans only the first
        ``rows`` rows; ``sparse=False`` leaves the sparse half out."""
        import jax
        import jax.numpy as jnp

        prec = (jax.lax.Precision.DEFAULT if bf16
                else jax.lax.Precision.HIGHEST)
        cast = jnp.bfloat16 if bf16 else jnp.float32
        n = self.n if rows is None else min(int(rows), self.n)
        block_rows, span = self.block_rows, self.span
        q = q_sparse.tocsr()
        nq = q.shape[0]
        cols = np.unique(q.indices)
        # the slab's columns: the queries' own, then one that is always zero
        width = _pow2(len(cols) + 1)
        pos = np.full(self.d, len(cols), np.int32)
        pos[cols] = np.arange(len(cols), dtype=np.int32)
        qu = np.zeros((width, nq), np.float32)
        qc = q.tocoo()
        qu[pos[qc.col], qc.row] = qc.data
        lo = self.lo
        step = np.maximum(self.hi - lo, 1e-12) / (
            2 ** (dense_bits or 1) - 1)

        @jax.jit
        def block(e0, count, r0, best_s, best_i, idx, val, row, xd, pos,
                  qu, qe, lo, step):
            xb = jax.lax.dynamic_slice(xd, (r0, 0), (block_rows, xd.shape[1]))
            if dense_bits is not None:
                xb = jnp.round((xb - lo) / step) * step + lo
            s = jnp.dot(xb.astype(cast), qe.astype(cast), precision=prec,
                        preferred_element_type=jnp.float32)        # (b, S)
            if sparse:
                live = jnp.arange(span) < count
                c = jnp.where(live, pos[jax.lax.dynamic_slice(
                    idx, (e0,), (span,))], width - 1)
                v = jnp.where(live, jax.lax.dynamic_slice(val, (e0,),
                                                          (span,)), 0)
                r = jnp.where(live, jax.lax.dynamic_slice(row, (e0,),
                                                          (span,)) - r0, 0)
                xs = jnp.zeros((block_rows, width),
                               jnp.float32).at[r, c].add(v)
                s = s + jnp.dot(xs.astype(cast), qu.astype(cast),
                                precision=prec,
                                preferred_element_type=jnp.float32)
            ids = r0 + jnp.arange(block_rows)
            s = jnp.where((ids < n)[:, None], s, -jnp.inf)
            cand_s = jnp.concatenate([best_s, s.T], axis=1)
            cand_i = jnp.concatenate(
                [best_i, jnp.broadcast_to(ids[None, :], s.T.shape)], axis=1)
            top_s, at = jax.lax.top_k(cand_s, h)
            return top_s, jnp.take_along_axis(cand_i, at, axis=1)

        pos_d, qu_d = jnp.asarray(pos), jnp.asarray(qu)
        qe_d = jnp.asarray(np.asarray(q_dense, np.float32).T)
        lo_d, step_d = jnp.asarray(lo), jnp.asarray(step.astype(np.float32))
        best_s = jnp.full((nq, h), -jnp.inf, jnp.float32)
        best_i = jnp.full((nq, h), -1, jnp.int32)
        first = self.first
        for b in range(-(-n // block_rows)):
            best_s, best_i = block(int(first[b]), int(first[b + 1] - first[b]),
                                   b * block_rows, best_s, best_i, self.idx,
                                   self.val, self.row, self.xd, pos_d, qu_d,
                                   qe_d, lo_d, step_d)
        return np.asarray(best_s), np.asarray(best_i).astype(np.int64)


def exact_topk(x_sparse: sp.csr_matrix, x_dense: np.ndarray,
               q_sparse: sp.csr_matrix, q_dense: np.ndarray, h: int, *,
               block_rows: int = 4096, **variant):
    """``DeviceCorpus(x_sparse, x_dense).topk(q_sparse, q_dense, h)``: one
    pass, with the ``topk`` variant keywords."""
    return DeviceCorpus(x_sparse, x_dense, block_rows=block_rows).topk(
        q_sparse, q_dense, h, **variant)


def pair_scores(x_sparse: sp.csr_matrix, x_dense: np.ndarray,
                q_sparse: sp.csr_matrix, q_dense: np.ndarray,
                ids: np.ndarray) -> np.ndarray:
    """Exact float64 score of query ``i`` against row ``ids[i, j]``.
    Ids outside the corpus score NaN."""
    s, h = ids.shape
    n = x_sparse.shape[0]
    flat = ids.reshape(-1)
    ok = (flat >= 0) & (flat < n)
    safe = np.where(ok, flat, 0)
    qrep = np.repeat(np.arange(s), h)
    xs = x_sparse[safe].astype(np.float64)
    qs = q_sparse.tocsr()[qrep].astype(np.float64)
    sparse = np.asarray(xs.multiply(qs).sum(axis=1)).reshape(-1)
    dense = np.einsum("ij,ij->i", np.asarray(x_dense)[safe].astype(np.float64),
                      np.asarray(q_dense, np.float64)[qrep])
    out = sparse + dense
    out[~ok] = np.nan
    return out.reshape(s, h)


def compare(served_s: np.ndarray, served_i: np.ndarray,
            pair_exact: np.ndarray, exact_s: np.ndarray,
            exact_i: np.ndarray, failed: int) -> dict:
    """The numbers one run's sample is judged by, and its recall.

    ``served_s``/``served_i`` (S, h): what the timed path returned for the
    sampled requests; ``pair_exact`` (S, h): the exact score of each served
    (query, row) pair; ``exact_s``/``exact_i`` (S, h): the exact top-h.

    * ``score_err_rms``: root mean square over served pairs of
      ``|served score - exact score|``, each over its query's exact best
      score.  Well-formed answers only.  It holds the refined score to the
      row it is served with, whichever row that is.
    * ``recall_miss``: the share of the exact top-h that was not served.
      It holds the choice of rows: a pass 1 that scans part of the corpus,
      misranks rows or selects the wrong candidates serves exact scores of
      the wrong rows, which only this number sees.
    * ``malformed``: sampled answers that are not ``h`` distinct valid rows
      with finite scores in descending order.
    * ``failed``: requests of the window that never got an answer.
    * ``recall_at_10``: ``1 - recall_miss``, the end-to-end metric."""
    s, h = served_i.shape
    ok = np.isfinite(served_s).all(axis=1) & np.isfinite(pair_exact).all(
        axis=1)
    ok &= (np.diff(served_s, axis=1) <= 0).all(axis=1)
    ok &= np.array([len(set(r)) == h for r in served_i.tolist()])
    scale = np.maximum(np.abs(exact_s[:, 0]), 1e-30)[:, None]
    rel = np.abs(served_s.astype(np.float64) - pair_exact) / scale
    err = float(math.sqrt(np.mean(rel[ok] ** 2))) if ok.any() else math.inf
    recall = float(np.mean([len(set(a) & set(b)) / h for a, b in
                            zip(served_i.tolist(), exact_i.tolist())]))
    return {"score_err_rms": err, "recall_miss": 1.0 - recall,
            "malformed": int((~ok).sum()), "failed": int(failed),
            "recall_at_10": recall,
            "score_err_max": float(rel[ok].max()) if ok.any() else math.inf}
