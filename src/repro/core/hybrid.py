"""HybridIndex — the paper's full indexing + search pipeline (paper §6).

Build:
  1. cache-sort datapoints (Algorithm 1) — all row-parallel structures below
     store rows in sorted order; search maps ids back at the end.
  2. sparse data index: eta-prune (top ``keep_top`` per dim), split into the
     tile-sorted head block (most-active dims) + padded inverted index (tail).
  3. sparse residual index: remaining entries as padded rows (eps = 0 default).
  4. dense data index: PQ, K_U = d^D/2 subspaces, l = 16 (LUT16 kernel path).
  5. dense residual index: int8 scalar quantization (K_V = d^D, l = 256).

Search (batch of hybrid queries) is delegated to core/engine.py's
ScoringEngine: the entire three-pass loop (pass 1 approx overfetch alpha*h,
pass 2 + dense residual keep beta*h, pass 3 + sparse residual return top h)
runs as one jitted device function; this class only converts queries to the
padded device layout and maps result positions back to original ids.

Row shards (paper §7.2, ``HybridIndexParams.num_shards``): the rows split
into contiguous shards, each built as above over its own rows against one
shared compact column space and served from its own device; a search runs
every shard's engine and merges their top-h on the host
(core/streaming.py::fanout_search).
"""

from __future__ import annotations

import contextlib
import dataclasses
import multiprocessing
import os
import time
from concurrent.futures import ProcessPoolExecutor, ThreadPoolExecutor

import jax
import jax.numpy as jnp
import numpy as np
import scipy.sparse as sp

from repro.obs import NULL_TRACER, Observability

from .cache_sort import cache_sort, dimension_activity
from .engine import Backend, IndexArrays, ScoringEngine
from .pq import (PQCodebooks, ScalarQuant, pq_decode, pq_encode,
                 scalar_quantize, train_codebooks)
from .pruning import prune_split
from .sparse_index import (CompactColumns, PaddedInvertedIndex,
                           PaddedSparseRows, TileSparseHead,
                           build_compact_columns, build_padded_inverted_index,
                           build_padded_rows, build_tile_sparse_head,
                           sparse_queries_to_padded)

__all__ = ["HybridIndexParams", "HybridIndex", "SearchResult"]


@dataclasses.dataclass(frozen=True)
class HybridIndexParams:
    # sparse side
    keep_top: int = 256          # eta: entries kept per dim in the data index
    head_dims: int = 128         # most-active dims served by the tile block
    block_rows: int = 128        # tile height (the TPU "cache line", B)
    block_cols: int = 128
    nq_max: int = 256            # padded query nnz
    use_head_block: bool = True
    # dense side
    pq_subspaces: int | None = None   # default d^D // 2  (paper §6.1.1)
    pq_codes: int = 16
    kmeans_iters: int = 12
    seed: int = 0
    # search
    alpha: int = 20              # overfetch multiplier (pass 1)
    beta: int = 5                # keep multiplier (pass 2)
    use_lut16_kernel: bool = False  # legacy alias for backend="pallas"
    # engine backend: ref | onehot-mxu | pallas | pallas-packed
    backend: str | None = None
    # store PQ codes packed two-per-byte (half the HBM).  None => pack iff
    # the backend is pallas-packed; True also works with ref/onehot (they
    # unpack in-jit, bit-for-bit with unpacked storage).
    pack_codes: bool | None = None
    # row shards (paper §7.2): >1 splits the rows into this many contiguous
    # shards, each built over its own rows and served on its own device
    num_shards: int = 1

    def __post_init__(self):
        if self.num_shards < 1:
            raise ValueError(f"num_shards must be >= 1, got {self.num_shards}")

    def resolve_backend(self) -> Backend:
        if self.backend is not None:
            return Backend.from_name(self.backend)
        return Backend.PALLAS if self.use_lut16_kernel else Backend.REF

    def resolve_pack(self) -> bool:
        if self.pack_codes is not None:
            return self.pack_codes
        return self.resolve_backend() is Backend.PALLAS_PACKED


@dataclasses.dataclass
class SearchResult:
    ids: np.ndarray        # (Q, h) original datapoint ids
    scores: np.ndarray     # (Q, h) refined inner products
    # diagnostics
    pass1_ids: np.ndarray | None = None


@dataclasses.dataclass(frozen=True)
class ShardEngines:
    """The engine of a row-sharded index: one ``ScoringEngine`` per shard,
    each on its own device.  ``arrays`` holds every shard's, to wait on or
    free.  A search has to run every shard and merge (``HybridIndex.
    search``, ``QueryService(index=)``), so ``search`` refuses rather than
    answer from one shard."""
    engines: tuple[ScoringEngine, ...]

    @property
    def arrays(self) -> tuple[IndexArrays, ...]:
        return tuple(e.arrays for e in self.engines)

    @property
    def backend(self) -> Backend:
        return self.engines[0].backend

    def search(self, *args, **kwargs):
        raise ValueError("a row-sharded index searches every shard and "
                         "merges: use HybridIndex.search or "
                         "QueryService(index=)")


@dataclasses.dataclass
class HybridIndex:
    params: HybridIndexParams
    num_points: int
    pi: np.ndarray                     # sorted position -> original id
    cols: CompactColumns
    inv_index: PaddedInvertedIndex     # tail dims of the pruned data index
    head: TileSparseHead | None        # head dims of the pruned data index
    head_dim_ids: np.ndarray           # compact ids in the head block (pad -1)
    sparse_residual: PaddedSparseRows
    codebooks: PQCodebooks
    codes: jax.Array                   # (N, K) uint8; (N, ceil(K/2)) packed
                                       # when params.resolve_pack() — the
                                       # engine's array, not a second copy
    dense_residual: ScalarQuant
    d_dense: int
    engine: "ScoringEngine | ShardEngines"  # device-resident three-pass
                                            # scorer(s)
    # streaming support (core/streaming.py, DESIGN.md §6): present iff the
    # index was built with mutable=True; owns the retained corpus, the delta
    # shard, and the tombstone bookkeeping behind insert()/delete()/compact()
    mutable_state: "object | None" = None
    # row shards (params.num_shards > 1): one single-shard index per shard,
    # built over its own rows against the shared ``cols``, and the global
    # cache-sorted position of each one's first row.  The sharded index
    # holds only what is global (num_points, pi, cols, d_dense); ``engine``
    # is a ShardEngines, and the per-row artifacts and codebooks are None.
    shards: "tuple[HybridIndex, ...] | None" = None
    offsets: np.ndarray | None = None

    # -- row shards (paper §7.2) -------------------------------------------
    @property
    def engines(self) -> list[ScoringEngine]:
        """One engine per row shard, in row order (just ``[engine]`` when
        the index is not sharded)."""
        if self.shards is None:
            return [self.engine]
        return list(self.engine.engines)

    @property
    def row_offsets(self) -> np.ndarray:
        """Global cache-sorted position of each shard's first row."""
        if self.shards is None:
            return np.zeros(1, np.int64)
        return self.offsets

    # -- build -------------------------------------------------------------
    @classmethod
    def build(cls, x_sparse: sp.spmatrix, x_dense: np.ndarray,
              params: HybridIndexParams = HybridIndexParams(), *,
              mutable: bool = False,
              ext_ids: np.ndarray | None = None,
              delta_capacity: int = 64,
              obs: Observability | None = None) -> "HybridIndex":
        """Build the index.  With ``params.num_shards`` S > 1 the rows are
        split into S contiguous shards, the compact column space is built
        once over all rows, and each shard runs the single-shard build on
        its own rows against it, on device ``s % len(jax.devices())``; the
        shards build concurrently, their host stages in spawned worker
        processes held to the CPU (so a script that builds one needs an
        ``if __name__ == "__main__":`` guard).  Returns once every
        shard's arrays are on their device.  ``obs`` (its tracer) gets one
        ``index.build`` root span with an ``index.build.shard`` child per
        shard."""
        x_sparse = x_sparse.tocsr()
        n = x_sparse.shape[0]
        x_dense = np.asarray(x_dense, np.float32)
        assert x_dense.shape[0] == n
        tracer = obs.tracer if obs is not None else NULL_TRACER
        with tracer.root("index.build", rows=n,
                         shards=params.num_shards) as span:
            if params.num_shards == 1:
                idx = cls._build_shard(x_sparse, x_dense, params, span=span)
            else:
                idx = cls._build_sharded(x_sparse, x_dense, params, span)
        if mutable:
            from .streaming import MutableState
            # delta_capacity pre-sizes the delta shard's device arrays
            # (amortized doubling still applies past it); a caller that
            # knows its insert rate avoids the growth re-materializations
            idx.mutable_state = MutableState(idx, x_sparse, x_dense,
                                             ext_ids=ext_ids,
                                             delta_capacity=delta_capacity)
        elif ext_ids is not None:
            raise ValueError("ext_ids only applies with mutable=True")
        return idx

    @classmethod
    def _build_sharded(cls, x_sparse: sp.csr_matrix, x_dense: np.ndarray,
                       params: HybridIndexParams, span) -> "HybridIndex":
        n, num = x_sparse.shape[0], params.num_shards
        if num > n:
            raise ValueError(f"cannot split {n} rows into {num} shards")
        sizes = np.full(num, n // num, np.int64)
        sizes[: n % num] += 1
        offsets = np.concatenate([[0], np.cumsum(sizes)[:-1]])
        # one column space for every shard: queries are encoded once
        cols = CompactColumns(global_ids=np.flatnonzero(
            np.bincount(x_sparse.indices, minlength=x_sparse.shape[1])))
        devices = jax.devices()
        one = dataclasses.replace(params, num_shards=1)

        def build(s: int) -> "HybridIndex":
            lo, hi = offsets[s], offsets[s] + sizes[s]
            return cls._build_shard(x_sparse[lo:hi], x_dense[lo:hi], one,
                                    span=span, cols=cols, shard=s,
                                    device=devices[s % len(devices)],
                                    pool=pool)

        with _cpu_workers(num) as pool, ThreadPoolExecutor(
                num, thread_name_prefix="index-build") as ex:
            shards = tuple(ex.map(build, range(num)))
        pi = np.concatenate([off + s.pi for off, s in zip(offsets, shards)])
        # the per-shard artifacts stay with their shards: no one shard's
        # stands for the index
        return cls(params=params, num_points=n, pi=pi, cols=cols,
                   inv_index=None, head=None, head_dim_ids=None,
                   sparse_residual=None, codebooks=None, codes=None,
                   dense_residual=None, d_dense=shards[0].d_dense,
                   engine=ShardEngines(tuple(s.engine for s in shards)),
                   shards=shards, offsets=offsets)

    @classmethod
    def _build_shard(cls, x_sparse: sp.csr_matrix, x_dense: np.ndarray,
                     params: HybridIndexParams, *, span,
                     cols: CompactColumns | None = None, shard: int = 0,
                     device=None, pool=None) -> "HybridIndex":
        """The single-shard build: steps 1-3 (host) then 4-5 (device).
        ``cols`` None builds the compact column space from these rows.
        ``device`` None leaves placement to JAX's default, as an unsharded
        build does; otherwise everything is made on and committed to it.
        ``pool`` runs the host stage in a worker process."""
        n = x_sparse.shape[0]
        where = device if device is not None else jax.devices()[0]
        with span.child("index.build.shard", shard=shard, rows=n,
                        device=str(where)) as sp_, \
                (jax.default_device(device) if device is not None
                 else contextlib.nullcontext()):
            t0 = time.perf_counter()
            if pool is not None:
                parts = pool.submit(_sparse_stage_numpy, x_sparse, params,
                                    cols).result()
            else:
                parts = _sparse_stage(x_sparse, params, cols)
            t1 = time.perf_counter()
            idx = cls._dense_stage(parts, x_dense, params, device)
            sp_.set("host_s", t1 - t0)
            sp_.set("device_s", time.perf_counter() - t1)
        return idx

    @classmethod
    def _dense_stage(cls, parts: "_SparseParts", x_dense: np.ndarray,
                     params: HybridIndexParams, device) -> "HybridIndex":
        """Steps 4-5 and the engine over one shard's sparse parts."""
        pi = parts.pi
        xd = x_dense[pi]
        n = len(pi)

        # 4. dense PQ data index
        d_dense = xd.shape[1]
        k_u = params.pq_subspaces or max(d_dense // 2, 1)
        cb = train_codebooks(jnp.asarray(xd), k_u, params.pq_codes,
                             iters=params.kmeans_iters, seed=params.seed)
        codes = pq_encode(jnp.asarray(xd), cb)

        # 5. dense residual index (int8)
        recon = np.asarray(pq_decode(codes, cb))
        dres = scalar_quantize(jnp.asarray(xd - recon))

        backend = params.resolve_backend()
        arrays = IndexArrays.build(
            codebooks=cb, codes=codes, inv_index=parts.inv_index,
            head=parts.head, dense_residual=dres,
            sparse_residual=parts.sparse_residual,
            num_points=n, d_active=parts.cols.num_active,
            with_bcsr=backend in (Backend.PALLAS, Backend.PALLAS_PACKED),
            pack=params.resolve_pack())
        if device is not None:
            # committed: a search dispatches to the device its index is on
            arrays = jax.block_until_ready(jax.device_put(arrays, device))
        engine = ScoringEngine(arrays=arrays, backend=backend)
        # hold the ENGINE's arrays (codes possibly packed): the unpacked
        # (N, K) build-time array must not stay resident or packing saves
        # nothing
        return cls(params=params, num_points=n, pi=pi, cols=parts.cols,
                   inv_index=arrays.inv_index, head=arrays.head,
                   head_dim_ids=parts.head_dim_ids,
                   sparse_residual=arrays.sparse_residual,
                   codebooks=arrays.codebooks, codes=arrays.codes,
                   dense_residual=arrays.dense_residual, d_dense=d_dense,
                   engine=engine)

    # -- persistence (thin wrappers over repro/persist, DESIGN.md §7) ------
    @classmethod
    def load(cls, root: str, *, backend=None) -> "HybridIndex":
        """Recover a mutable index from a durable store: committed snapshot
        (checksum-verified leaf blobs) + WAL-tail replay through the
        streaming machinery — bit-identical, ids and scores, to the index
        at its last durably-acked mutation.  ``backend`` overrides the
        recorded engine backend (any backend serves any snapshot)."""
        from repro.persist import recover
        rec = recover(root, backend=backend)
        rec.durability.close()       # load-only: no appends from here
        return rec.index

    def save(self, root: str) -> str:
        """Bootstrap a durable store for this freshly built mutable index
        (initial snapshot + empty WAL) without keeping a WAL handle open —
        the one-shot "write my index to disk" form.  Serving with
        durability goes through ``QueryService(persist_dir=…)`` instead."""
        from repro.persist import bootstrap
        d = bootstrap(root, self)
        d.close()
        return root

    # -- streaming mutation (thin wrappers over core/streaming.py) ---------
    def _mutable(self):
        if self.mutable_state is None:
            raise ValueError("index is immutable; build with "
                             "HybridIndex.build(..., mutable=True)")
        return self.mutable_state

    def insert(self, x_sparse, x_dense, ids=None) -> np.ndarray:
        """Insert (or upsert) rows into the delta shard (DESIGN.md §6),
        encoded against the frozen build artifacts.  Returns external ids."""
        return self._mutable().insert(x_sparse, x_dense, ids=ids)

    def delete(self, ids) -> int:
        """Tombstone rows by external id; returns how many were live."""
        return self._mutable().delete(ids)

    def compact(self, retrain: bool | None = None,
                obs: Observability | None = None) -> "HybridIndex":
        """Fold the delta + tombstones down; returns the NEW mutable index
        (this one is untouched — swap at the call site, e.g.
        QueryService.refresh).  ``retrain=True`` re-runs the full batch
        build (new codebooks / column space / cache-sort); ``retrain=False``
        merge-compacts into the frozen artifacts; ``None`` (default) merges
        unless out-of-column-space sparse entries force a retrain
        (core/streaming.py, DESIGN.md §6.2); a sharded index always
        rebuilds.  ``obs`` gets a rebuild's ``index.build`` span."""
        return self._mutable().compact(retrain=retrain, obs=obs)

    @property
    def delta_version(self) -> int:
        """Monotone mutation counter (0 for an untouched mutable index)."""
        return self._mutable().version

    # -- search ------------------------------------------------------------
    def search(self, q_sparse: sp.spmatrix, q_dense: np.ndarray, h: int = 20,
               alpha: int | None = None, beta: int | None = None,
               return_pass1: bool = False) -> SearchResult:
        """Thin wrapper: pad queries to the device layout, run the engine's
        single-jit three-pass search, map positions back to original ids.

        A mutable index (build(..., mutable=True)) routes through the
        delta-merging path instead and returns EXTERNAL ids (which default
        to build-row positions, so the two paths agree until the first
        mutation)."""
        if self.mutable_state is not None:
            if return_pass1:
                raise ValueError("return_pass1 is a diagnostic of the "
                                 "single-engine path; not available on a "
                                 "mutable index")
            from .streaming import search_mutable
            return search_mutable(self, q_sparse, q_dense, h=h,
                                  alpha=alpha, beta=beta)
        p = self.params
        alpha = p.alpha if alpha is None else alpha
        beta = p.beta if beta is None else beta

        q_dense = jnp.asarray(np.asarray(q_dense, np.float32))
        q_dims_np, q_vals_np = sparse_queries_to_padded(
            q_sparse, self.cols, nq_max=p.nq_max)
        if self.shards is not None:
            if return_pass1:
                raise ValueError("return_pass1 is a diagnostic of the "
                                 "single-engine path; not available on a "
                                 "sharded index")
            from .streaming import fanout_search, plan_overfetch
            engines = self.engines
            s3, ids3 = fanout_search(
                engines, plan_overfetch(engines, h, ()), self.offsets,
                self.pi, None, None, frozenset(), jnp.asarray(q_dims_np),
                jnp.asarray(q_vals_np), q_dense, h=h, alpha=alpha, beta=beta)
            return SearchResult(ids=ids3, scores=s3)
        s3, ids3, ids1 = self.engine.search(
            jnp.asarray(q_dims_np), jnp.asarray(q_vals_np), q_dense,
            h=h, alpha=alpha, beta=beta)

        orig = self.pi[np.asarray(ids3)]
        return SearchResult(
            ids=orig, scores=np.asarray(s3),
            pass1_ids=self.pi[np.asarray(ids1)] if return_pass1 else None)

    def exact_scores(self, q_sparse: sp.spmatrix, q_dense: np.ndarray,
                     x_sparse: sp.spmatrix, x_dense: np.ndarray) -> np.ndarray:
        """Brute-force q·x for validation (original row order)."""
        return (np.asarray((q_sparse @ x_sparse.T).todense())
                + np.asarray(q_dense, np.float32) @ np.asarray(x_dense, np.float32).T)


@dataclasses.dataclass
class _SparseParts:
    """The host stage of one shard's build (steps 1-3)."""
    pi: np.ndarray                     # sorted position -> shard row
    cols: CompactColumns
    inv_index: PaddedInvertedIndex
    head: TileSparseHead | None
    head_dim_ids: np.ndarray
    sparse_residual: PaddedSparseRows


def _sparse_stage(x_sparse: sp.csr_matrix, params: HybridIndexParams,
                  cols: CompactColumns | None) -> _SparseParts:
    """Steps 1-3 over one shard's rows; ``cols`` None builds the compact
    column space from them."""
    # 1. cache sort; permute every row-parallel structure once.
    pi = cache_sort(x_sparse)
    xs = x_sparse[pi]

    # 2-3. prune + compact columns over the FULL sparse matrix so data
    # index and residual share one column space.
    split = prune_split(xs, keep_top=params.keep_top)
    if cols is None:
        cols, _ = build_compact_columns(xs)
    idx_compact = _remap(split.index, cols)
    res_compact = _remap(split.residual, cols)

    head = None
    head_dim_ids = np.empty(0, np.int32)
    tail_index = idx_compact
    if params.use_head_block and cols.num_active > 0:
        activity = dimension_activity(idx_compact)
        n_head = min(params.head_dims, cols.num_active)
        head_compact = np.sort(np.argsort(-activity)[:n_head]).astype(np.int32)
        head = build_tile_sparse_head(
            idx_compact, head_compact,
            block_rows=params.block_rows, block_cols=params.block_cols)
        head_dim_ids = np.asarray(head.head_dims)
        # zero head dims out of the tail inverted index
        tail_index = idx_compact.tolil()
        tail_index[:, head_compact] = 0
        tail_index = tail_index.tocsr()
        tail_index.eliminate_zeros()
    inv_index = build_padded_inverted_index(tail_index)
    sparse_residual = build_padded_rows(res_compact)
    return _SparseParts(pi=pi, cols=cols, inv_index=inv_index, head=head,
                        head_dim_ids=head_dim_ids,
                        sparse_residual=sparse_residual)


def _sparse_stage_numpy(x_sparse: sp.csr_matrix, params: HybridIndexParams,
                        cols: CompactColumns) -> _SparseParts:
    """``_sparse_stage`` in a worker process, its arrays as numpy."""
    parts = _sparse_stage(x_sparse, params, cols)
    return dataclasses.replace(
        parts, inv_index=jax.tree.map(np.asarray, parts.inv_index),
        head=jax.tree.map(np.asarray, parts.head),
        sparse_residual=jax.tree.map(np.asarray, parts.sparse_residual))


def _cpu_only() -> None:
    """A host-stage worker's initializer: JAX held to the CPU, since the
    chips belong to the process that started the worker."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    jax.config.update("jax_platforms", "cpu")


def _cpu_workers(n: int) -> ProcessPoolExecutor:
    """Up to ``n`` spawned processes for the host stage, JAX on the CPU."""
    return ProcessPoolExecutor(
        n, mp_context=multiprocessing.get_context("spawn"),
        initializer=_cpu_only)


def _remap(x: sp.spmatrix, cols: CompactColumns) -> sp.csr_matrix:
    xc = x.tocsc()[:, cols.global_ids].tocsr()
    return xc
