"""Streaming mutable index: delta shard + tombstones + compaction
(DESIGN.md §6; the paper's §7 deployment assumption that the index keeps
serving while the corpus changes).

The batch pipeline (core/hybrid.py) freezes everything at build time:
codebooks, residual quantization grid, compact column space, cache-sort
order.  Mutation therefore splits into two tiers:

* ``DeltaShard`` — a small append-friendly side index holding rows inserted
  since the last build.  Device arrays are sized to an amortized-doubling
  *capacity* (stable shapes => the jit cache grows O(log inserts), the same
  argument as the serving layer's batch buckets); a ``valid_mask`` of
  additive 0/-inf scores tombstones dead slots on device, so they can never
  crowd live rows out of any pass's top-k.  New rows are encoded against the
  FROZEN main-index artifacts: PQ codes via the existing codebooks
  (``core.pq.encode_rows``, packed two-per-byte on append when the main
  index is packed, odd-K phantom nibble included), int8 dense residual via
  the frozen scale/zero grid (``scalar_quantize_rows``), and sparse entries
  as delta posting lists (``sparse_index.DeltaPostings``) over the frozen
  compact column space.  Sparse dims unseen by the main build stay buffered
  in the retained corpus row and only become searchable after compaction.

* ``MutableState`` — the host-side source of truth: the retained corpus
  (initial build rows + appended rows), per-row alive flags, the delta
  shard, and the set of *main tombstones* (external ids deleted or
  superseded while resident in the main generation; the search merge drops
  them host-side).  ``compact()`` folds everything down.  Two policies
  (DESIGN.md §6.2): ``retrain=True`` re-runs the deterministic batch build
  on the surviving rows in corpus order — bit-identical to a scratch build,
  which is what the incremental-vs-rebuild equivalence property pins
  (tests/test_streaming.py) — while the default *merge* path
  (``merge_compact``) keeps every frozen artifact (codebooks, quant grid,
  column space, head-dim set) and only re-derives the row-parallel
  structures, trading a k-means retrain for an O(n) re-encode; its scores
  drift from a scratch build only by the dense encoding error, pinned by
  the relaxed-equivalence property suite.

``HybridIndex.build(..., mutable=True)`` attaches a ``MutableState``;
``HybridIndex.insert/delete/compact`` are thin wrappers over this module,
and ``serve/query_service.py`` serves the delta as one more engine in its
shard fan-out.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import scipy.sparse as sp

from repro.obs import NULL_SPAN

from .distributed import ceil16, merge_topk_host
from .engine import IndexArrays, ScoringEngine, tombstone_mask
from .pq import PQCodebooks, ScalarQuant, encode_rows, scalar_quantize_rows
from .sparse_index import (CompactColumns, DeltaPostings,
                           PaddedInvertedIndex, PaddedSparseRows)

__all__ = ["DeltaShard", "DeltaSnapshot", "MutableState", "search_mutable",
           "plan_overfetch", "fanout_search"]


@jax.jit
def _append_batch(codes, resq, rcols, rvals, inv_rows, inv_vals, start,
                  c_rows, r_rows, rc_rows, rv_rows, dims, p_rows, p_vals):
    """ONE fused device dispatch per insert batch (ROADMAP item; DESIGN.md
    §6.1): the appended slots land as a contiguous
    ``lax.dynamic_update_slice`` block into each structural array, and the
    touched dims' posting rectangles as one row scatter — O(rows appended)
    transfer instead of re-uploading the whole shard.  ``dims`` are padded
    to a power-of-two count with repeats of a real dim (duplicate indices
    carry identical rows), so the jit cache grows with
    (batch size, log touched-dims, log capacity), not per insert."""
    dus = jax.lax.dynamic_update_slice
    return (dus(codes, c_rows, (start, 0)), dus(resq, r_rows, (start, 0)),
            dus(rcols, rc_rows, (start, 0)), dus(rvals, rv_rows, (start, 0)),
            inv_rows.at[dims].set(p_rows), inv_vals.at[dims].set(p_vals))


@jax.jit
def _append_batch_rows(codes, resq, rcols, rvals, start,
                       c_rows, r_rows, rc_rows, rv_rows):
    """Row-only variant of ``_append_batch`` for inserts that touched no
    posting list (pure-dense rows, or everything spilled past the cap)."""
    dus = jax.lax.dynamic_update_slice
    return (dus(codes, c_rows, (start, 0)), dus(resq, r_rows, (start, 0)),
            dus(rcols, rc_rows, (start, 0)), dus(rvals, rv_rows, (start, 0)))


@dataclasses.dataclass(frozen=True)
class DeltaSnapshot:
    """One immutable, device-ready view of the delta shard.  Searches hold a
    snapshot for their whole lifetime, so mutations never race a reader —
    the streaming analogue of the service's refcounted generations."""
    arrays: IndexArrays      # capacity-shaped, valid_mask applied
    ids: np.ndarray          # (capacity,) int64 external ids (-1 = empty)
    count: int               # slots ever filled (dead ones included)
    live: int                # slots filled and not tombstoned
    version: int             # mutation counter at snapshot time

    @property
    def capacity(self) -> int:
        """Padded slot count of the device arrays (== arrays.num_points)."""
        return self.arrays.num_points


class DeltaShard:
    """Append-friendly device-resident side index (DESIGN.md §6.1).

    Host mirrors (numpy) are the source of truth; ``snapshot()`` lazily
    materializes an ``IndexArrays`` of the full capacity with a tombstone
    ``valid_mask``.  Slots are append-only — a delete tombstones, an upsert
    tombstones the old slot and appends — and are only reclaimed by
    compaction, which throws the whole shard away.

    Sparse layout: per-dim posting lists capped at ``postings_cap`` entries
    (pass 1), overflow spilled to per-slot residual rows (pass 3).  Both
    serving paths fetch h == capacity from the delta, so every slot is
    pass-3 refined and the split loses nothing; the cap is what keeps the
    pass-1 gather rectangle (d_active, l_max) narrow when a power-law hot
    dim appears in most delta rows.

    Cost model: an INSERT appends incrementally on device — ONE fused
    dispatch writing the appended slots as a contiguous
    ``dynamic_update_slice`` block into every structural array plus a
    scatter of the touched dims' posting rows (O(rows appended) transfer
    instead of re-uploading the whole shard; ``incremental=False`` restores
    the old full re-materialization, kept as the benchmark baseline).
    Capacity / rectangle growth still re-materializes (the shapes
    changed).  A DELETE reuses the structural arrays and swaps only the
    (capacity,) mask leaf.
    """

    def __init__(self, *, codebooks: PQCodebooks, cols: CompactColumns,
                 dense_residual: ScalarQuant, d_dense: int, pack: bool,
                 capacity: int = 64, l_max: int = 4,
                 postings_cap: int | None = 16):
        self.codebooks = codebooks
        self.cols = cols
        self.pack = pack
        self._scale = np.asarray(dense_residual.scale, np.float32)
        self._zero = np.asarray(dense_residual.zero, np.float32)
        self._scale_j = dense_residual.scale      # device copies, shared with
        self._zero_j = dense_residual.zero        # the main generation
        k = codebooks.num_subspaces
        self._kp = (k + 1) // 2 if pack else k
        self.capacity = max(int(capacity), 1)
        self._codes = np.zeros((self.capacity, self._kp), np.uint8)
        self._resq = np.zeros((self.capacity, d_dense), np.int8)
        self._postings = DeltaPostings(cols.num_active, l_max=l_max,
                                       l_cap=postings_cap)
        # per-slot residual rows: postings overflow past l_cap spills here
        # and is scored EXACTLY in pass 3 — both serving paths fetch
        # h == capacity, so every slot is refined and no mass is lost
        self._rmax = 1
        self._row_cols = np.full((self.capacity, self._rmax),
                                 cols.num_active, np.int32)
        self._row_vals = np.zeros((self.capacity, self._rmax), np.float32)
        self._ids = np.full(self.capacity, -1, np.int64)
        self._dead = np.zeros(self.capacity, bool)
        self.count = 0
        self.version = 0
        self.dropped_nnz = 0      # sparse entries outside the compact space
        # incremental device appends (fused dynamic_update_slice batch);
        # False restores full re-materialization per insert (bench baseline)
        self.incremental = True
        # host->device bytes shipped for structural arrays (rebuilds count
        # the whole shard, incremental appends only the new rows) — the
        # transfer-volume claim benchmarks/serve_bench.py records
        self.upload_bytes = 0
        self._snapshot: DeltaSnapshot | None = None
        # structural device arrays (everything but the tombstone mask),
        # invalidated by inserts only: a delete re-uploads just the
        # (capacity,) mask leaf instead of the whole shard
        self._arrays_struct: IndexArrays | None = None

    @property
    def live_count(self) -> int:
        """Rows that are filled and not tombstoned."""
        return self.count - int(self._dead[: self.count].sum())

    def _grow(self, need: int) -> None:
        cap = self.capacity
        while cap < need:
            cap *= 2
        if cap == self.capacity:
            return
        grow = cap - self.capacity
        self._codes = np.pad(self._codes, ((0, grow), (0, 0)))
        self._resq = np.pad(self._resq, ((0, grow), (0, 0)))
        self._row_cols = np.pad(self._row_cols, ((0, grow), (0, 0)),
                                constant_values=self.cols.num_active)
        self._row_vals = np.pad(self._row_vals, ((0, grow), (0, 0)))
        self._ids = np.pad(self._ids, (0, grow), constant_values=-1)
        self._dead = np.pad(self._dead, (0, grow))
        self.capacity = cap

    def _grow_rmax(self, need: int) -> None:
        rmax = self._rmax
        while rmax < need:
            rmax *= 2
        if rmax == self._rmax:
            return
        grow = rmax - self._rmax
        self._row_cols = np.pad(self._row_cols, ((0, 0), (0, grow)),
                                constant_values=self.cols.num_active)
        self._row_vals = np.pad(self._row_vals, ((0, 0), (0, grow)))
        self._rmax = rmax

    def insert_rows(self, x_sparse: sp.spmatrix, x_dense: np.ndarray,
                    ext_ids: np.ndarray) -> np.ndarray:
        """Append rows, encoding against the frozen main-index artifacts.
        Returns the assigned slot numbers."""
        xs = x_sparse.tocsr()
        xd = np.asarray(x_dense, np.float32)
        m = xs.shape[0]
        assert xd.shape[0] == m == len(ext_ids)
        cap0, lmax0, rmax0 = (self.capacity, self._postings.l_max,
                              self._rmax)
        self._grow(self.count + m)
        # dense: PQ codes + residual against frozen codebooks / frozen grid
        codes_u = encode_rows(xd, self.codebooks, pack=False)
        from .pq import pack_codes, pq_decode
        recon = np.asarray(pq_decode(jnp.asarray(codes_u), self.codebooks))
        resq = scalar_quantize_rows(xd - recon, self._scale, self._zero)
        codes_store = pack_codes(codes_u) if self.pack else codes_u
        slots = np.arange(self.count, self.count + m)
        self._codes[slots] = codes_store
        self._resq[slots] = resq
        self._ids[slots] = np.asarray(ext_ids, np.int64)
        # sparse: postings in the frozen compact column space; entries past
        # the per-dim cap spill to the slot's pass-3 residual row
        touched: list[int] = []
        for j, slot in enumerate(slots):
            lo, hi = xs.indptr[j], xs.indptr[j + 1]
            compact = self.cols.to_compact(xs.indices[lo:hi])
            keep = compact < self.cols.num_active
            self.dropped_nnz += int((~keep).sum())
            kept = compact[keep]
            touched.extend(int(d) for d in kept)
            sd, sv = self._postings.append(int(slot), kept,
                                           xs.data[lo:hi][keep])
            if len(sd):
                self._grow_rmax(len(sd))
                self._row_cols[slot, : len(sd)] = sd
                self._row_vals[slot, : len(sd)] = sv
        self.count += m
        self.version += 1
        self._snapshot = None
        if (self.incremental and self._arrays_struct is not None
                and self.capacity == cap0
                and self._postings.l_max == lmax0 and self._rmax == rmax0):
            # device-side append: rows are written in place of the (already
            # correctly sized) structural arrays — O(rows) transfer
            self._incremental_append(slots, np.unique(
                np.asarray(touched, np.int64)))
        else:
            # shape changed (capacity/rectangle growth) or no device copy
            # yet: fall back to full re-materialization at next snapshot()
            self._arrays_struct = None
        return slots

    def _incremental_append(self, slots: np.ndarray,
                            dims: np.ndarray) -> None:
        """Functionally update the structural device arrays with the rows
        just appended — one fused ``_append_batch`` dispatch.  Updates
        build NEW device arrays, so snapshots held by in-flight searches
        keep the leaves they pinned."""
        st = self._arrays_struct
        lo, m = int(slots[0]), len(slots)
        row_args = (jnp.asarray(self._codes[lo:lo + m]),
                    jnp.asarray(self._resq[lo:lo + m]),
                    jnp.asarray(self._row_cols[lo:lo + m]),
                    jnp.asarray(self._row_vals[lo:lo + m]))
        self.upload_bytes += sum(
            a[lo:lo + m].nbytes for a in (self._codes, self._resq,
                                          self._row_cols, self._row_vals))
        inv = st.inv_index
        if dims.size:
            pad = 1 << max(int(np.ceil(np.log2(dims.size))), 0)
            dims_p = np.concatenate(
                [dims, np.full(pad - dims.size, dims[0], dims.dtype)])
            rows_h, vals_h = self._postings.rows_for(dims_p, self.capacity)
            self.upload_bytes += rows_h.nbytes + vals_h.nbytes
            codes, resq, rcols, rvals, irows, ivals = _append_batch(
                st.codes, st.dense_residual.q, st.sparse_residual.cols,
                st.sparse_residual.vals, inv.rows, inv.vals, jnp.int32(lo),
                *row_args, jnp.asarray(dims_p.astype(np.int32)),
                jnp.asarray(rows_h), jnp.asarray(vals_h))
            inv = PaddedInvertedIndex(rows=irows, vals=ivals,
                                      num_points=inv.num_points)
        else:
            codes, resq, rcols, rvals = _append_batch_rows(
                st.codes, st.dense_residual.q, st.sparse_residual.cols,
                st.sparse_residual.vals, jnp.int32(lo), *row_args)
        self._arrays_struct = dataclasses.replace(
            st, codes=codes, inv_index=inv,
            dense_residual=ScalarQuant(q=resq, scale=self._scale_j,
                                       zero=self._zero_j),
            sparse_residual=PaddedSparseRows(cols=rcols, vals=rvals))

    def tombstone(self, slot: int) -> None:
        """Mark one slot dead; its -inf mask row removes it from scoring."""
        if not 0 <= slot < self.count:
            raise IndexError(f"slot {slot} outside filled range "
                             f"[0, {self.count})")
        if not self._dead[slot]:
            self._dead[slot] = True
            self.version += 1
            self._snapshot = None

    def snapshot(self) -> DeltaSnapshot:
        """Materialize (and cache) the device view of the current state.
        Structural arrays are reused across tombstone-only mutations — a
        delete swaps in a fresh (capacity,) mask leaf, nothing else."""
        if self._snapshot is None:
            cap = self.capacity
            if self._arrays_struct is None:
                self.upload_bytes += (
                    self._codes.nbytes + self._resq.nbytes
                    + self._row_cols.nbytes + self._row_vals.nbytes
                    + self._postings._rows.nbytes
                    + self._postings._vals.nbytes)
                self._arrays_struct = IndexArrays.build(
                    codebooks=self.codebooks,
                    codes=jnp.asarray(self._codes),
                    inv_index=self._postings.to_padded(cap),
                    head=None,
                    dense_residual=ScalarQuant(q=jnp.asarray(self._resq),
                                               scale=self._scale_j,
                                               zero=self._zero_j),
                    # capped-postings spill lives here, refined in pass 3
                    sparse_residual=PaddedSparseRows(
                        cols=jnp.asarray(self._row_cols),
                        vals=jnp.asarray(self._row_vals)),
                    num_points=cap, d_active=self.cols.num_active,
                    with_bcsr=False, pre_packed=self.pack)
            arrays = dataclasses.replace(
                self._arrays_struct,
                valid_mask=tombstone_mask(cap, self.count, self._dead))
            self._snapshot = DeltaSnapshot(
                arrays=arrays, ids=self._ids.copy(), count=self.count,
                live=self.live_count, version=self.version)
        return self._snapshot


class MutableState:
    """Host-side mutation bookkeeping attached to a ``HybridIndex`` built
    with ``mutable=True`` (DESIGN.md §6): retained corpus, alive flags,
    delta shard, main tombstones, and the monotone mutation version that
    result caches key on."""

    def __init__(self, index, x_sparse: sp.csr_matrix, x_dense: np.ndarray,
                 ext_ids: np.ndarray | None = None,
                 delta_capacity: int = 64):
        n = x_sparse.shape[0]
        self.params = index.params
        self.x_sparse0 = x_sparse.tocsr()
        self.x_dense0 = np.asarray(x_dense, np.float32)
        self.ids_built = (np.arange(n, dtype=np.int64) if ext_ids is None
                          else np.asarray(ext_ids, np.int64))
        assert len(self.ids_built) == n
        if len(np.unique(self.ids_built)) != n:
            raise ValueError("ext_ids must be unique")
        if n and self.ids_built.min() < 0:
            raise ValueError("external ids must be non-negative (-1 is the "
                             "merge layer's empty-slot sentinel)")
        self.alive0 = np.ones(n, bool)
        # cache-sorted position -> external id, computed ONCE: pi and
        # ids_built are both frozen for this generation, and the search
        # hot path must not re-gather an O(N) map per call
        self.id_map = self.ids_built[index.pi]
        # frozen head-dim set (compact ids, pad -1): merge_compact rebuilds
        # the head block over the SAME dims instead of re-ranking activity,
        # so its tile layout stays comparable across merge generations
        # a sharded index's per-shard artifacts stay with its shards; the
        # delta shard, on device 0, encodes inserts against shard 0's
        base = index.shards[0] if index.shards is not None else index
        self.head_dims0 = np.asarray(base.head_dim_ids)
        # sparse entries silently outside the frozen column space in the
        # merged MAIN structures (merge_compact carries + grows this);
        # nonzero means only a retrain can make them searchable
        self.main_dropped_nnz = 0
        self.extra_sparse: list[sp.csr_matrix] = []
        self.extra_dense: list[np.ndarray] = []
        self.extra_ids: list[int] = []
        self.extra_alive: list[bool] = []
        self.main_tombstones: set[int] = set()
        self.version = 0
        self.next_id = int(self.ids_built.max(initial=-1)) + 1
        self._loc = {int(e): ("init", i)
                     for i, e in enumerate(self.ids_built)}
        self.delta = DeltaShard(
            codebooks=base.codebooks, cols=index.cols,
            dense_residual=base.dense_residual, d_dense=index.d_dense,
            pack=index.params.resolve_pack(), capacity=delta_capacity)

    # -- mutation ---------------------------------------------------------

    def insert(self, x_sparse, x_dense, ids=None) -> np.ndarray:
        """Insert (or upsert) rows; returns the external ids assigned."""
        xs = sp.csr_matrix(x_sparse)
        if xs.shape[1] != self.x_sparse0.shape[1]:
            raise ValueError(
                f"sparse width {xs.shape[1]} != corpus width "
                f"{self.x_sparse0.shape[1]}")
        xd = np.atleast_2d(np.asarray(x_dense, np.float32))
        if xd.shape[1] != self.x_dense0.shape[1]:
            raise ValueError(
                f"dense width {xd.shape[1]} != corpus width "
                f"{self.x_dense0.shape[1]}")
        m = xs.shape[0]
        if m == 0:
            return np.empty(0, np.int64)
        if ids is None:
            ids = np.arange(self.next_id, self.next_id + m, dtype=np.int64)
        ids = np.atleast_1d(np.asarray(ids, np.int64))
        if not (len(ids) == m == xd.shape[0]):
            raise ValueError(
                f"row-count mismatch: {m} sparse, {xd.shape[0]} dense, "
                f"{len(ids)} ids")
        if len(np.unique(ids)) != m:
            raise ValueError("duplicate external ids within one insert batch")
        if ids.min() < 0:
            raise ValueError("external ids must be non-negative (-1 is the "
                             "merge layer's empty-slot sentinel)")
        # encode FIRST, retire old copies after: if validation or encoding
        # raises, the upserted ids' existing rows must survive untouched
        slots = self.delta.insert_rows(xs, xd, ids)
        for e in ids:
            self._kill(int(e))            # upsert: retire any existing row
        for j, (e, _slot) in enumerate(zip(ids, slots)):
            self.extra_sparse.append(xs[j])
            self.extra_dense.append(xd[j])
            self.extra_ids.append(int(e))
            self.extra_alive.append(True)
            self._loc[int(e)] = ("extra", len(self.extra_ids) - 1)
        self.next_id = max(self.next_id, int(ids.max()) + 1)
        self.version += 1
        return ids

    def _kill(self, ext_id: int) -> bool:
        loc = self._loc.get(ext_id)
        if loc is None:
            return False
        kind, i = loc
        if kind == "init":
            if not self.alive0[i]:
                return False
            self.alive0[i] = False
            self.main_tombstones.add(ext_id)
        else:
            if not self.extra_alive[i]:
                return False
            self.extra_alive[i] = False
            self.delta.tombstone(i)       # slot j == extra index j
        del self._loc[ext_id]
        return True

    def delete(self, ids) -> int:
        """Tombstone rows by external id; returns how many were live."""
        killed = 0
        for e in np.atleast_1d(np.asarray(ids, np.int64)):
            killed += self._kill(int(e))
        if killed:
            self.version += 1
        return killed

    # -- compaction -------------------------------------------------------

    @property
    def live_rows(self) -> int:
        """Logical corpus size: surviving initial rows + live inserts."""
        return int(self.alive0.sum()) + sum(self.extra_alive)

    def survivors(self) -> tuple[sp.csr_matrix, np.ndarray, np.ndarray]:
        """Surviving corpus rows in canonical order (initial order, then
        insertion order) — the exact input a from-scratch batch build on the
        current logical contents would receive, which is what the
        equivalence property test relies on."""
        keep0 = np.flatnonzero(self.alive0)
        xs_parts = [self.x_sparse0[keep0]]
        xd_parts = [self.x_dense0[keep0]]
        ids = [self.ids_built[keep0]]
        live = [j for j, a in enumerate(self.extra_alive) if a]
        if live:
            xs_parts += [self.extra_sparse[j] for j in live]
            xd_parts.append(np.stack([self.extra_dense[j] for j in live]))
            ids.append(np.asarray([self.extra_ids[j] for j in live],
                                  np.int64))
        xs = sp.vstack(xs_parts, format="csr") if len(xs_parts) > 1 \
            else xs_parts[0]
        return xs, np.concatenate(xd_parts, axis=0), np.concatenate(ids)

    _EMPTY_COMPACT_MSG = (
        "cannot compact an empty corpus: the batch build (k-means, "
        "column space) needs at least one surviving row; keep the "
        "delta serving or insert before compacting")

    def merge_compact(self):
        """Fold delta + tombstones into the FROZEN build artifacts
        (DESIGN.md §6.2): keep the codebooks, residual-quant grid, compact
        column space and head-dim set, and re-derive only the row-parallel
        structures over the surviving rows — new cache-sort, re-pruned
        posting lists, PQ codes via ``encode_rows`` against the existing
        codebooks, int8 residuals on the existing grid.  O(n) encode
        instead of a k-means retrain; rows already resident in the main
        generation re-encode to IDENTICAL codes (deterministic argmin over
        unchanged codebooks), so merged scores drift from a scratch rebuild
        only by the delta rows' frozen-vs-retrained dense encoding error —
        the tolerance the relaxed-equivalence suite (tests/test_streaming.py)
        pins.  Sparse entries outside the frozen column space stay buffered
        in the retained corpus (counted in ``main_dropped_nnz``) until a
        ``compact(retrain=True)``.  Returns a NEW mutable ``HybridIndex``;
        the caller swaps it in."""
        from .cache_sort import cache_sort
        from .engine import Backend
        from .hybrid import HybridIndex, _remap
        from .pq import pq_decode
        from .pruning import prune_split
        from .sparse_index import (build_padded_inverted_index,
                                   build_padded_rows, build_tile_sparse_head)
        if self.live_rows == 0:
            raise ValueError(self._EMPTY_COMPACT_MSG)
        if self.params.num_shards > 1:
            raise ValueError("merge compaction keeps one shard's frozen "
                             "artifacts; a sharded index compacts by "
                             "rebuild (retrain=True)")
        params, delta = self.params, self.delta
        cols, codebooks = delta.cols, delta.codebooks
        xs, xd, ids = self.survivors()
        n = xs.shape[0]
        pi = cache_sort(xs)
        xs_s, xd_s = xs[pi], np.asarray(xd, np.float32)[pi]
        split = prune_split(xs_s, keep_top=params.keep_top)
        idx_compact = _remap(split.index, cols)     # frozen column space
        res_compact = _remap(split.residual, cols)
        dropped = int(xs_s.nnz) - int(idx_compact.nnz) - int(res_compact.nnz)
        head = None
        head_dim_ids = np.empty(0, np.int32)
        tail_index = idx_compact
        hd = self.head_dims0[self.head_dims0 >= 0].astype(np.int32)
        if hd.size and cols.num_active > 0:
            # same FROZEN head dims, not a re-ranked activity top-n: the
            # query-side head/tail split must match the index layout
            head = build_tile_sparse_head(idx_compact, hd,
                                          block_rows=params.block_rows,
                                          block_cols=params.block_cols)
            head_dim_ids = np.asarray(head.head_dims)
            tail_index = idx_compact.tolil()
            tail_index[:, hd] = 0
            tail_index = tail_index.tocsr()
            tail_index.eliminate_zeros()
        inv_index = build_padded_inverted_index(tail_index)
        sparse_residual = build_padded_rows(res_compact)
        codes_u = encode_rows(xd_s, codebooks, pack=False)
        recon = np.asarray(pq_decode(jnp.asarray(codes_u), codebooks))
        resq = scalar_quantize_rows(xd_s - recon, delta._scale, delta._zero)
        dres = ScalarQuant(q=jnp.asarray(resq), scale=delta._scale_j,
                           zero=delta._zero_j)
        backend = params.resolve_backend()
        arrays = IndexArrays.build(
            codebooks=codebooks, codes=jnp.asarray(codes_u),
            inv_index=inv_index, head=head, dense_residual=dres,
            sparse_residual=sparse_residual, num_points=n,
            d_active=cols.num_active,
            with_bcsr=backend in (Backend.PALLAS, Backend.PALLAS_PACKED),
            pack=params.resolve_pack())
        engine = ScoringEngine(arrays=arrays, backend=backend)
        new = HybridIndex(params=params, num_points=n, pi=pi, cols=cols,
                          inv_index=inv_index, head=head,
                          head_dim_ids=head_dim_ids,
                          sparse_residual=sparse_residual,
                          codebooks=codebooks, codes=arrays.codes,
                          dense_residual=dres, d_dense=xd.shape[1],
                          engine=engine)
        new.mutable_state = MutableState(new, xs, xd, ext_ids=ids,
                                         delta_capacity=delta.capacity)
        new.mutable_state.next_id = max(new.mutable_state.next_id,
                                        self.next_id)
        new.mutable_state.main_dropped_nnz = self.main_dropped_nnz + dropped
        return new

    def compact(self, retrain: bool | None = None, obs=None):
        """Fold delta + tombstones down; returns a NEW mutable
        ``HybridIndex`` (this state is untouched; the caller swaps, e.g.
        through QueryService's double-buffered ``refresh()``).

        ``retrain=True`` re-runs the full batch build on the surviving rows
        (new codebooks, new compact column space, new cache-sort) —
        bit-identical to building from scratch, with the same
        ``num_shards``.  ``retrain=False`` merges into the frozen artifacts
        (``merge_compact``; unsharded indexes only).  The default ``None``
        auto-routes: merge, unless the index is sharded or sparse entries
        have been dropped outside the frozen column space (delta buffering
        or a previous merge) — those only become searchable under a
        retrain.  ``obs`` gets the rebuild's ``index.build`` span."""
        from .hybrid import HybridIndex
        if self.live_rows == 0:
            raise ValueError(self._EMPTY_COMPACT_MSG)
        if retrain is None:
            retrain = (self.params.num_shards > 1 or
                       self.delta.dropped_nnz + self.main_dropped_nnz > 0)
        if not retrain:
            return self.merge_compact()
        xs, xd, ids = self.survivors()
        new = HybridIndex.build(xs, xd, self.params, mutable=True,
                                ext_ids=ids, obs=obs)
        # carry the id counter: the fresh state only sees surviving ids, so
        # recomputing max+1 could re-mint a previously deleted id and
        # resurrect it under new content
        new.mutable_state.next_id = max(new.mutable_state.next_id,
                                        self.next_id)
        return new


def plan_overfetch(engines, h: int, deleted) -> list[int]:
    """Per-main-engine fetch depths under pending tombstones (DESIGN.md
    §6.2): every main engine overfetches by the 16-bucketed tombstone count
    (the bucket keeps the jit-static fetch sizes bounded) so dropping
    tombstoned ids at the merge can never leave fewer than h live results;
    overfetch-then-truncate of a deterministic top-k is exact, so the
    mutation-free path stays bit-identical to the plain one."""
    slack = ceil16(len(deleted)) if deleted else 0
    return [min(h + slack, e.num_points) for e in engines]


def fanout_search(engines, h_fetch, offsets, id_map, delta_engine,
                  delta_ids, deleted, qd, qv, qe, *, h: int, alpha: int,
                  beta: int, qn: int | None = None, executor=None,
                  dedup_upserts: bool = False, span=NULL_SPAN):
    """THE fan-out merge (DESIGN.md §6.2): dispatch every main engine plus
    the delta engine back-to-back (JAX async dispatch overlaps them — the
    in-process form of the paper's §7.2 RPC fan-out), assemble the per-
    engine candidates in the common EXTERNAL id space, and merge top-h on
    the host with main-generation tombstones dropped.

    Shared by ``search_mutable`` (one engine, one offset) and
    ``QueryService._run_batch`` (per-shard engines + bucket padding) — one
    implementation instead of the two copies a parity test used to pin.

    engines/h_fetch/offsets: the main engines, their fetch depths
    (``plan_overfetch``), and each engine's global row offset; ``id_map``
    maps global row positions to external ids (None = identity);
    ``delta_engine`` fetches its whole capacity so tombstone-masked slots
    can never crowd out live ones, with ``delta_ids`` mapping slots to
    external ids (``delta_ids=None`` when the delta engine already returns
    EXTERNAL ids — the RPC delta part of the cluster tier); ``qn`` trims
    bucket padding before the merge.  Engines are any ``ShardSearcher``
    duck-type — ``.search(qd, qv, qe, h=, alpha=, beta=) -> (scores, ids)``
    plus ``.num_points`` — so in-process ``ScoringEngine`` and the cluster
    tier's RPC shard handles dispatch through the same code (DESIGN.md
    §8.2).  ``executor`` (a ``concurrent.futures`` executor) runs the
    dispatches concurrently — required for BLOCKING remote engines, where
    back-to-back calls would serialize the network round-trips; the
    in-process path leaves it None because JAX async dispatch already
    overlaps device work.  ``dedup_upserts`` forwards to
    ``merge_topk_host`` (see its docstring for the cross-transport upsert
    race it closes).  ``span`` (a ``repro.obs`` span, ``QueryService``'s
    ``serve.batch``) gets three children, one per stage (DESIGN.md §9.2):
    ``serve.dispatch`` (every engine's ``search`` call), ``serve.device_wait``
    (until every engine's output is ready) and ``serve.merge`` (host
    assembly + top-h merge, the device wait excluded).  Returns ``(scores,
    ids) (qn, h)`` numpy arrays.
    """
    with span.child("serve.dispatch"):
        if executor is not None:
            futs = [executor.submit(e.search, qd, qv, qe, h=hf,
                                    alpha=alpha, beta=beta)
                    for e, hf in zip(engines, h_fetch)]
            dfut = None
            if delta_engine is not None:
                dfut = executor.submit(delta_engine.search, qd, qv, qe,
                                       h=delta_engine.num_points,
                                       alpha=alpha, beta=beta)
            outs = [f.result() for f in futs]
            delta_out = dfut.result() if dfut is not None else None
        else:
            outs = [e.search(qd, qv, qe, h=hf, alpha=alpha, beta=beta)
                    for e, hf in zip(engines, h_fetch)]
            delta_out = None
            if delta_engine is not None:
                delta_out = delta_engine.search(qd, qv, qe,
                                                h=delta_engine.num_points,
                                                alpha=alpha, beta=beta)
    with span.child("serve.device_wait"):
        jax.block_until_ready((outs, delta_out))
    with span.child("serve.merge"):
        # assemble per-engine candidate parts in a COMMON id space.
        # Shards stay in row order so stable-sort tie-breaking matches
        # lax.top_k on the unsharded array.
        parts = []
        for out, off in zip(outs, offsets):
            s = np.asarray(out[0])
            ids = np.asarray(out[1]).astype(np.int64)
            if qn is not None:
                s, ids = s[:qn], ids[:qn]
            ids = ids + int(off)
            if id_map is not None:
                ids = np.asarray(id_map)[ids]
            parts.append((s, ids, True))
        if delta_out is not None:
            s = np.asarray(delta_out[0])
            pos = np.asarray(delta_out[1]).astype(np.int64)
            if qn is not None:
                s, pos = s[:qn], pos[:qn]
            parts.append((s, pos if delta_ids is None else delta_ids[pos],
                          False))
        return merge_topk_host(parts, h, drop_ids=deleted,
                               dedup_upserts=dedup_upserts)


def search_mutable(index, q_sparse, q_dense, h: int = 20,
                   alpha: int | None = None, beta: int | None = None):
    """Three-pass search over the main generation's shard engines + delta
    shard with host merge (DESIGN.md §6.2) — the single-process form of
    what QueryService does in its fan-out (literally the same
    ``fanout_search`` helper).  Returns a SearchResult whose ids are
    EXTERNAL ids."""
    from .hybrid import SearchResult
    from .sparse_index import sparse_queries_to_padded

    st = index.mutable_state
    p = index.params
    alpha = p.alpha if alpha is None else alpha
    beta = p.beta if beta is None else beta
    q_dims, q_vals = sparse_queries_to_padded(q_sparse, index.cols,
                                              nq_max=p.nq_max)
    qd, qv = jnp.asarray(q_dims), jnp.asarray(q_vals)
    qe = jnp.asarray(np.asarray(q_dense, np.float32))

    engines = index.engines
    h_fetch = plan_overfetch(engines, h, st.main_tombstones)
    snap = st.delta.snapshot() if st.delta.live_count else None
    delta_engine = None
    if snap is not None:
        delta_engine = ScoringEngine(arrays=snap.arrays,
                                     backend=index.engine.backend)
    s, ids = fanout_search(
        engines, h_fetch, index.row_offsets,
        st.id_map, delta_engine,
        snap.ids if snap is not None else None, st.main_tombstones,
        qd, qv, qe, h=h, alpha=alpha, beta=beta)
    return SearchResult(ids=ids, scores=s)
