"""Distributed hybrid search (paper §7.2 "Online Search": 200 servers, one
shard each, merge results) mapped to JAX shard_map over the mesh 'data' axis.

Each device owns a row-shard of every row-parallel index structure (PQ codes,
inverted-index, residuals).  A query batch is replicated; every device scores
its shard and keeps a local top-k; only (k × num_shards) candidates cross the
network (all_gather), never the index — the same communication pattern as the
paper's RPC fan-out.

All scoring routes through core/engine.py (one implementation of the paper's
scorer); this module only adds the shard_map plumbing:

* ``make_sharded_search_fn``  — pass-1 only (approximate scores + merge);
* ``make_sharded_search3_fn`` — the FULL three-pass search per shard (pass 1
  approx → pass 2 dense residual → pass 3 sparse residual, each shard refining
  its own candidates against its local residual rows — the paper's per-server
  reordering) followed by one all_gather merge of the refined top-h.

The same functions lower at ShapeDtypeStruct scale (1e9 rows across 512
devices) in launch/dryrun.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from . import engine as eng
from . import residual as res
from .pq import ScalarQuant
from .sparse_index import (PaddedInvertedIndex, PaddedSparseRows,
                           TileSparseHead, score_inverted)

__all__ = ["sharded_pass1_topk", "make_sharded_search_fn",
           "make_sharded_search3_fn", "sharded_three_pass_topk", "merge_topk",
           "merge_topk_host", "ceil16", "split_index_arrays"]


def merge_topk(scores: jax.Array, ids: jax.Array, k: int):
    """Merge per-shard candidates: (Q, S*k) -> (Q, k)."""
    vals, pos = jax.lax.top_k(scores, k)
    return vals, jnp.take_along_axis(ids, pos, axis=1)


def ceil16(n: int) -> int:
    """Round up to the 16 bucket — the tombstone-overfetch granularity
    (DESIGN.md §6.2): overfetch sizes are jit-static, so bucketing them
    keeps the compilation cache bounded while mutations accumulate."""
    return -(-n // 16) * 16


def merge_topk_host(parts, h: int, *, drop_ids=None, dedup_upserts=False):
    """Host-side top-h merge over per-engine candidate sets, the streaming
    generalization of the serving fan-out merge (DESIGN.md §5.4, §6.2) and
    the router-side merge of the cluster tier (DESIGN.md §8.2).

    parts: iterable of ``(scores (Q, k_i), ids (Q, k_i), filtered)`` — the
    per-engine top-k, already mapped to a COMMON (external) id space; the
    widths k_i may differ (the delta shard fetches its whole capacity).
    ``filtered=True`` parts drop candidates whose id is in ``drop_ids``
    (main-generation tombstones); the delta part passes False so an
    upserted row's new copy survives while its superseded main copy dies.
    ``filtered`` may also be an explicit array of ids to drop from THAT
    part only — the per-shard tombstone view the cluster router needs:
    ``drop_ids`` alone assumes every part shares one tombstone view, which
    a lagging replica does not (its own view is stale, so the MERGE must
    apply the caller's authoritative set, per part, DESIGN.md §8.4).

    ``dedup_upserts=True`` additionally drops, from every filtered part,
    any id that appears with a finite score in an unfiltered (delta) part:
    a live delta copy proves every main copy of that id is tombstoned
    (upsert kills before it appends), so the rule is exact — it only
    matters across a transport, where the main and delta parts cannot pin
    one atomic view the way the in-process fan-out does.

    Stable descending sort over parts concatenated in caller order, so ties
    break exactly like ``lax.top_k`` on the unsharded array when parts are
    shard slices in row order.  Entries with non-finite scores (tombstone
    masks, dropped ids) get id -1; callers overfetch (h + tombstone slack)
    so a full result always has h real rows.  Returns (scores, ids) (Q, h).
    """
    drop = np.asarray(sorted(drop_ids), np.int64) \
        if drop_ids else np.empty(0, np.int64)
    parts = [(np.asarray(s, np.float32), np.asarray(ids, np.int64), f)
             for s, ids, f in parts]
    delta_live = np.empty(0, np.int64)
    if dedup_upserts:
        live = [ids[np.isfinite(s)] for s, ids, f in parts
                if isinstance(f, bool) and not f]
        if live:
            delta_live = np.unique(np.concatenate([v.ravel() for v in live]))
    ss, ii = [], []
    for s, ids, filtered in parts:
        if isinstance(filtered, bool):
            part_drop = drop if filtered else np.empty(0, np.int64)
        else:                      # explicit per-part tombstone view
            part_drop = np.asarray(sorted(filtered), np.int64)
            filtered = True
        if filtered and delta_live.size:
            part_drop = np.union1d(part_drop, delta_live)
        if part_drop.size:
            s = np.where(np.isin(ids, part_drop), -np.inf, s)
        ss.append(s)
        ii.append(ids)
    ss = np.concatenate(ss, axis=1)
    ii = np.concatenate(ii, axis=1)
    if ss.shape[1] < h:                       # tiny pool: pad to (Q, h)
        pad = h - ss.shape[1]
        ss = np.pad(ss, ((0, 0), (0, pad)), constant_values=-np.inf)
        ii = np.pad(ii, ((0, 0), (0, pad)), constant_values=-1)
    order = np.argsort(-ss, axis=1, kind="stable")[:, :h]
    s_out = np.take_along_axis(ss, order, axis=1)
    i_out = np.take_along_axis(ii, order, axis=1)
    return s_out, np.where(np.isfinite(s_out), i_out, -1)


def split_index_arrays(arrays: eng.IndexArrays, num_shards: int, *,
                       ragged: bool = False
                       ) -> tuple[list[eng.IndexArrays], np.ndarray]:
    """Row-slice one ``IndexArrays`` into per-shard copies + row offsets.

    The host-side analogue of the shard_map row sharding above, and the
    fan-out entry point for ``serve/query_service.py`` (DESIGN.md §5): each
    shard gets its own complete ``IndexArrays`` over rows ``[s*n/S, (s+1)*n/S)``
    so a ``ScoringEngine`` per shard runs the FULL three-pass search on its
    rows; the service dispatches all shards back-to-back (JAX async dispatch
    overlaps them) and merges the per-shard top-k on host.

    Every row-parallel structure is sliced; the inverted index is localized
    (entries outside the shard re-padded to the ``n_local`` sentinel); the
    head block is re-padded to the tile grid and its BCSR form rebuilt when
    the parent carried one.  Column-space structures (codebooks, scales,
    ``head_pos``) are shared with the parent, not copied.

    Returns ``(shards, row_offsets)`` with ``row_offsets[s]`` the global row
    id of shard ``s``'s first row.  By default requires
    ``num_points % num_shards == 0`` (the same equal-rows contract as
    ``sharded_pass1_topk``); ``ragged=True`` instead ceil-splits — the first
    ``n % S`` shards get one extra row — which the cluster tier (DESIGN.md
    §8.2) needs because a compacted corpus has an arbitrary survivor count.
    Either way the shards are contiguous row slices in row order, so the
    stable ``merge_topk_host`` over them is bit-identical to the unsharded
    search.
    """
    n = arrays.num_points
    if num_shards < 1 or (n % num_shards and not ragged) or num_shards > n:
        raise ValueError(
            f"cannot split {n} rows into {num_shards} equal shards"
            + (" (pass ragged=True for a ceil-split)"
               if ragged is False and num_shards <= n else ""))
    base, rem = divmod(n, num_shards)
    sizes = np.full(num_shards, base, np.int64)
    sizes[:rem] += 1
    bounds = np.concatenate([[0], np.cumsum(sizes)])
    offsets = bounds[:-1].astype(np.int32)

    inv_rows = np.asarray(arrays.inv_index.rows)
    inv_vals = np.asarray(arrays.inv_index.vals)
    sres_cols = np.asarray(arrays.sparse_residual.cols)
    sres_vals = np.asarray(arrays.sparse_residual.vals)
    res_q = np.asarray(arrays.dense_residual.q)
    codes = np.asarray(arrays.codes)
    head_block = (np.asarray(arrays.head.block, np.float32)
                  if arrays.head is not None else None)
    vmask = (np.asarray(arrays.valid_mask)
             if arrays.valid_mask is not None else None)

    shards: list[eng.IndexArrays] = []
    for s in range(num_shards):
        lo, hi = int(bounds[s]), int(bounds[s + 1])
        n_local = hi - lo
        inside = (inv_rows >= lo) & (inv_rows < hi)
        inv_s = PaddedInvertedIndex(
            rows=jnp.asarray(
                np.where(inside, inv_rows - lo, n_local).astype(np.int32)),
            vals=jnp.asarray(
                np.where(inside, inv_vals, 0.0).astype(np.float32)),
            num_points=n_local)

        head_s = arrays.head
        tiles, ptr, col = arrays.head_tiles, arrays.head_ptr, arrays.head_col
        max_steps = arrays.head_max_steps
        if arrays.head is not None:
            br, bc = arrays.head.block_rows, arrays.head.block_cols
            n_pad = -(-n_local // br) * br
            blk = np.zeros((n_pad, head_block.shape[1]), np.float32)
            blk[:n_local] = head_block[lo:hi]
            occ = blk.reshape(n_pad // br, br,
                              blk.shape[1] // bc, bc).any(axis=(1, 3))
            head_s = TileSparseHead(
                block=jnp.asarray(blk, arrays.head.block.dtype),
                occupancy=jnp.asarray(occ), head_dims=arrays.head.head_dims,
                block_rows=br, block_cols=bc)
            if max_steps > 0:
                from repro.kernels.ops import bcsr_from_head
                tiles, ptr, col, max_steps = bcsr_from_head(head_s)

        shards.append(eng.IndexArrays(
            codebooks=arrays.codebooks,
            codes=jnp.asarray(codes[lo:hi]),
            inv_index=inv_s, head=head_s, head_pos=arrays.head_pos,
            head_tiles=tiles, head_ptr=ptr, head_col=col,
            dense_residual=ScalarQuant(q=jnp.asarray(res_q[lo:hi]),
                                       scale=arrays.dense_residual.scale,
                                       zero=arrays.dense_residual.zero),
            sparse_residual=PaddedSparseRows(
                cols=jnp.asarray(sres_cols[lo:hi]),
                vals=jnp.asarray(sres_vals[lo:hi])),
            num_points=n_local, d_active=arrays.d_active,
            head_max_steps=max_steps, codes_packed=arrays.codes_packed,
            valid_mask=(jnp.asarray(vmask[lo:hi])
                        if vmask is not None else None)))
    return shards, offsets


def _pass1_scores_local(codes, lut, inv_rows, inv_vals, q_dims, q_vals,
                        backend: eng.Backend):
    """Approximate hybrid scores for the local row-shard, via the engine.

    For backend PALLAS_PACKED, ``codes`` is the packed (N_local, ceil(K/2))
    form — packed codes row-shard exactly like unpacked ones, so each device
    streams (and stores) half the code bytes."""
    n_local = codes.shape[0]
    inv = PaddedInvertedIndex(rows=inv_rows, vals=inv_vals,
                              num_points=n_local)
    return (eng.adc_scores(codes, lut, backend)
            + score_inverted(inv, q_dims, q_vals))


def _pass1_topk_local(codes, lut, inv_rows, inv_vals, q_dims, q_vals, *,
                      k: int, backend: eng.Backend):
    """Per-shard pass-1 top-k: fused scan-and-select (DESIGN.md §2.5) on the
    Pallas backends when k fits the candidate buffer — the per-device
    (Q, N_local) score matrix never hits HBM — else materialize + top_k.
    Both routes are bit-identical (shared block partial sums; fp32 add is
    commutative), so the fan-out merge sees the same candidates either way."""
    from repro.kernels.ops import MAX_FUSED_CANDIDATES, lut16_adc_topk
    n_local = codes.shape[0]
    inv = PaddedInvertedIndex(rows=inv_rows, vals=inv_vals,
                              num_points=n_local)
    if (backend in (eng.Backend.PALLAS, eng.Backend.PALLAS_PACKED)
            and k <= MAX_FUSED_CANDIDATES):
        bias = score_inverted(inv, q_dims, q_vals)
        return lut16_adc_topk(
            codes, lut, k, bias=bias,
            packed=backend is eng.Backend.PALLAS_PACKED)
    scores = (eng.adc_scores(codes, lut, backend)
              + score_inverted(inv, q_dims, q_vals))
    return jax.lax.top_k(scores, k)


def _pass1_local(codes, lut, inv_rows, inv_vals, q_dims, q_vals, row_offset,
                 *, k: int, axis: str, backend: eng.Backend):
    """Runs on one shard (inside shard_map): engine pass-1 top-k for the
    local rows (fused on the Pallas backends), then all_gather the
    candidate sets."""
    local_s, local_i = _pass1_topk_local(codes, lut, inv_rows, inv_vals,
                                         q_dims, q_vals, k=k, backend=backend)
    local_i = local_i + row_offset[0]                  # globalize ids
    all_s = jax.lax.all_gather(local_s, axis, axis=1, tiled=True)  # (Q, S*k)
    all_i = jax.lax.all_gather(local_i, axis, axis=1, tiled=True)
    return merge_topk(all_s, all_i, k)


def make_sharded_search_fn(mesh: Mesh, *, k: int, axis: str = "data",
                           adc: str = "gather"):
    """Build the jit-able sharded pass-1 search.

    Index arrays are sharded on their row axis over `axis`; queries and LUTs
    are replicated.  Returns fn(codes, lut, inv_rows, inv_vals, q_dims,
    q_vals, row_offset) -> (scores (Q,k), global ids (Q,k)).

    row_offset: (num_shards,) int32 — global row id of each shard's first row.
    adc: an engine Backend name — "ref"/"gather" (reference), "onehot"/
    "onehot-mxu" (MXU contraction), "pallas" (LUT16 kernel), or
    "pallas-packed" (LUT16 over two-per-byte 4-bit codes: pass codes packed
    via kernels pack_codes; half the per-device HBM, same row sharding).
    """
    backend = eng.Backend.from_name(adc)
    spec_rows = P(axis)        # row-sharded index structures
    spec_rep = P()             # replicated queries
    fn = jax.shard_map(
        functools.partial(_pass1_local, k=k, axis=axis, backend=backend),
        mesh=mesh,
        in_specs=(spec_rows, spec_rep, P(axis, None), P(axis, None),
                  spec_rep, spec_rep, P(axis)),
        out_specs=(spec_rep, spec_rep),
        check_vma=False,
    )
    return jax.jit(fn)


def sharded_pass1_topk(mesh: Mesh, codes, lut, inv_rows, inv_vals, q_dims,
                       q_vals, *, k: int, axis: str = "data",
                       adc: str = "gather"):
    """Convenience wrapper: shards the inputs, runs the pass-1 search.

    NOTE inv_rows/inv_vals must be *per-shard stacked*: shape
    (num_shards * d_active, L) where each shard's slice holds row ids
    local to that shard.  ``row_offset`` is derived from equal row sharding.
    """
    num_shards = mesh.shape[axis]
    n = codes.shape[0]
    assert n % num_shards == 0
    row_offset = jnp.arange(num_shards, dtype=jnp.int32) * (n // num_shards)
    fn = make_sharded_search_fn(mesh, k=k, axis=axis, adc=adc)
    return fn(codes, lut, inv_rows, inv_vals, q_dims, q_vals, row_offset)


# ---------------------------------------------------------------------------
# Full three-pass sharded search (paper §7.2: every server refines locally,
# the coordinator merges refined top-h)
# ---------------------------------------------------------------------------

def _search3_local(codes, lut, inv_rows, inv_vals, res_q, res_scale, res_zero,
                   sres_cols, sres_vals, q_dims, q_vals, q_dense, q_cols,
                   row_offset, *, h: int, alpha: int, beta: int, axis: str,
                   backend: eng.Backend):
    """One shard's full three-pass search; candidate counts are per-shard so
    every server does the paper's reordering on its own rows."""
    n_local = codes.shape[0]
    c1 = min(max(alpha * h, h), n_local)
    c2 = min(max(beta * h, h), c1)

    # pass 1: local candidates, overfetch c1 (fused on Pallas backends)
    s1, ids1 = _pass1_topk_local(codes, lut, inv_rows, inv_vals,
                                 q_dims, q_vals, k=c1, backend=backend)

    # pass 2: + local dense residual rows, keep c2
    sq = ScalarQuant(q=res_q, scale=res_scale, zero=res_zero)
    extra_d = res.dense_residual_scores(sq, ids1, q_dense)
    s2, ids2 = res.reorder_pass(s1, ids1, extra_d, c2)

    # pass 3: + local sparse residual rows, local top-h
    rows = PaddedSparseRows(cols=sres_cols, vals=sres_vals)
    extra_s = res.sparse_residual_scores(rows, ids2, q_cols)
    s3, ids3 = res.reorder_pass(s2, ids2, extra_s, h)

    ids3 = ids3 + row_offset[0]                        # globalize ids
    all_s = jax.lax.all_gather(s3, axis, axis=1, tiled=True)   # (Q, S*h)
    all_i = jax.lax.all_gather(ids3, axis, axis=1, tiled=True)
    return merge_topk(all_s, all_i, h)


def make_sharded_search3_fn(mesh: Mesh, *, h: int, alpha: int = 20,
                            beta: int = 5, axis: str = "data",
                            adc: str = "gather"):
    """Build the jit-able sharded THREE-pass search.

    Row-sharded over `axis`: codes (N, K) — or (N, ceil(K/2)) packed
    two-per-byte when adc="pallas-packed" — inv_rows/inv_vals (per-shard
    stacked, see sharded_pass1_topk), res_q (N, d^D) int8 dense-residual rows,
    sres_cols/sres_vals (N, R) padded sparse-residual rows.  Replicated: lut,
    res_scale/res_zero, q_dims/q_vals, q_dense (Q, d^D), q_cols
    (Q, d_active + 1) — the padded sparse queries scattered into the compact
    column space (engine.scatter_queries_compact).  row_offset: (S,) int32.

    Returns fn(...) -> (scores (Q, h), global ids (Q, h)).
    """
    backend = eng.Backend.from_name(adc)
    rows = P(axis)
    rep = P()
    fn = jax.shard_map(
        functools.partial(_search3_local, h=h, alpha=alpha, beta=beta,
                          axis=axis, backend=backend),
        mesh=mesh,
        in_specs=(rows, rep, P(axis, None), P(axis, None),   # codes, lut, inv
                  rows, rep, rep,                            # dense residual
                  rows, rows,                                # sparse residual
                  rep, rep, rep, rep,                        # queries
                  P(axis)),                                  # row_offset
        out_specs=(rep, rep),
        check_vma=False,
    )
    return jax.jit(fn)


def sharded_three_pass_topk(mesh: Mesh, codes, lut, inv_rows, inv_vals,
                            res_q, res_scale, res_zero, sres_cols, sres_vals,
                            q_dims, q_vals, q_dense, q_cols, *, h: int,
                            alpha: int = 20, beta: int = 5,
                            axis: str = "data", adc: str = "gather"):
    """Convenience wrapper: places the inputs on the mesh, derives
    row_offset from equal row sharding, and runs the full three-pass
    fan-out search.

    Row-sharded inputs go through ``NamedSharding(mesh, P(axis))``, so from
    host (numpy) arrays every device receives only its own rows and no
    device ever holds the whole index; the rest is replicated."""
    num_shards = mesh.shape[axis]
    n = codes.shape[0]
    assert n % num_shards == 0
    row_offset = np.arange(num_shards, dtype=np.int32) * (n // num_shards)
    rows, rep = NamedSharding(mesh, P(axis)), NamedSharding(mesh, P())
    placed = [jax.device_put(x, s) for x, s in (
        (codes, rows), (lut, rep), (inv_rows, rows), (inv_vals, rows),
        (res_q, rows), (res_scale, rep), (res_zero, rep),
        (sres_cols, rows), (sres_vals, rows), (q_dims, rep), (q_vals, rep),
        (q_dense, rep), (q_cols, rep), (row_offset, rows))]
    fn = make_sharded_search3_fn(mesh, h=h, alpha=alpha, beta=beta, axis=axis,
                                 adc=adc)
    return fn(*placed)
