"""Snapshot store: one durable, versioned copy of a pristine index
generation (DESIGN.md §7.1).

A snapshot is exactly what the batch build (or a compaction, which IS the
batch build) produces: every ``IndexArrays`` leaf — PQ codes in whatever
packing the engine serves (the packed two-per-byte form is stored as-is,
half the bytes on disk too), codebooks, the frozen residual grid, padded
posting lists, the tile head — plus the host-side artifacts search needs
(``pi``, the compact column space) and the retained corpus that makes the
generation MUTABLE again after a restart (``MutableState``'s initial rows
+ external ids + the auto-id counter).  By default mutations are NOT part
of a snapshot; they live in the WAL and are replayed through the normal
streaming machinery on recovery, so a plain snapshot is only taken at a
build/compaction point where the delta is empty (``version == 0``).  A
DELTA-STATE snapshot (``delta_state=True``; DESIGN.md §7.6) additionally
serializes the appended rows in insertion order plus the alive flags, and
load replays them through the same insert/delete machinery — recovery
under sustained ingest becomes snapshot + short WAL tail without waiting
for a compaction.

On-disk layout (all under one store root)::

    root/
      CURRENT                 {"format": 1, "snapshot": "snap-000002"}
      snap-000002/
        manifest.json         format version, params, scalars,
                              replay_from_seq, per-leaf table w/ sha256
        <leaf>.bin            raw C-order bytes per array leaf
      wal/wal-*.log           mutation segments (persist/wal.py)

Commit protocol: leaves + manifest are written into ``.tmp-snap-…``, each
blob fsync'd, then ONE atomic rename publishes the directory and CURRENT is
rewritten (tmp + rename) to point at it.  A crash anywhere before the
CURRENT swap leaves the previous snapshot authoritative and at worst a
``.tmp-snap-…`` directory that the next writer sweeps; a crash after it is
a completed commit.  Loading verifies every leaf's sha256 before the index
is allowed to serve.

Device-array re-derivation on load is the SAME deterministic host assembly
the batch build runs (``IndexArrays.build``: head scatter table, BCSR
tiles), so a loaded engine is bit-identical to the one that was saved.
"""

from __future__ import annotations

import dataclasses
import json
import os
import shutil

import jax.numpy as jnp
import numpy as np
import scipy.sparse as sp

from repro.checkpoint.leaves import (fsync_dir, read_array_blob,
                                     write_array_blob)

__all__ = ["FORMAT_VERSION", "write_snapshot", "load_snapshot",
           "read_current", "list_snapshots", "store_files"]

FORMAT_VERSION = 1
_CURRENT = "CURRENT"
_MANIFEST = "manifest.json"
_SNAP_PREFIX, _TMP_PREFIX = "snap-", ".tmp-snap-"


def read_current(root: str) -> dict | None:
    """The committed CURRENT pointer, or None when the store is empty."""
    path = os.path.join(root, _CURRENT)
    if not os.path.exists(path):
        return None
    with open(path) as f:
        return json.load(f)


def list_snapshots(root: str) -> list[str]:
    """Committed snapshot directory names, oldest first."""
    if not os.path.isdir(root):
        return []
    return sorted(d for d in os.listdir(root) if d.startswith(_SNAP_PREFIX))


def store_files(root: str) -> list[str]:
    """Root-relative paths of the files a fresh follower needs to copy to
    bootstrap from this store — the snapshot-distribution manifest of the
    cluster tier (DESIGN.md §8.3): the CURRENT pointer plus every file of
    the snapshot it names, CURRENT LAST so a reader copying in order never
    commits a pointer before its target exists.  WAL segments are excluded
    on purpose — the tail ships separately (``MutationWAL.read_frames``)
    and keeps shipping after bootstrap."""
    cur = read_current(root)
    if cur is None:
        raise FileNotFoundError(
            f"{root!r} has no committed snapshot store (CURRENT missing)")
    snap = cur["snapshot"]
    snap_dir = os.path.join(root, snap)
    names = sorted(os.listdir(snap_dir))
    return [f"{snap}/{n}" for n in names] + [_CURRENT]


def _sweep_tmp(root: str) -> None:
    """Remove half-written ``.tmp-snap-…`` directories (crash litter)."""
    for d in os.listdir(root):
        if d.startswith(_TMP_PREFIX):
            shutil.rmtree(os.path.join(root, d), ignore_errors=True)


def _index_leaves(index) -> dict[str, np.ndarray]:
    """Flatten everything a generation needs into named host arrays."""
    st = index.mutable_state
    xs0 = st.x_sparse0
    leaves = {
        "pi": index.pi,
        "cols_global_ids": np.asarray(index.cols.global_ids),
        "inv_rows": np.asarray(index.inv_index.rows),
        "inv_vals": np.asarray(index.inv_index.vals),
        "res_cols": np.asarray(index.sparse_residual.cols),
        "res_vals": np.asarray(index.sparse_residual.vals),
        "centers": np.asarray(index.codebooks.centers),
        "codes": np.asarray(index.codes),
        "dres_q": np.asarray(index.dense_residual.q),
        "dres_scale": np.asarray(index.dense_residual.scale),
        "dres_zero": np.asarray(index.dense_residual.zero),
        "corpus_data": xs0.data,
        "corpus_indices": xs0.indices,
        "corpus_indptr": xs0.indptr,
        "corpus_dense": st.x_dense0,
        "ids_built": st.ids_built,
    }
    if index.head is not None:
        leaves["head_block"] = np.asarray(index.head.block)
        leaves["head_occupancy"] = np.asarray(index.head.occupancy)
        leaves["head_dims"] = np.asarray(index.head.head_dims)
    return leaves


def _delta_leaves(st) -> dict[str, np.ndarray]:
    """Extra leaves of a DELTA-STATE snapshot (DESIGN.md §7.6): every
    appended row in INSERTION order (CSR parts + dense + external ids) plus
    the alive flags of both tiers.  Load replays the rows one by one
    through the normal ``MutableState.insert`` machinery — upsert kill
    chains included — then applies the flags as deletes, so the rebuilt
    delta shard / tombstone set is bit-identical to the one serialized."""
    if st.extra_sparse:
        xse = sp.vstack(st.extra_sparse, format="csr")
        xde = np.stack(st.extra_dense).astype(np.float32)
    else:
        xse = sp.csr_matrix((0, st.x_sparse0.shape[1]), dtype=np.float32)
        xde = np.zeros((0, st.x_dense0.shape[1]), np.float32)
    return {
        "extra_data": xse.data,
        "extra_indices": xse.indices,
        "extra_indptr": xse.indptr,
        "extra_dense": xde,
        "extra_ids": np.asarray(st.extra_ids, np.int64),
        "extra_alive": np.asarray(st.extra_alive, np.uint8),
        "alive0": st.alive0.astype(np.uint8),
    }


def write_snapshot(root: str, index, *, replay_from_seq: int,
                   keep_last: int = 2, delta_state: bool = False) -> str:
    """Serialize a mutable generation; atomic commit; returns the committed
    snapshot directory.

    ``replay_from_seq`` is the WAL sequence number recovery resumes from —
    every mutation below it is already folded into this snapshot's rows.
    ``keep_last`` older snapshots are garbage-collected after the commit.
    By default raises ``ValueError`` on a non-pristine index (pending delta
    rows or tombstones — compact first; a plain snapshot is a compaction
    output).  ``delta_state=True`` lifts that: the pending delta rows and
    alive flags are serialized too (DESIGN.md §7.6) and load replays them,
    so a LIVE index under ingest can checkpoint without compacting."""
    st = index.mutable_state
    if st is None:
        raise ValueError("snapshots need a mutable index "
                         "(HybridIndex.build(..., mutable=True))")
    if index.shards is not None:
        raise ValueError("a row-sharded index has no snapshot format; "
                         "persist it unsharded")
    if not delta_state and (st.version != 0 or st.delta.count
                            or st.main_tombstones):
        raise ValueError(
            "snapshot requires a pristine generation (no pending delta rows "
            "or tombstones): compact() first — a snapshot is by definition "
            "a build/compaction output, mutations belong to the WAL "
            "(or pass delta_state=True to checkpoint the live state)")
    os.makedirs(root, exist_ok=True)
    _sweep_tmp(root)
    # max+1, not count+1: GC shrinks the list, and a recycled name would
    # collide with a still-existing directory at the commit rename
    existing = [int(s[len(_SNAP_PREFIX):]) for s in list_snapshots(root)]
    seqno = max(existing, default=0) + 1
    name = f"{_SNAP_PREFIX}{seqno:06d}"
    tmp = os.path.join(root, f"{_TMP_PREFIX}{seqno:06d}")
    final = os.path.join(root, name)
    os.makedirs(tmp)
    try:
        leaves = _index_leaves(index)
        if delta_state:
            leaves.update(_delta_leaves(st))
        table = {k: write_array_blob(os.path.join(tmp, f"{k}.bin"), v)
                 for k, v in leaves.items()}
        manifest = {
            "format": FORMAT_VERSION,
            "replay_from_seq": int(replay_from_seq),
            "params": dataclasses.asdict(index.params),
            "scalars": {
                "num_points": int(index.num_points),
                "d_dense": int(index.d_dense),
                "inv_num_points": int(index.inv_index.num_points),
                "codes_packed": bool(index.engine.arrays.codes_packed),
                "backend": index.engine.backend.value,
                "next_id": int(st.next_id),
                "delta_capacity": int(st.delta.capacity),
                "delta_state": bool(delta_state),
                "corpus_shape": list(st.x_sparse0.shape),
                "head": (None if index.head is None else
                         {"block_rows": index.head.block_rows,
                          "block_cols": index.head.block_cols}),
            },
            "leaves": table,
        }
        with open(os.path.join(tmp, _MANIFEST), "w") as f:
            json.dump(manifest, f, indent=1)
            f.flush()
            os.fsync(f.fileno())
        # the blobs' contents are fsync'd, but their directory ENTRIES
        # live in tmp's dirent table — flush those before the publish
        # rename, or a committed snapshot could point at files that never
        # hit disk
        fsync_dir(tmp)
    except BaseException:
        shutil.rmtree(tmp, ignore_errors=True)
        raise
    os.rename(tmp, final)                      # publish the directory
    fsync_dir(root)
    cur_tmp = os.path.join(root, _CURRENT + ".tmp")
    with open(cur_tmp, "w") as f:
        json.dump({"format": FORMAT_VERSION, "snapshot": name}, f)
        f.flush()
        os.fsync(f.fileno())
    os.replace(cur_tmp, os.path.join(root, _CURRENT))   # commit
    fsync_dir(root)
    for old in list_snapshots(root)[:-max(keep_last, 1)]:
        shutil.rmtree(os.path.join(root, old), ignore_errors=True)
    return final


def load_snapshot(root: str, *, snapshot: str | None = None,
                  backend=None, verify: bool = True):
    """Load the committed (or a named) snapshot back into a mutable
    ``HybridIndex``; returns ``(index, manifest)``.

    Every leaf's sha256 is checked (``verify=False`` skips, for benchmarks
    only).  ``backend`` overrides the recorded engine backend — the stored
    codes stay in their recorded packing; ref/onehot backends unpack in-jit,
    so any backend can serve any snapshot."""
    from repro.core.engine import Backend, IndexArrays, ScoringEngine
    from repro.core.hybrid import HybridIndex, HybridIndexParams
    from repro.core.pq import PQCodebooks, ScalarQuant
    from repro.core.sparse_index import (CompactColumns, PaddedInvertedIndex,
                                         PaddedSparseRows, TileSparseHead)
    from repro.core.streaming import MutableState

    if snapshot is None:
        cur = read_current(root)
        if cur is None:
            raise FileNotFoundError(f"no committed snapshot under {root!r}")
        if cur.get("format") != FORMAT_VERSION:
            raise ValueError(f"unsupported snapshot store format "
                             f"{cur.get('format')!r} (have {FORMAT_VERSION})")
        snapshot = cur["snapshot"]
    snap_dir = os.path.join(root, snapshot)
    with open(os.path.join(snap_dir, _MANIFEST)) as f:
        manifest = json.load(f)
    if manifest.get("format") != FORMAT_VERSION:
        raise ValueError(f"unsupported snapshot format "
                         f"{manifest.get('format')!r}")
    table = manifest["leaves"]

    def leaf(name):
        return read_array_blob(os.path.join(snap_dir, table[name]["file"]),
                               table[name], verify=verify)

    sc = manifest["scalars"]
    params = HybridIndexParams(**manifest["params"])
    if backend is not None:
        params = dataclasses.replace(
            params, backend=Backend.from_name(backend).value,
            pack_codes=bool(sc["codes_packed"]))
    resolved = params.resolve_backend()

    cols = CompactColumns(global_ids=leaf("cols_global_ids"))
    inv_index = PaddedInvertedIndex(rows=jnp.asarray(leaf("inv_rows")),
                                    vals=jnp.asarray(leaf("inv_vals")),
                                    num_points=int(sc["inv_num_points"]))
    head = None
    head_dim_ids = np.empty(0, np.int32)
    if sc["head"] is not None:
        head = TileSparseHead(block=jnp.asarray(leaf("head_block")),
                              occupancy=jnp.asarray(leaf("head_occupancy")),
                              head_dims=jnp.asarray(leaf("head_dims")),
                              block_rows=int(sc["head"]["block_rows"]),
                              block_cols=int(sc["head"]["block_cols"]))
        head_dim_ids = np.asarray(head.head_dims)
    sparse_residual = PaddedSparseRows(cols=jnp.asarray(leaf("res_cols")),
                                       vals=jnp.asarray(leaf("res_vals")))
    codebooks = PQCodebooks(centers=jnp.asarray(leaf("centers")))
    dres = ScalarQuant(q=jnp.asarray(leaf("dres_q")),
                       scale=jnp.asarray(leaf("dres_scale")),
                       zero=jnp.asarray(leaf("dres_zero")))
    arrays = IndexArrays.build(
        codebooks=codebooks, codes=jnp.asarray(leaf("codes")),
        inv_index=inv_index, head=head, dense_residual=dres,
        sparse_residual=sparse_residual,
        num_points=int(sc["num_points"]), d_active=cols.num_active,
        with_bcsr=resolved in (Backend.PALLAS, Backend.PALLAS_PACKED),
        pre_packed=bool(sc["codes_packed"]))
    engine = ScoringEngine(arrays=arrays, backend=resolved)
    idx = HybridIndex(params=params, num_points=int(sc["num_points"]),
                      pi=leaf("pi"), cols=cols, inv_index=inv_index,
                      head=head, head_dim_ids=head_dim_ids,
                      sparse_residual=sparse_residual, codebooks=codebooks,
                      codes=arrays.codes, dense_residual=dres,
                      d_dense=int(sc["d_dense"]), engine=engine)
    xs0 = sp.csr_matrix(
        (leaf("corpus_data"), leaf("corpus_indices"), leaf("corpus_indptr")),
        shape=tuple(sc["corpus_shape"]))
    ms = MutableState(
        idx, xs0, leaf("corpus_dense"), ext_ids=leaf("ids_built"),
        # restore the pre-sized delta capacity: replaying a long WAL tail
        # into the default would re-pay every growth re-materialization
        delta_capacity=int(sc.get("delta_capacity", 64)))
    idx.mutable_state = ms
    if sc.get("delta_state"):
        # DELTA-STATE snapshot (DESIGN.md §7.6): replay the serialized
        # appended rows one by one through the NORMAL insert path — same
        # order, same ids, so every upsert kill chain, capacity doubling
        # and posting append happens exactly as it did live — then apply
        # the stored alive flags as deletes.  Bit-identical final state.
        eids = leaf("extra_ids")
        ealive = leaf("extra_alive").astype(bool)
        alive0 = leaf("alive0").astype(bool)
        xse = sp.csr_matrix(
            (leaf("extra_data"), leaf("extra_indices"),
             leaf("extra_indptr")),
            shape=(len(eids), int(sc["corpus_shape"][1])))
        xde = leaf("extra_dense")
        for j in range(len(eids)):
            ms.insert(xse[j], xde[j:j + 1], ids=eids[j:j + 1])
        dead = [e for e, (kind, i) in ms._loc.items()
                if not (alive0[i] if kind == "init" else ealive[i])]
        if dead:
            ms.delete(sorted(dead))
    ms.next_id = max(ms.next_id, int(sc["next_id"]))
    return idx, manifest
