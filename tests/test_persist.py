"""Durable index persistence (repro/persist, DESIGN.md §7).

The headline property (ISSUE 5 acceptance): for random insert/delete/
search/compact interleavings served through a durable ``QueryService``,
killing the process and truncating the WAL at ARBITRARY byte offsets, then
recovering, yields search results bit-identical — ids AND scores — to an
index that applied exactly the mutations whose WAL records survived
complete ("recover to the last complete record"), across backends
{ref, pallas, pallas-packed} and odd/even PQ subspace counts.

Plus unit coverage of the two mechanisms the property rests on: the framed
checksummed WAL (torn tails, crc corruption, rotation/truncation, reopen-
after-crash) and the snapshot store (bit-exact leaf round trip, checksum
verification, pristine-only rule, atomic commit leaving no litter on
failure).

Group commit + delta-state snapshots (DESIGN.md §7.6) extend the matrix:
one shared fsync acks a whole batch of framed records (crash between batch
fsyncs loses only unacked mutations, property-tested at arbitrary WAL
byte-truncation points), and ``checkpoint()`` folds the LIVE delta into a
snapshot so recovery under sustained ingest replays only a short tail.
"""

import os
import shutil
import tempfile

import numpy as np
import pytest
import scipy.sparse as sp

from hypothesis import given, settings, strategies as st

from repro import persist
from repro.core.hybrid import HybridIndex, HybridIndexParams
from repro.data import make_hybrid_dataset
from repro.persist.wal import _scan_segment
from repro.serve import QueryService

# -- shared tiny workload ----------------------------------------------------

N0, N_POOL, NQ = 120, 170, 3
D_SPARSE, NNZ = 360, 12

_DS_CACHE = {}


def _cached_dataset(d_dense):
    if d_dense not in _DS_CACHE:
        _DS_CACHE[d_dense] = make_hybrid_dataset(
            num_points=N_POOL, num_queries=NQ, d_sparse=D_SPARSE,
            d_dense=d_dense, nnz_per_row=NNZ, seed=23)
    return _DS_CACHE[d_dense]


def _params(backend, k):
    return HybridIndexParams(keep_top=24, head_dims=12, kmeans_iters=3,
                             backend=backend, pq_subspaces=k)


def _build_mutable(ds, params, n0=N0):
    return HybridIndex.build(ds.x_sparse[:n0], ds.x_dense[:n0], params,
                             mutable=True)


def _search(index, ds, h=8):
    r = index.search(ds.q_sparse, ds.q_dense, h=h)
    return np.asarray(r.ids), np.asarray(r.scores)


# -- WAL framing / truncation / corruption -----------------------------------

def _tiny_wal(root, n=3):
    wal = persist.MutationWAL(os.path.join(root, "wal"))
    seqs = []
    for i in range(n):
        seqs.append(wal.append_insert(
            sp.csr_matrix(np.eye(2, 5, dtype=np.float32) * (i + 1)),
            np.full((2, 3), i, np.float32),
            np.asarray([2 * i, 2 * i + 1])))
    wal.close()
    return wal.segment_paths[-1], seqs


def test_wal_roundtrip_and_reopen(tmp_path):
    """Append/replay round trip is bit-exact (dtypes included), and
    reopening continues the sequence after the last complete record."""
    root = str(tmp_path)
    path, seqs = _tiny_wal(root)
    wal = persist.MutationWAL(os.path.join(root, "wal"))
    records = wal.records()
    assert [r.seq for r in records] == seqs == [1, 2, 3]
    a = records[1].arrays
    assert a["data"].dtype == np.float32
    np.testing.assert_array_equal(
        a["dense"], np.full((2, 3), 1, np.float32))
    np.testing.assert_array_equal(a["ids"], [2, 3])
    assert wal.next_seq == 4
    wal.append_delete([7])
    assert wal.records()[-1].kind == persist.RECORD_DELETE
    wal.close()


def test_wal_truncation_every_byte_offset(tmp_path):
    """Truncating the log at EVERY byte offset recovers exactly the records
    that are complete below the cut — never a partial one, never a crash."""
    root = str(tmp_path)
    path, _ = _tiny_wal(root)
    full = open(path, "rb").read()
    records, size, clean = _scan_segment(path)
    assert clean and size == len(full) and len(records) == 3
    counts = []
    for cut in range(len(full) + 1):
        with open(path, "wb") as f:
            f.write(full[:cut])
        got, valid, _ = _scan_segment(path)
        # every surviving record is an original prefix, in order
        assert [g.seq for g in got] == [r.seq for r in records[:len(got)]]
        assert valid <= cut
        # reopening for append truncates the torn tail and resumes
        wal = persist.MutationWAL(os.path.join(root, "wal"))
        assert wal.next_seq == (got[-1].seq + 1 if got else 1)
        assert os.path.getsize(path) == valid
        wal.close()
        counts.append(len(got))
    assert counts[0] == 0 and counts[-1] == 3
    assert sorted(set(counts)) == [0, 1, 2, 3]   # every prefix reachable


def test_wal_crc_corruption_stops_replay(tmp_path):
    """A flipped byte — in a payload OR in the header's seq field — fails
    the crc and replay stops at the last record before it instead of
    silently skipping or reordering a mutation."""
    root = str(tmp_path)
    path, _ = _tiny_wal(root)
    full = open(path, "rb").read()
    records, _, _ = _scan_segment(path)
    assert len(records) == 3
    buf = bytearray(full)
    buf[len(buf) // 2] ^= 0xFF            # inside record 2's payload
    with open(path, "wb") as f:
        f.write(bytes(buf))
    got, _, clean = _scan_segment(path)
    assert not clean and len(got) < 3
    # header corruption: flip a byte of record 1's seq field (offset 3-10)
    # — the crc covers the header prefix, so this must NOT decode as a
    # valid record with a different seq
    buf = bytearray(full)
    buf[5] ^= 0xFF
    with open(path, "wb") as f:
        f.write(bytes(buf))
    got, valid, clean = _scan_segment(path)
    assert not clean and len(got) == 0 and valid == 0


def test_wal_refuses_midlog_bitrot(tmp_path):
    """Corruption with intact records decodable AFTER it is bitrot, not a
    torn tail: reopening for append must refuse to truncate the acked
    records away, and replay over a corrupt SEALED segment must raise."""
    root = str(tmp_path)
    path, _ = _tiny_wal(root)
    buf = bytearray(open(path, "rb").read())
    records, _, _ = _scan_segment(path)
    assert len(records) == 3
    buf[len(buf) // 2] ^= 0xFF            # record 2; record 3 stays intact
    with open(path, "wb") as f:
        f.write(bytes(buf))
    with pytest.raises(ValueError, match="bitrot"):
        persist.MutationWAL(os.path.join(root, "wal"))


def test_wal_refuses_corrupt_sealed_segment(tmp_path):
    """A rotated (non-active) segment can never hold a torn tail — any
    anomaly there is acked-data loss and replay raises."""
    wal = persist.MutationWAL(str(tmp_path / "wal"))
    for _ in range(2):
        wal.append_delete([1])
    wal.rotate()
    wal.append_delete([2])
    sealed = wal.segment_paths[0]
    buf = bytearray(open(sealed, "rb").read())
    buf[-1] ^= 0xFF                       # corrupt the sealed segment
    with open(sealed, "wb") as f:
        f.write(bytes(buf))
    with pytest.raises(ValueError, match="sealed"):
        wal.records()
    wal.close()


def test_wal_rotate_and_truncate_segments(tmp_path):
    """rotate() cuts a fresh segment at next_seq; truncate_before drops
    fully superseded segments and never the active one."""
    wal = persist.MutationWAL(str(tmp_path / "wal"))
    for _ in range(3):
        wal.append_delete([1])
    first_new = wal.rotate()
    assert first_new == 4
    wal.append_delete([2])
    assert len(wal.segment_paths) == 2
    assert wal.truncate_before(first_new) == 1
    assert len(wal.segment_paths) == 1
    assert [r.seq for r in wal.records()] == [4]
    assert wal.truncate_before(10 ** 6) == 0      # active never deleted
    wal.close()


# -- WAL group commit (DESIGN.md §7.6) ----------------------------------------

def test_wal_group_commit_defers_and_batches_fsync(tmp_path, monkeypatch):
    """sync=False appends defer the disk sync; one ``sync_to`` then fsyncs
    ONCE for the whole raced-in batch, later calls below the watermark are
    no-ops, and ``append_many`` amortizes framing + flush + fsync the same
    way — the shared-fsync ack path."""
    import repro.persist.wal as wal_mod
    calls = {"n": 0}
    real_fsync = os.fsync

    def counting_fsync(fd):
        calls["n"] += 1
        return real_fsync(fd)

    monkeypatch.setattr(wal_mod.os, "fsync", counting_fsync)
    wal = persist.MutationWAL(str(tmp_path / "wal"))
    seqs = [wal.append_delete([i], sync=False) for i in range(5)]
    assert seqs == [1, 2, 3, 4, 5]
    assert calls["n"] == 0 and wal._synced_seq == 0    # nothing acked yet
    wal.sync_to(seqs[-1])
    assert calls["n"] == 1 and wal._synced_seq == 5    # one fsync, all acked
    for s in seqs:
        wal.sync_to(s)                                 # already covered
    assert calls["n"] == 1
    wal.sync_to(wal.append_delete([9], sync=False))
    assert calls["n"] == 2 and wal._synced_seq == 6
    got = wal.append_many([
        (persist.RECORD_DELETE, {"ids": np.asarray([j], np.int64)})
        for j in range(4)])
    assert got == [7, 8, 9, 10]
    assert calls["n"] == 3 and wal._synced_seq == 10
    assert [r.seq for r in wal.records()] == list(range(1, 11))
    wal.close()


def test_wal_group_commit_rotate_seals_durably(tmp_path):
    """A deferred (sync=False) record followed by ``rotate()`` lands
    fsync'd INSIDE the sealed segment — sealing must never strand a
    flushed-but-unsynced group-commit record in a file no later
    ``sync_to`` can reach — and the sync watermark resets to the new
    segment's base."""
    wal = persist.MutationWAL(str(tmp_path / "wal"))
    a = wal.append_delete([1], sync=False)
    assert wal._synced_seq == 0
    first = wal.rotate()
    assert first == 2 and wal._synced_seq == 1         # sealed ⇒ durable
    b = wal.append_delete([2], sync=False)
    assert wal._synced_seq == 1
    wal.sync_to(b)
    assert wal._synced_seq == 2
    assert [r.seq for r in wal.records()] == [a, b] == [1, 2]
    wal.close()
    reopened = persist.MutationWAL(str(tmp_path / "wal"))
    assert reopened.next_seq == 3
    assert [r.seq for r in reopened.records()] == [1, 2]
    reopened.close()


@settings(max_examples=2, deadline=None)
@given(st.integers(0, 9999))
def test_group_commit_crash_matrix_truncation(seed):
    """Group-commit batches, then a crash at ARBITRARY WAL byte offsets:
    the surviving log is always a clean in-order record prefix, and every
    batch whose shared fsync returned before the cut point — i.e. the
    flushed size at ack time is below the cut — survives in full.  Acked
    mutations are never lost; only records past the last covering fsync
    can fall off."""
    rng = np.random.default_rng(seed)
    root = tempfile.mkdtemp(prefix="persist-gc-")
    try:
        wal = persist.MutationWAL(os.path.join(root, "wal"))
        seg = wal.segment_paths[-1]
        acked = []                  # (flushed bytes at ack, last acked seq)
        for b in range(5):
            entries = [(persist.RECORD_DELETE,
                        {"ids": np.asarray([10 * b + j], np.int64)})
                       for j in range(int(rng.integers(1, 5)))]
            seqs = wal.append_many(entries)        # one shared fsync = ack
            acked.append((os.path.getsize(seg), seqs[-1]))
        wal.close()
        full = open(seg, "rb").read()
        assert acked[-1][0] == len(full)
        cuts = sorted({0, len(full)}
                      | {int(c) for c in rng.integers(0, len(full) + 1,
                                                      size=12)}
                      | {s for s, _ in acked})
        for cut in cuts:
            with open(seg, "wb") as f:
                f.write(full[:cut])
            got, valid, _ = _scan_segment(seg)
            assert [g.seq for g in got] == list(range(1, len(got) + 1))
            assert valid <= cut
            for size_at_ack, last_seq in acked:
                if cut >= size_at_ack:      # crash struck after this ack
                    assert last_seq <= len(got), \
                        f"acked seq {last_seq} lost at cut {cut}"
    finally:
        shutil.rmtree(root, ignore_errors=True)


# -- term fencing (DESIGN.md §8.7) --------------------------------------------

def test_wal_term_monotone_and_persisted(tmp_path):
    """Terms only grow, survive close/reopen via the TERM file, and stamp
    every subsequently appended record."""
    wal = persist.MutationWAL(os.path.join(str(tmp_path), "wal"))
    assert wal.term == 1
    wal.append_delete([1])
    wal.set_term(3)
    with pytest.raises(ValueError, match="monotone"):
        wal.set_term(2)
    wal.set_term(3)                       # idempotent re-adopt is fine
    wal.append_delete([2])
    wal.close()
    wal = persist.MutationWAL(os.path.join(str(tmp_path), "wal"))
    assert wal.term == 3
    terms = [r.term for r in wal.records()]
    assert terms == [1, 3]
    wal.close()


def test_wal_append_frames_zombie_fence(tmp_path):
    """A shipped frame stamped with a term below the follower's is REFUSED
    (the zombie ex-primary fence), while an overlapping re-ship of frames
    the log already holds stays idempotent — the seq<next_seq skip runs
    BEFORE the fence, so old same-term history never trips it."""
    root = str(tmp_path)
    old = persist.MutationWAL(os.path.join(root, "old"))     # term 1
    s1 = old.append_delete([1])
    buf1, _ = old.read_frames(s1)
    follower = persist.MutationWAL(os.path.join(root, "f"))
    follower.append_frames(buf1)                # term-1 history lands
    follower.set_term(2)                        # learns of a promotion
    # the deposed primary keeps writing in term 1 …
    s2 = old.append_delete([2])
    buf2, _ = old.read_frames(s2)
    with pytest.raises(ValueError, match="zombie"):
        follower.append_frames(buf2)            # … and is refused
    assert follower.next_seq == s2              # nothing landed
    # re-shipping already-held term-1 frames is still a no-op, not a raise
    assert follower.append_frames(buf1) == []
    old.close()
    follower.close()


def test_wal_append_frames_adopts_higher_term(tmp_path):
    """A shipped frame carrying a HIGHER term is adopted durably before it
    lands, and the noop term barrier replays as a no-op through recovery's
    ``apply_record``."""
    root = str(tmp_path)
    new = persist.MutationWAL(os.path.join(root, "new"))
    new.set_term(5)
    sn = new.append_noop()                      # the promotion barrier
    assert new.records()[-1].kind == persist.RECORD_NOOP
    buf, seqs = new.read_frames(sn)
    follower = persist.MutationWAL(os.path.join(root, "f"))
    recs = follower.append_frames(buf)
    assert seqs == [sn] and [r.seq for r in recs] == [sn]
    assert follower.term == 5                   # adopted …
    follower.close()
    follower = persist.MutationWAL(os.path.join(root, "f"))
    assert follower.term == 5                   # … and persisted
    persist.apply_record(object(), recs[0])     # noop touches nothing
    follower.close()
    new.close()


def test_wal_start_seq_bootstrap_continues_at_horizon(tmp_path):
    """A brand-new log opened with ``start_seq=N`` (a follower whose
    fetched snapshot has ``replay_from_seq=N``) accepts shipped frames
    starting at N instead of seeing a 1..N-1 gap — the post-compaction
    bootstrap path."""
    root = str(tmp_path)
    primary = persist.MutationWAL(os.path.join(root, "p"))
    for i in range(4):
        primary.append_delete([i])
    primary.rotate()                            # compaction cut at seq 5
    s5 = primary.append_delete([99])
    assert s5 == 5
    buf, _ = primary.read_frames(5)
    fresh = persist.MutationWAL(os.path.join(root, "f"), start_seq=5)
    assert fresh.next_seq == 5
    recs = fresh.append_frames(buf)             # no gap error
    assert [r.seq for r in recs] == [5]
    fresh.close()
    # start_seq is ignored once segments exist: reopen resumes after 5
    fresh = persist.MutationWAL(os.path.join(root, "f"), start_seq=1)
    assert fresh.next_seq == 6
    fresh.close()
    primary.close()


# -- snapshot store -----------------------------------------------------------

@pytest.mark.parametrize("backend,k", [("ref", 4), ("pallas-packed", 3)])
def test_snapshot_roundtrip_bit_identical(tmp_path, backend, k):
    """write_snapshot -> load_snapshot reproduces the index bit for bit
    (search ids AND scores), including packed odd-K codes."""
    ds = _cached_dataset(12)
    idx = _build_mutable(ds, _params(backend, k))
    root = str(tmp_path)
    persist.write_snapshot(root, idx, replay_from_seq=1)
    loaded, manifest = persist.load_snapshot(root)
    assert manifest["scalars"]["codes_packed"] == (backend == "pallas-packed")
    ids0, s0 = _search(idx, ds)
    ids1, s1 = _search(loaded, ds)
    np.testing.assert_array_equal(ids1, ids0)
    np.testing.assert_array_equal(s1, s0)
    # the loaded index is mutable and serves inserts immediately
    new = loaded.insert(ds.q_sparse[0] * 1e3, ds.q_dense[0])
    assert loaded.search(ds.q_sparse, ds.q_dense, h=4).ids[0, 0] == new[0]


def test_snapshot_checksum_mismatch_raises(tmp_path):
    """A corrupted leaf blob must fail recovery loudly, never serve."""
    ds = _cached_dataset(8)
    idx = _build_mutable(ds, _params("ref", 4))
    root = str(tmp_path)
    persist.write_snapshot(root, idx, replay_from_seq=1)
    snap = persist.list_snapshots(root)[-1]
    blob = os.path.join(root, snap, "codes.bin")
    buf = bytearray(open(blob, "rb").read())
    buf[0] ^= 0xFF
    with open(blob, "wb") as f:
        f.write(bytes(buf))
    with pytest.raises(ValueError, match="checksum mismatch"):
        persist.load_snapshot(root)
    # verify=False skips the check (benchmark path) and does load
    persist.load_snapshot(root, verify=False)


def test_snapshot_requires_pristine_generation(tmp_path):
    """Snapshots are build/compaction outputs: a pending delta or tombstone
    belongs to the WAL, and write_snapshot refuses it."""
    ds = _cached_dataset(8)
    idx = _build_mutable(ds, _params("ref", 4))
    idx.insert(ds.q_sparse[0], ds.q_dense[0])
    with pytest.raises(ValueError, match="pristine"):
        persist.write_snapshot(str(tmp_path), idx, replay_from_seq=1)
    immutable = HybridIndex.build(ds.x_sparse[:40], ds.x_dense[:40],
                                  _params("ref", 4))
    with pytest.raises(ValueError, match="mutable"):
        persist.write_snapshot(str(tmp_path), immutable, replay_from_seq=1)


def test_snapshot_write_failure_leaves_store_clean(tmp_path, monkeypatch):
    """A crash mid-snapshot must leave the previous snapshot authoritative
    and sweep its own temp directory — no torn commit, no litter."""
    ds = _cached_dataset(8)
    idx = _build_mutable(ds, _params("ref", 4))
    root = str(tmp_path)
    persist.write_snapshot(root, idx, replay_from_seq=1)
    before = persist.read_current(root)

    import repro.persist.snapshot as snap_mod
    real = snap_mod.write_array_blob
    calls = {"n": 0}

    def flaky(path, arr):
        calls["n"] += 1
        if calls["n"] > 3:
            raise OSError("disk full (injected)")
        return real(path, arr)

    monkeypatch.setattr(snap_mod, "write_array_blob", flaky)
    with pytest.raises(OSError, match="injected"):
        persist.write_snapshot(root, idx, replay_from_seq=5)
    monkeypatch.setattr(snap_mod, "write_array_blob", real)
    assert persist.read_current(root) == before
    assert not [d for d in os.listdir(root) if d.startswith(".tmp-snap")]
    persist.load_snapshot(root)          # previous snapshot still loads


def test_snapshot_names_stay_monotone_across_gc(tmp_path):
    """REGRESSION: snapshot numbering must be max+1, not count+1 — after
    keep_last GC shrinks the list, a recycled name would collide with a
    still-existing directory at the commit rename."""
    ds = _cached_dataset(8)
    idx = _build_mutable(ds, _params("ref", 4))
    root = str(tmp_path)
    for i in range(4):
        persist.write_snapshot(root, idx, replay_from_seq=i + 1,
                               keep_last=2)
    assert persist.list_snapshots(root) == ["snap-000003", "snap-000004"]
    assert persist.read_current(root)["snapshot"] == "snap-000004"
    persist.load_snapshot(root)


def test_bootstrap_refuses_existing_store(tmp_path):
    ds = _cached_dataset(8)
    idx = _build_mutable(ds, _params("ref", 4))
    root = str(tmp_path / "store")
    persist.bootstrap(root, idx).close()
    with pytest.raises(ValueError, match="already holds"):
        persist.bootstrap(root, idx)
    with pytest.raises(FileNotFoundError, match="CURRENT"):
        persist.recover(str(tmp_path / "nowhere"))


def test_bootstrap_rejection_leaves_no_litter(tmp_path):
    """Bootstrapping a non-pristine index is rejected BEFORE the WAL is
    created: no stray wal/ directory, no open handle, and the root can be
    bootstrapped cleanly after compacting."""
    ds = _cached_dataset(8)
    idx = _build_mutable(ds, _params("ref", 4))
    idx.insert(ds.q_sparse[0], ds.q_dense[0])
    root = str(tmp_path / "store")
    with pytest.raises(ValueError, match="pristine"):
        persist.bootstrap(root, idx)
    assert not os.path.exists(os.path.join(root, "wal"))
    assert persist.read_current(root) is None
    persist.bootstrap(root, idx.compact()).close()
    assert persist.recover(root).replayed == 0


# -- delta-state snapshots (DESIGN.md §7.6) -----------------------------------

@pytest.mark.parametrize("backend,k", [("ref", 4), ("pallas-packed", 3)])
def test_delta_snapshot_roundtrip_bit_identical(tmp_path, backend, k):
    """A LIVE index — delta rows, an upsert, tombstones pending — round-
    trips through a delta-state snapshot bit for bit (ids AND scores, delta
    internals included), and the loaded index keeps serving mutations."""
    ds = _cached_dataset(12)
    idx = _build_mutable(ds, _params(backend, k))
    new = idx.insert(ds.x_sparse[N0:N0 + 9], ds.x_dense[N0:N0 + 9])
    idx.insert(ds.x_sparse[N0 + 9], ds.x_dense[N0 + 9],
               ids=[int(new[2])])                       # upsert a delta row
    assert idx.delete([3, int(new[0])]) == 2            # main + delta kill
    root = str(tmp_path)
    persist.write_snapshot(root, idx, replay_from_seq=1, delta_state=True)
    loaded, manifest = persist.load_snapshot(root)
    assert manifest["scalars"]["delta_state"]
    st0, st1 = idx.mutable_state, loaded.mutable_state
    assert st1.next_id == st0.next_id
    assert st1.main_tombstones == st0.main_tombstones
    assert list(st1.extra_ids) == list(st0.extra_ids)
    assert list(st1.extra_alive) == list(st0.extra_alive)
    assert st1.delta.count == st0.delta.count
    assert st1.delta.dropped_nnz == st0.delta.dropped_nnz
    ids0, s0 = _search(idx, ds)
    ids1, s1 = _search(loaded, ds)
    np.testing.assert_array_equal(ids1, ids0)
    np.testing.assert_array_equal(s1, s0)
    got = loaded.insert(ds.q_sparse[0] * 1e3, ds.q_dense[0])
    assert loaded.search(ds.q_sparse, ds.q_dense, h=4).ids[0, 0] == got[0]


def test_pristine_snapshot_still_refuses_live_state(tmp_path):
    """The default (non-delta) write path keeps the pristine-only rule:
    live deltas belong to checkpoint(), not compaction snapshots."""
    ds = _cached_dataset(8)
    idx = _build_mutable(ds, _params("ref", 4))
    idx.insert(ds.q_sparse[0], ds.q_dense[0])
    with pytest.raises(ValueError, match="delta_state=True"):
        persist.write_snapshot(str(tmp_path), idx, replay_from_seq=1)


def test_service_checkpoint_restores_with_short_tail(tmp_path):
    """svc.checkpoint() cuts a delta-state snapshot mid-stream: a restore
    replays ONLY the post-checkpoint WAL tail and is bit-identical to the
    live pre-close state."""
    ds = _cached_dataset(8)
    idx = _build_mutable(ds, _params("ref", 4))
    root = str(tmp_path / "store")
    svc = QueryService(index=idx, h=8, cache_size=0, auto_compact=False,
                       persist_dir=root)
    svc.insert(ds.x_sparse[N0:N0 + 10], ds.x_dense[N0:N0 + 10])
    svc.delete([1, 4])
    svc.checkpoint()
    assert persist.read_current(root)["snapshot"] == "snap-000002"
    svc.insert(ds.x_sparse[N0 + 10:N0 + 13], ds.x_dense[N0 + 10:N0 + 13])
    svc.delete([7])
    s_live, i_live = svc.search_sparse(ds.q_sparse, ds.q_dense)
    live_stats = svc.stats()
    svc.close()

    svc2 = QueryService(restore_from=root, h=8, cache_size=0,
                        auto_compact=False)
    stats = svc2.stats()
    assert stats["recovered_replayed"] == 2             # only the tail
    assert stats["delta_rows"] == live_stats["delta_rows"]
    assert stats["deleted_pending"] == live_stats["deleted_pending"]
    s_rec, i_rec = svc2.search_sparse(ds.q_sparse, ds.q_dense)
    np.testing.assert_array_equal(i_rec, i_live)
    np.testing.assert_array_equal(s_rec, s_live)
    svc2.close()


def test_service_checkpoint_requires_durability(tmp_path):
    ds = _cached_dataset(8)
    svc = QueryService(index=_build_mutable(ds, _params("ref", 4)), h=8,
                       cache_size=0, auto_compact=False)
    with pytest.raises(ValueError, match="durable service"):
        svc.checkpoint()
    svc.close()


def test_service_auto_delta_checkpoint(tmp_path):
    """delta_snapshot_records=3 cuts a checkpoint every third logged
    mutation: after 7 mutations two auto-checkpoints exist and a restore
    replays only the 1-record tail."""
    ds = _cached_dataset(8)
    idx = _build_mutable(ds, _params("ref", 4))
    root = str(tmp_path / "store")
    svc = QueryService(index=idx, h=8, cache_size=0, auto_compact=False,
                       persist_dir=root, delta_snapshot_records=3)
    for j in range(7):
        svc.insert(ds.x_sparse[N0 + j], ds.x_dense[N0 + j])
    assert persist.read_current(root)["snapshot"] == "snap-000003"
    s_live, i_live = svc.search_sparse(ds.q_sparse, ds.q_dense)
    svc.close()
    svc2 = QueryService(restore_from=root, h=8, cache_size=0,
                        auto_compact=False)
    assert svc2.stats()["recovered_replayed"] == 1
    s_rec, i_rec = svc2.search_sparse(ds.q_sparse, ds.q_dense)
    np.testing.assert_array_equal(i_rec, i_live)
    np.testing.assert_array_equal(s_rec, s_live)
    svc2.close()


def test_service_acks_only_after_shared_fsync(tmp_path):
    """Every service mutation returns with its WAL record fsync-covered:
    the sync watermark tracks the last assigned seq after each ack (the
    group-commit ack-after-shared-fsync contract)."""
    ds = _cached_dataset(8)
    idx = _build_mutable(ds, _params("ref", 4))
    svc = QueryService(index=idx, h=8, cache_size=0, auto_compact=False,
                       persist_dir=str(tmp_path / "store"))
    wal = svc._durability.wal
    for j in range(3):
        svc.insert(ds.x_sparse[N0 + j], ds.x_dense[N0 + j])
        assert wal._synced_seq == wal.next_seq - 1
    svc.delete([0])
    assert wal._synced_seq == wal.next_seq - 1
    svc.close()


# -- crash-recovery property (the acceptance criterion) ----------------------

def _run_durable_ops(svc, ds, rng, n_ops, compact_at=None,
                     checkpoint_at=None):
    """Random insert/upsert/delete interleaving through a durable service;
    returns the per-op records needed to rebuild any prefix by hand.
    Ops AFTER the last compaction/checkpoint cut are returned separately
    (the WAL tail)."""
    tail_ops = []
    live = list(svc._index.mutable_state.ids_built)
    pool = list(range(N0, N_POOL))
    for t in range(n_ops):
        if compact_at is not None and t == compact_at:
            svc.compact()
            tail_ops = []
        if checkpoint_at is not None and t == checkpoint_at:
            svc.checkpoint()
            tail_ops = []
        if rng.random() < 0.62 or len(live) < 4:
            src = pool.pop(0)
            ext = int(rng.choice(live)) if rng.random() < 0.25 else None
            got = svc.insert(ds.x_sparse[src], ds.x_dense[src], ids=ext)
            if ext is None:
                live.append(int(got[0]))
            tail_ops.append(("ins", ds.x_sparse[src], ds.x_dense[src],
                             got.copy()))
        else:
            ext = int(rng.choice(live))
            svc.delete([ext])
            live.remove(ext)
            tail_ops.append(("del", np.asarray([ext], np.int64)))
    return tail_ops


def _apply_ops(index, ops):
    for op in ops:
        if op[0] == "ins":
            index.mutable_state.insert(op[1], op[2], ids=op[3])
        else:
            index.mutable_state.delete(op[1])


def _check_crash_recovery(backend, k, d_dense, seed, compact_mid=False,
                          checkpoint_mid=False):
    """Kill-and-recover at arbitrary WAL byte offsets == an index that
    applied exactly the complete records' mutations, bit for bit."""
    ds = _cached_dataset(d_dense)
    params = _params(backend, k)
    rng = np.random.default_rng(seed)
    root = tempfile.mkdtemp(prefix="persist-prop-")
    try:
        idx = _build_mutable(ds, params)
        svc = QueryService(index=idx, h=8, cache_size=0, auto_compact=False,
                           persist_dir=root)
        n_ops = 10
        tail_ops = _run_durable_ops(
            svc, ds, rng, n_ops,
            compact_at=5 if compact_mid else None,
            checkpoint_at=5 if checkpoint_mid else None)
        ids_live, s_live = _search(svc._index, ds)
        svc.close()

        active = persist.MutationWAL(os.path.join(root, "wal"))
        seg = active.segment_paths[-1]
        active.close()
        full = open(seg, "rb").read()
        records, size, clean = _scan_segment(seg)
        assert clean and len(records) == len(tail_ops)

        # crash points: empty tail, torn header, torn payload, a clean
        # record boundary, and the full log (pure restart)
        mid = size // 2
        offsets = sorted({0, 7, mid, size - 3, size})
        expected = None          # progressive prefix rebuild (offsets sorted)
        applied = 0
        for cut in offsets:
            crash = tempfile.mkdtemp(prefix="persist-crash-")
            shutil.rmtree(crash)
            shutil.copytree(root, crash)
            seg_c = os.path.join(crash, "wal", os.path.basename(seg))
            with open(seg_c, "r+b") as f:
                f.truncate(cut)
            rec = persist.recover(crash)
            rec.durability.close()
            if expected is None:
                expected, _ = persist.load_snapshot(root)
            n = rec.replayed
            assert n == len([r for r in _scan_segment(seg_c)[0]])
            _apply_ops(expected, tail_ops[applied:n])
            applied = max(applied, n)
            ids_r, s_r = _search(rec.index, ds)
            ids_e, s_e = _search(expected, ds)
            np.testing.assert_array_equal(ids_r, ids_e)
            np.testing.assert_array_equal(s_r, s_e)
            shutil.rmtree(crash, ignore_errors=True)
        # the full-log recovery must equal the live pre-crash state exactly
        np.testing.assert_array_equal(ids_e, ids_live)
        np.testing.assert_array_equal(s_e, s_live)
    finally:
        shutil.rmtree(root, ignore_errors=True)


@settings(max_examples=2, deadline=None)
@given(st.integers(0, 9999))
def test_crash_recovery_ref_even_k(seed):
    """recover() ≡ applied-prefix index: ref backend, even K."""
    _check_crash_recovery("ref", 4, 8, seed)


@settings(max_examples=1, deadline=None)
@given(st.integers(0, 9999))
def test_crash_recovery_ref_odd_k(seed):
    """recover() ≡ applied-prefix index: ref backend, odd K."""
    _check_crash_recovery("ref", 3, 12, seed)


@settings(max_examples=1, deadline=None)
@given(st.integers(0, 9999))
def test_crash_recovery_pallas_even_k(seed):
    """recover() ≡ applied-prefix index: pallas backend, even K."""
    _check_crash_recovery("pallas", 4, 8, seed)


@settings(max_examples=1, deadline=None)
@given(st.integers(0, 9999))
def test_crash_recovery_pallas_odd_k(seed):
    """recover() ≡ applied-prefix index: pallas backend, odd K."""
    _check_crash_recovery("pallas", 3, 12, seed)


@settings(max_examples=1, deadline=None)
@given(st.integers(0, 9999))
def test_crash_recovery_packed_even_k(seed):
    """recover() ≡ applied-prefix index: packed 4-bit codes, even K."""
    _check_crash_recovery("pallas-packed", 4, 8, seed)


@settings(max_examples=1, deadline=None)
@given(st.integers(0, 9999))
def test_crash_recovery_packed_odd_k(seed):
    """recover() ≡ applied-prefix index: packed codes, odd-K phantom
    nibble through the WAL-replayed delta append."""
    _check_crash_recovery("pallas-packed", 3, 12, seed)


@settings(max_examples=1, deadline=None)
@given(st.integers(0, 9999))
def test_crash_recovery_with_mid_stream_compaction(seed):
    """Compaction mid-interleaving cuts a snapshot + truncates the WAL;
    crash recovery over the post-compaction tail stays bit-identical."""
    _check_crash_recovery("ref", 4, 8, seed, compact_mid=True)


@settings(max_examples=1, deadline=None)
@given(st.integers(0, 9999))
def test_crash_recovery_with_delta_checkpoint(seed):
    """A delta-state checkpoint mid-interleaving (live delta + tombstones
    folded into the snapshot): crashes at arbitrary byte offsets in the
    post-checkpoint tail recover bit-identically from the delta snapshot
    plus the surviving records."""
    _check_crash_recovery("ref", 4, 8, seed, checkpoint_mid=True)


# -- durable service integration ----------------------------------------------

def test_service_restore_matches_live(tmp_path):
    """Close a durable service mid-stream, restore_from the store: search
    results, delta rows and tombstones are all bit-identical."""
    ds = _cached_dataset(8)
    idx = _build_mutable(ds, _params("ref", 4))
    root = str(tmp_path / "store")
    svc = QueryService(index=idx, h=8, cache_size=0, auto_compact=False,
                       persist_dir=root)
    new = svc.insert(ds.x_sparse[N0:N0 + 12], ds.x_dense[N0:N0 + 12])
    svc.delete([int(new[0]), 3, 9])
    s_live, i_live = svc.search_sparse(ds.q_sparse, ds.q_dense)
    live_stats = svc.stats()
    svc.close()

    svc2 = QueryService(restore_from=root, h=8, cache_size=0,
                        auto_compact=False)
    s_rec, i_rec = svc2.search_sparse(ds.q_sparse, ds.q_dense)
    np.testing.assert_array_equal(i_rec, i_live)
    np.testing.assert_array_equal(s_rec, s_live)
    stats = svc2.stats()
    assert stats["delta_rows"] == live_stats["delta_rows"] == 11
    assert stats["deleted_pending"] == live_stats["deleted_pending"] == 2
    assert stats["recovered_replayed"] == 2 and stats["durable"]
    assert stats["wal_next_seq"] == live_stats["wal_next_seq"]
    svc2.close()


def test_service_compact_checkpoints_and_truncates(tmp_path):
    """compact() on a durable service cuts a snapshot, advances CURRENT,
    and truncates the WAL so the next restore replays nothing."""
    ds = _cached_dataset(8)
    idx = _build_mutable(ds, _params("ref", 4))
    root = str(tmp_path / "store")
    svc = QueryService(index=idx, h=8, cache_size=0, auto_compact=False,
                       persist_dir=root)
    svc.insert(ds.x_sparse[N0:N0 + 8], ds.x_dense[N0:N0 + 8])
    assert persist.read_current(root)["snapshot"] == "snap-000001"
    svc.compact()
    assert persist.read_current(root)["snapshot"] == "snap-000002"
    s_live, i_live = svc.search_sparse(ds.q_sparse, ds.q_dense)
    svc.close()
    svc2 = QueryService(restore_from=root, h=8, cache_size=0,
                        auto_compact=False)
    assert svc2.stats()["recovered_replayed"] == 0
    s_rec, i_rec = svc2.search_sparse(ds.q_sparse, ds.q_dense)
    np.testing.assert_array_equal(i_rec, i_live)
    np.testing.assert_array_equal(s_rec, s_live)
    svc2.close()


def test_service_persist_arg_validation(tmp_path):
    ds = _cached_dataset(8)
    idx = _build_mutable(ds, _params("ref", 4))
    with pytest.raises(ValueError, match="don't also pass"):
        QueryService(index=idx, restore_from=str(tmp_path))
    with pytest.raises(ValueError, match="bootstraps a NEW store"):
        QueryService(persist_dir=str(tmp_path))
    with pytest.raises(FileNotFoundError):
        QueryService(restore_from=str(tmp_path / "missing"))


def test_service_poisoned_after_wal_append_failure(tmp_path, monkeypatch):
    """A failed WAL append propagates (the batch was never acked) and
    poisons the durability handle: further mutations are refused, searches
    keep serving, and a restart recovers to the pre-failure state."""
    ds = _cached_dataset(8)
    idx = _build_mutable(ds, _params("ref", 4))
    root = str(tmp_path / "store")
    svc = QueryService(index=idx, h=8, cache_size=0, auto_compact=False,
                       persist_dir=root)
    ok = svc.insert(ds.x_sparse[N0:N0 + 2], ds.x_dense[N0:N0 + 2])
    s_before, i_before = svc.search_sparse(ds.q_sparse, ds.q_dense)

    def boom(*a, **k):
        raise OSError("disk full (injected)")

    monkeypatch.setattr(svc._durability.wal, "append_insert", boom)
    with pytest.raises(OSError, match="injected"):
        svc.insert(ds.x_sparse[N0 + 2:N0 + 4], ds.x_dense[N0 + 2:N0 + 4])
    with pytest.raises(RuntimeError, match="poisoned"):
        svc.insert(ds.x_sparse[N0 + 4:N0 + 5], ds.x_dense[N0 + 4:N0 + 5])
    with pytest.raises(RuntimeError, match="poisoned"):
        svc.delete([int(ok[0])])
    svc.search_sparse(ds.q_sparse, ds.q_dense)      # serving still works
    svc.close()
    # restart recovers the pre-failure state: only the acked batch replays
    # (compare service-to-service so both sides use the same bucket
    # padding — reduction shapes are part of bit-identity)
    svc2 = QueryService(restore_from=root, h=8, cache_size=0,
                        auto_compact=False)
    assert svc2.stats()["recovered_replayed"] == 1
    s_r, i_r = svc2.search_sparse(ds.q_sparse, ds.q_dense)
    np.testing.assert_array_equal(i_r, i_before)
    np.testing.assert_array_equal(s_r, s_before)
    svc2.close()


def test_delta_capacity_survives_recovery(tmp_path):
    """The pre-sized delta capacity is recorded in the manifest, so WAL
    replay after restart doesn't re-pay the growth re-materializations."""
    ds = _cached_dataset(8)
    idx = HybridIndex.build(ds.x_sparse[:N0], ds.x_dense[:N0],
                            _params("ref", 4), mutable=True,
                            delta_capacity=256)
    root = str(tmp_path / "store")
    persist.bootstrap(root, idx).close()
    loaded = HybridIndex.load(root)
    assert loaded.mutable_state.delta.capacity == 256


def test_hybrid_index_save_load(tmp_path):
    """The one-shot save()/load() pair round-trips without a service."""
    ds = _cached_dataset(8)
    idx = _build_mutable(ds, _params("ref", 4))
    root = str(tmp_path / "store")
    idx.save(root)
    loaded = HybridIndex.load(root)
    ids0, s0 = _search(idx, ds)
    ids1, s1 = _search(loaded, ds)
    np.testing.assert_array_equal(ids1, ids0)
    np.testing.assert_array_equal(s1, s0)
    # backend override serves the same snapshot through another engine
    alt = HybridIndex.load(root, backend="onehot-mxu")
    ids2, _ = _search(alt, ds)
    np.testing.assert_array_equal(ids2, ids0)


# -- incremental delta device appends (ISSUE 5 satellite) ---------------------

def test_incremental_append_matches_rematerialization():
    """The dynamic_update_slice append path produces device arrays (and
    search results) identical to full re-materialization."""
    ds = _cached_dataset(8)
    params = _params("ref", 4)
    fast = _build_mutable(ds, params)
    slow = _build_mutable(ds, params)
    slow.mutable_state.delta.incremental = False
    # force an early snapshot so the incremental path has a struct to update
    fast.search(ds.q_sparse, ds.q_dense, h=4)
    for lo in (N0, N0 + 5):
        rows = slice(lo, lo + 5)
        fast.insert(ds.x_sparse[rows], ds.x_dense[rows])
        slow.insert(ds.x_sparse[rows], ds.x_dense[rows])
        fast.delete([lo])
        slow.delete([lo])
        a = fast.mutable_state.delta.snapshot().arrays
        b = slow.mutable_state.delta.snapshot().arrays
        np.testing.assert_array_equal(np.asarray(a.codes),
                                      np.asarray(b.codes))
        np.testing.assert_array_equal(np.asarray(a.inv_index.rows),
                                      np.asarray(b.inv_index.rows))
        np.testing.assert_array_equal(np.asarray(a.inv_index.vals),
                                      np.asarray(b.inv_index.vals))
        np.testing.assert_array_equal(np.asarray(a.dense_residual.q),
                                      np.asarray(b.dense_residual.q))
        np.testing.assert_array_equal(np.asarray(a.sparse_residual.cols),
                                      np.asarray(b.sparse_residual.cols))
        np.testing.assert_array_equal(np.asarray(a.sparse_residual.vals),
                                      np.asarray(b.sparse_residual.vals))
        rf = fast.search(ds.q_sparse, ds.q_dense, h=8)
        rs = slow.search(ds.q_sparse, ds.q_dense, h=8)
        np.testing.assert_array_equal(rf.ids, rs.ids)
        np.testing.assert_array_equal(rf.scores, rs.scores)
    # the second round really did take the incremental path
    assert fast.mutable_state.delta._arrays_struct is not None


def test_incremental_append_survives_capacity_growth():
    """Growth (capacity doubling / rectangle widening) invalidates the
    device copy and falls back to re-materialization — still correct."""
    ds = _cached_dataset(8)
    idx = _build_mutable(ds, _params("ref", 4))
    st_ = idx.mutable_state
    cap0 = st_.delta.capacity
    idx.insert(ds.x_sparse[N0:N0 + 2], ds.x_dense[N0:N0 + 2])
    idx.search(ds.q_sparse, ds.q_dense, h=4)          # materializes struct
    idx.insert(ds.x_sparse[N0 + 2:N0 + 3], ds.x_dense[N0 + 2:N0 + 3])
    assert st_.delta._arrays_struct is not None       # incremental applied
    m = cap0 + 3                                      # force doubling
    rows = sp.vstack([ds.q_sparse[0] * 1e3] * m).tocsr()
    ids = idx.insert(rows, np.tile(ds.q_dense[0], (m, 1)))
    assert st_.delta.capacity > cap0
    r = idx.search(ds.q_sparse, ds.q_dense, h=m + 2)
    assert set(ids) <= set(r.ids[0])
