#!/usr/bin/env python3
"""Chip smoke test: the three-pass hybrid search end to end on a TPU.

    python3 chip_smoke.py                 # one chip (the default)
    python3 chip_smoke.py --four-chips    # the sharded fan-out on 4 chips

One chip: QuerySim-shaped data (paper Table 3: power-law sparse activity,
134 nnz/row nominal, 200 dense dims, K=100 PQ subspaces) at 1,000,000 rows,
generated from ``--seed``; ``HybridIndex.build`` with the ``pallas-packed``
backend; ``QueryService`` serving warmed batches of 1, 8 and 32 queries; a
few inserts and a delete; then the checks: the compiled search carries the
Pallas kernels (``tpu_custom_call``), top-10 ids agree with the ``ref``
backend on the same ``IndexArrays``, recall@10 against exact float32
search matches ``ref``'s, the inserted planted row is found and the deleted
row never returns.

Four chips: the paper's fan-out (every shard refines its own rows, the
coordinator merges, §7.2) through ``make_sharded_search3_fn`` over a
("data", 4) mesh, with each chip loading only its rows, compared with the
same per-shard search run shard by shard on one chip and merged on the host.

Every time printed is a smoke timing on the host's wall clock, not a device
metric.  Any failed check exits non-zero; the last line of a passing run is
``{"ok": true, "device": {...}}``.  JAX is held to the TPU: a host without
one fails at start-up instead of running on the CPU.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
H, ALPHA, BETA = 10, 20, 5           # top-10, pass-1 overfetch 200, keep 50
BUCKETS = (1, 8, 32)
NUM_QUERIES = 64
PAPER_ROWS = 5_000_000               # paper Table 3 corpus
ONE_CHIP_ROWS = 1_000_000            # one chip's shard of it
FOUR_CHIP_ROWS = 200_000             # the fan-out check, not a sized cell


def log(msg: str) -> None:
    print(msg, flush=True)


@contextlib.contextmanager
def phase(name: str, walls: dict):
    t0 = time.perf_counter()
    yield
    walls[name] = time.perf_counter() - t0
    log(f"phase {name}: {walls[name]:.3f} s (smoke timing, host wall clock)")


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"CHECK FAILED: {what}")
    log(f"check ok: {what}")


def make_data(rows: int, seed: int):
    from repro.data import make_hybrid_dataset
    return make_hybrid_dataset(num_points=rows, num_queries=NUM_QUERIES,
                               d_sparse=200_000, d_dense=200,
                               nnz_per_row=134, alpha=2.0, dense_weight=2.0,
                               seed=seed)


def index_params(**kw):
    from repro.core.hybrid import HybridIndexParams
    return HybridIndexParams(keep_top=192, head_dims=128, kmeans_iters=6,
                             backend="pallas-packed", **kw)


def exact_topk(ds, h: int):
    """Exact float32 inner products on the host, top-h ids per query."""
    import numpy as np
    scores = (ds.q_sparse.astype(np.float32) @ ds.x_sparse.T.astype(
        np.float32)).toarray()
    scores += ds.q_dense @ ds.x_dense.T
    return np.argpartition(-scores, h, axis=1)[:, :h]


def overlap(a, b) -> float:
    """Mean per-query share of a's top-h ids that are also in b's."""
    import numpy as np
    return float(np.mean([len(set(x) & set(y)) / len(x)
                          for x, y in zip(a.tolist(), b.tolist())]))


def run_one_chip(rows: int, seed: int) -> None:
    import jax
    import numpy as np

    from repro.core.engine import Backend, ScoringEngine
    from repro.core.hybrid import HybridIndex
    from repro.core.sparse_index import sparse_queries_to_padded
    from repro.serve import QueryService

    walls: dict[str, float] = {}
    log(f"rows: {rows:,} of the paper's {PAPER_ROWS:,}-row Table 3 corpus "
        f"(one chip's shard; depth cut, widths kept)")
    with phase("data", walls):
        ds = make_data(rows, seed)
    with phase("build", walls):
        params = index_params()
        idx = HybridIndex.build(ds.x_sparse, ds.x_dense, params, mutable=True)
        jax.block_until_ready(idx.engine.arrays)
    arrays = idx.engine.arrays
    index_bytes = sum(x.nbytes for x in jax.tree.leaves(arrays))
    log(f"index: {index_bytes:,} device bytes ({index_bytes / rows:.0f} B/row)"
        f", d_active={arrays.d_active}, codes {arrays.codes.shape} "
        f"packed={arrays.codes_packed}")

    q_dims, q_vals = sparse_queries_to_padded(ds.q_sparse, idx.cols,
                                              nq_max=params.nq_max)
    q_dense = np.asarray(ds.q_dense, np.float32)
    svc = QueryService(index=idx, h=H, alpha=ALPHA, beta=BETA,
                       buckets=BUCKETS, cache_size=0, auto_compact=False)

    with phase("serve", walls):
        for b in BUCKETS:
            batches = [np.arange(i * b, (i + 1) * b) % NUM_QUERIES
                       for i in range(4)]
            t0 = time.perf_counter()
            svc.search(q_dims[batches[0]], q_vals[batches[0]],
                       q_dense[batches[0]])
            first = time.perf_counter() - t0
            times = []
            for rws in batches[1:]:
                t0 = time.perf_counter()
                svc.search(q_dims[rws], q_vals[rws], q_dense[rws])
                times.append(time.perf_counter() - t0)
            log(f"bucket {b}: first call (compile + run) {first:.3f} s; "
                f"warm batches {', '.join(f'{t * 1e3:.3f}' for t in times)}"
                f" ms (smoke timing)")
        s_pal, ids_pal = svc.search(q_dims, q_vals, q_dense)
    check(ids_pal.shape == (NUM_QUERIES, H) and np.isfinite(s_pal).all(),
          f"pallas-packed returns finite ({NUM_QUERIES}, {H}) results")

    with phase("compiled_text", walls):
        qd, qv, qe = (jax.numpy.asarray(x[:32]) for x in
                      (q_dims, q_vals, q_dense))
        hlo = idx.engine.search_hlo(qd, qv, qe, h=H, alpha=ALPHA, beta=BETA)
    check("tpu_custom_call" in hlo,
          "the compiled search program contains tpu_custom_call")

    with phase("reference", walls):
        # ref gathers a (N*K, Q) LUT block, so it scores one query per call
        ref = ScoringEngine(arrays=arrays, backend=Backend.REF)
        ids_ref = np.stack([
            idx.pi[np.asarray(ref.search(
                q_dims[i:i + 1], q_vals[i:i + 1], q_dense[i:i + 1],
                h=H, alpha=ALPHA, beta=BETA)[1])[0]]
            for i in range(NUM_QUERIES)])
        exact = exact_topk(ds, H)
    agree = overlap(ids_pal, ids_ref)
    rec_pal, rec_ref = overlap(exact, ids_pal), overlap(exact, ids_ref)
    log(f"top-{H} id agreement pallas-packed vs ref: {agree:.4f}")
    log(f"recall@{H} vs exact float32: pallas-packed {rec_pal:.4f}, "
        f"ref {rec_ref:.4f}")
    check(agree >= 0.99, "pallas-packed agrees with ref on >= 99% of ids")
    check(abs(rec_pal - rec_ref) <= 0.01,
          "pallas-packed recall@10 within 0.01 of ref's")

    with phase("mutate", walls):
        # a planted row: query 0 itself, scaled, so it must rank first
        planted = svc.insert(ds.q_sparse[0] * 3.0, ds.q_dense[:1] * 3.0)
        svc.insert(ds.x_sparse[1:3], ds.x_dense[1:3] * 1.01)
        victim = int(ids_pal[1, 0])
        killed = svc.delete([victim])
        s_mut, ids_mut = svc.search(q_dims, q_vals, q_dense)
    check(killed == 1, f"delete of row {victim} tombstoned one live row")
    check(int(planted[0]) in ids_mut[0].tolist(),
          f"inserted planted row {int(planted[0])} is found for query 0")
    check(victim not in ids_mut, f"deleted row {victim} never returns")
    check(np.isfinite(s_mut).all(), "post-mutation scores are finite")

    stats = jax.devices()[0].memory_stats() or {}
    log(f"device peak_bytes_in_use: {stats.get('peak_bytes_in_use')}")
    log("phase walls (s, smoke timing): " + json.dumps(
        {k: round(v, 3) for k, v in walls.items()}))
    svc.close()


def run_four_chips(rows: int, seed: int) -> None:
    import jax
    import numpy as np

    from repro.core.distributed import (merge_topk_host,
                                        sharded_three_pass_topk,
                                        split_index_arrays)
    from repro.core.engine import scatter_queries_compact
    from repro.core.hybrid import HybridIndex
    from repro.core.pq import adc_lut
    from repro.core.sparse_index import sparse_queries_to_padded
    from repro.launch.mesh import make_test_mesh

    walls: dict[str, float] = {}
    shards = 4
    check(len(jax.devices()) >= shards, f"{shards} TPU chips are visible")
    log(f"rows: {rows:,} ({rows // shards:,} per chip); a fan-out check, "
        f"not a sized cell")
    # the index is built on the host's CPU backend, so the only copies on
    # the chips are the per-chip row shards placed below
    with phase("data_build_host", walls), \
            jax.default_device(jax.devices("cpu")[0]):
        ds = make_data(rows, seed)
        params = index_params(use_head_block=False)
        idx = HybridIndex.build(ds.x_sparse, ds.x_dense, params)
        arrays = idx.engine.arrays
        parts, offsets = split_index_arrays(arrays, shards)
        q_dims, q_vals = sparse_queries_to_padded(ds.q_sparse, idx.cols,
                                                  nq_max=params.nq_max)
        q_dense = np.asarray(ds.q_dense, np.float32)
        lut = np.asarray(adc_lut(q_dense, arrays.codebooks))
        q_cols = np.asarray(scatter_queries_compact(q_dims, q_vals,
                                                    arrays.d_active))
    host = np.asarray
    full = dict(codes=host(arrays.codes), res_q=host(arrays.dense_residual.q),
                sres_cols=host(arrays.sparse_residual.cols),
                sres_vals=host(arrays.sparse_residual.vals))
    scale = host(arrays.dense_residual.scale)
    zero = host(arrays.dense_residual.zero)
    inv = [(host(p.inv_index.rows), host(p.inv_index.vals)) for p in parts]
    index_bytes = (sum(v.nbytes for v in full.values())
                   + sum(r.nbytes + v.nbytes for r, v in inv))
    queries = (q_dims, q_vals, q_dense, q_cols)
    kw = dict(h=H, alpha=ALPHA, beta=BETA, adc="pallas-packed")

    def search(mesh, rows_sl, inv_part):
        return sharded_three_pass_topk(
            mesh, full["codes"][rows_sl], lut, *inv_part,
            full["res_q"][rows_sl], scale, zero, full["sres_cols"][rows_sl],
            full["sres_vals"][rows_sl], *queries, **kw)

    with phase("sharded_search", walls):
        mesh = make_test_mesh((shards,), ("data",))
        s4, i4 = search(mesh, slice(None), tuple(
            np.concatenate(x) for x in zip(*inv)))
        s4, i4 = np.asarray(s4), np.asarray(i4)
    # the peak so far covers placement and the whole sharded search
    per_chip = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                for d in jax.devices()]
    log(f"index bytes (all shards): {index_bytes:,}; peak bytes in use per "
        f"chip: {per_chip}")
    check(max(per_chip) < index_bytes, "no chip ever held the whole index")

    with phase("shard_by_shard_one_chip", walls):
        mesh1 = make_test_mesh((1,), ("data",))
        bounds = list(offsets) + [rows]
        merged = []
        for s in range(shards):
            lo, hi = int(bounds[s]), int(bounds[s + 1])
            ss, ii = search(mesh1, slice(lo, hi), inv[s])
            merged.append((np.asarray(ss), np.asarray(ii) + lo, True))
        s1, i1 = merge_topk_host(merged, H)
    check(np.array_equal(i4, i1),
          "4-chip ids equal the shard-by-shard one-chip merge exactly")
    check(np.allclose(s4, s1, rtol=1e-5, atol=1e-5),
          "4-chip scores match to f32 tolerance "
          f"(max |diff| {float(np.max(np.abs(s4 - s1))):.3g})")
    log("phase walls (s, smoke timing): " + json.dumps(
        {k: round(v, 3) for k, v in walls.items()}))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded fan-out check on 4 chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    # JAX must find the TPU or fail: no silent CPU run.  The four-chip
    # check also keeps the CPU backend, to build its index off the chips.
    os.environ["JAX_PLATFORMS"] = "tpu,cpu" if args.four_chips else "tpu"
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import jax

    from repro.compile_cache import enable_compile_cache

    log(f"compile cache: {enable_compile_cache()}")
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"no TPU: JAX's first device is {dev.platform!r}")
    log(f"device: {dev.device_kind} x{len(jax.devices())}; jax "
        f"{jax.__version__}")
    if args.four_chips:
        run_four_chips(FOUR_CHIP_ROWS, args.seed)
    else:
        run_one_chip(ONE_CHIP_ROWS, args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
