"""Serving-layer benchmark (DESIGN.md §5): QueryService vs the single-query
ScoringEngine loop, cache warm-up, refresh pause, and the packed-vs-unpacked
QPS sweep over the batch buckets.

Emits CSV rows like the other benchmark modules AND writes
``BENCH_serve.json`` with the documented schema (README "Serving"):

    workload                 points/queries/dims of the synthetic index
    single_query_loop_qps    one engine.search per query, Q=1 (the baseline
                             the batched service must beat >= 2x at Q=32)
    service_qps              {bucket: QPS} for the cold-cache service fed the
                             stream in chunks of that bucket
    batched_speedup_q32      service_qps["32"] / single_query_loop_qps
    cache                    {cold_qps, warm_qps, hit_rate} for an identical
                             repeated query stream through the LRU cache
    refresh                  {swap_s, first_search_after_s}: the refresh()
                             call itself (must not block on in-flight work)
                             and the first search against the new generation
    packed                   {bucket: {unpacked_qps, packed_qps, ratio}} —
                             Backend.PALLAS vs Backend.PALLAS_PACKED at
                             Q in {1, 8, 32} (interpret-mode numbers are a
                             structural proxy off-TPU; the HBM bytes halving
                             in BENCH_engine.json is the hardware claim)
    obs                      observability overhead at Q=32 (DESIGN.md §9.4):
                             {baseline_qps, disabled_qps, enabled_qps,
                             disabled_ratio, enabled_ratio, breakdown,
                             span_sourced} — baseline is Observability.off(),
                             disabled the default bundle, enabled adds
                             tracing; CI floors the ratios (>= 0.97 / 0.90)
    smoke                    true when run with --smoke (CI scale)

``--stream`` instead runs the streaming-mutation workload (DESIGN.md §6)
and writes ``BENCH_stream.json``:

    workload                 points/queries/dims of the synthetic index
    delta_free_qps           service QPS before any mutation (chunk 8)
    delta_qps                same stream with delta_rows live delta-shard
                             rows fanned in (the headline: must stay within
                             2x of delta_free_qps — interpret-mode numbers
                             are a structural proxy off-TPU)
    delta_ratio              delta_qps / delta_free_qps
    delta_rows               live rows in the delta when delta_qps ran
    insert_rate_rows_per_s   encode-on-insert throughput (batches of 16,
                             fused incremental device appends — the default)
    insert                   {incremental_rows_per_s, rebuild_rows_per_s,
                             speedup, incremental_bytes_per_row,
                             rebuild_bytes_per_row, upload_reduction}:
                             fused dynamic_update_slice appends vs
                             per-insert full re-materialization, the two
                             modes ALTERNATED batch-by-batch over the same
                             fill window so both see the same delta sizes;
                             off-TPU the rates are a structural proxy and
                             the bytes columns (structural host->device
                             upload per inserted row) carry the hardware
                             claim
    sustained                {qps, insert_rate, rounds}: interleaved
                             insert-batch + query-stream rounds on one wall
                             clock — the serving-while-mutating claim
    compaction               {seconds, rows_folded}: the full REBUILD fold
                             (retrain=True: k-means + column space redone),
                             measured on a discarded clone
    merge_compaction         {seconds, rows_folded, speedup_vs_rebuild}:
                             the frozen-artifact MERGE fold
                             (retrain=False, DESIGN.md §6.2) driving the
                             real refresh() swap
    post_compact_qps         stream QPS on the merged generation
    smoke                    true when run with --smoke (CI scale)

Run:  PYTHONPATH=src python -m benchmarks.serve_bench [--smoke] [--stream]
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import time

import jax.numpy as jnp
import numpy as np

from repro.core.engine import Backend, ScoringEngine
from repro.core.hybrid import HybridIndex, HybridIndexParams
from repro.core.pq import pack_codes
from repro.core.sparse_index import sparse_queries_to_padded
from repro.data import make_hybrid_dataset
from repro.obs import Observability
from repro.serve import QueryService

from .common import emit, timeit

OUT_JSON = "BENCH_serve.json"
OUT_STREAM_JSON = "BENCH_stream.json"
BUCKETS = (1, 8, 32)
H, ALPHA, BETA = 20, 20, 5


def _build(smoke: bool):
    """kernels_bench-scale workload (BENCH_engine.json's), or a CI-sized one."""
    n, d_s, nnz = (4000, 6000, 24) if smoke else (20000, 20000, 48)
    ds = make_hybrid_dataset(num_points=n, num_queries=32, d_sparse=d_s,
                             d_dense=64, nnz_per_row=nnz, seed=3)
    idx = HybridIndex.build(ds.x_sparse, ds.x_dense,
                            HybridIndexParams(keep_top=96, head_dims=64,
                                              kmeans_iters=6))
    q_dims, q_vals = sparse_queries_to_padded(ds.q_sparse, idx.cols,
                                              nq_max=idx.params.nq_max)
    return ds, idx, q_dims, q_vals, np.asarray(ds.q_dense, np.float32)


def _stream_qps(svc: QueryService, q_dims, q_vals, q_dense,
                chunk: int, repeat: int) -> float:
    """Feed the 32-query stream through the service in `chunk`-sized requests."""
    nq = q_dims.shape[0]

    def run():
        for lo in range(0, nq, chunk):
            svc.search(q_dims[lo:lo + chunk], q_vals[lo:lo + chunk],
                       q_dense[lo:lo + chunk])

    run()  # warm the jit cache for this bucket
    secs, _ = timeit(run, repeat=repeat)
    return nq / secs


def _engine_bucket_qps(engine: ScoringEngine, q_dims, q_vals, q_dense,
                       bucket: int, repeat: int) -> float:
    """Raw engine QPS at one padded batch size (the packed-vs-unpacked probe
    bypasses the service so cache/bucketing logic can't mask the kernel)."""
    nq = q_dims.shape[0]
    chunks = []
    for lo in range(0, nq, bucket):
        pad = bucket - min(bucket, nq - lo)
        chunks.append(tuple(
            jnp.asarray(np.pad(a[lo:lo + bucket], [(0, pad)] + [(0, 0)] *
                               (a.ndim - 1), constant_values=c))
            for a, c in ((q_dims, engine.arrays.d_active), (q_vals, 0),
                         (q_dense, 0))))

    def run():
        outs = [engine.search(*c, h=H, alpha=ALPHA, beta=BETA)
                for c in chunks]
        return [np.asarray(o[1]) for o in outs]

    run()
    secs, _ = timeit(run, repeat=repeat)
    return nq / secs


def _obs_overhead(idx, q_dims, q_vals, q_dense, repeat):
    """Observability overhead probe at Q=32 (DESIGN.md §9.4): three
    identically configured services — ``baseline`` (Observability.off()),
    ``disabled`` (the default: metrics on, trace off), ``enabled``
    (metrics + tracing) — measured in INTERLEAVED best-of rounds so
    machine drift hits every mode equally.  Returns the qps per mode, the
    ratios vs baseline (CI floors: disabled >= 0.97, enabled >= 0.90),
    and a span-sourced dispatch / device-wait / merge breakdown from the
    enabled mode."""
    modes = {"baseline": Observability.off(),
             "disabled": None,       # service default bundle
             "enabled": Observability(metrics=True, trace=True)}
    svcs = {k: QueryService(idx.engine, h=H, alpha=ALPHA, beta=BETA,
                            buckets=BUCKETS, cache_size=0,
                            **({} if v is None else {"obs": v}))
            for k, v in modes.items()}
    nq = q_dims.shape[0]
    for s in svcs.values():                  # shared-engine jit warmup
        s.search(q_dims, q_vals, q_dense)
    svcs["enabled"].obs.tracer.take()        # breakdown: measured runs only
    best = dict.fromkeys(svcs)
    # each round is ~1ms/mode; best-of-many so scheduler jitter cannot
    # fake an overhead the CI ratio floors would trip on
    for _ in range(max(25, repeat * 5)):
        for k, s in svcs.items():
            t0 = time.perf_counter()
            s.search(q_dims, q_vals, q_dense)
            dt = time.perf_counter() - t0
            if best[k] is None or dt < best[k]:
                best[k] = dt
    qps = {k: nq / v for k, v in best.items()}
    # span-sourced serve breakdown: sum the durations of each serve.batch
    # span's dispatch / device-wait / merge children over the enabled
    # mode's measured traces
    traces = svcs["enabled"].obs.tracer.take()
    stage = dict.fromkeys(("serve.dispatch", "serve.device_wait",
                           "serve.merge"), 0.0)
    nbatch = 0
    for t in traces:
        for c in t.get("children", ()):
            if c["name"] != "serve.batch":
                continue
            nbatch += 1
            for g in c.get("children", ()):
                if g["name"] in stage:
                    stage[g["name"]] += g["duration_s"]
    served = nq * len(traces) or 1
    for s in svcs.values():
        s.close()
    return {"baseline_qps": qps["baseline"],
            "disabled_qps": qps["disabled"],
            "enabled_qps": qps["enabled"],
            "disabled_ratio": qps["disabled"] / qps["baseline"],
            "enabled_ratio": qps["enabled"] / qps["baseline"],
            "breakdown": {
                "dispatch_us_per_q": stage["serve.dispatch"] / served * 1e6,
                "device_wait_us_per_q":
                    stage["serve.device_wait"] / served * 1e6,
                "merge_us_per_q": stage["serve.merge"] / served * 1e6,
                "traces": len(traces), "batches": nbatch},
            "span_sourced": True}


def main(smoke: bool = False):
    """Run the serving benches; prints CSV rows and writes BENCH_serve.json."""
    repeat = 2 if smoke else 5
    ds, idx, q_dims, q_vals, q_dense = _build(smoke)
    nq = q_dims.shape[0]

    # -- baseline: one engine.search per query ---------------------------
    singles = [(jnp.asarray(q_dims[i:i + 1]), jnp.asarray(q_vals[i:i + 1]),
                jnp.asarray(q_dense[i:i + 1])) for i in range(nq)]

    def single_loop():
        return [np.asarray(idx.engine.search(*s, h=H, alpha=ALPHA,
                                             beta=BETA)[1]) for s in singles]

    single_loop()
    s_single, _ = timeit(single_loop, repeat=repeat)
    qps_single = nq / s_single
    emit("serve_single_query_loop", s_single / nq * 1e6,
         f"qps={qps_single:.1f}")

    # -- batched service across buckets (cold cache) ---------------------
    svc = QueryService(idx.engine, h=H, alpha=ALPHA, beta=BETA,
                       buckets=BUCKETS, cache_size=0)
    service_qps = {}
    for bucket in BUCKETS:
        qps = _stream_qps(svc, q_dims, q_vals, q_dense, bucket, repeat)
        service_qps[str(bucket)] = qps
        emit(f"serve_service_q{bucket}", 1e6 / qps,
             f"qps={qps:.1f};speedup_vs_single={qps / qps_single:.2f}x")

    # -- warm-cache repeat of the same stream ----------------------------
    cached = QueryService(idx.engine, h=H, alpha=ALPHA, beta=BETA,
                          buckets=BUCKETS, cache_size=4 * nq)
    t0 = time.perf_counter()
    cached.search(q_dims, q_vals, q_dense)
    cold_s = time.perf_counter() - t0
    warm_s, _ = timeit(lambda: cached.search(q_dims, q_vals, q_dense),
                       repeat=repeat)
    info = cached.cache_info()
    emit("serve_cache_warm", warm_s / nq * 1e6,
         f"qps={nq / warm_s:.1f};hit_rate={info.hit_rate:.3f}")

    # -- observability overhead + span-sourced breakdown (DESIGN.md §9.4) -
    # (before the refresh section: refresh() DONATES idx.engine's retired
    # buffers, so these probes must run while that engine is still live)
    obs = _obs_overhead(idx, q_dims, q_vals, q_dense, repeat)
    emit("serve_obs_overhead", 1e6 / obs["enabled_qps"],
         f"disabled_ratio={obs['disabled_ratio']:.3f};"
         f"enabled_ratio={obs['enabled_ratio']:.3f}")

    # -- refresh pause ----------------------------------------------------
    idx2 = HybridIndex.build(ds.x_sparse, ds.x_dense,
                             dataclasses.replace(idx.params, seed=11))
    t0 = time.perf_counter()
    svc.refresh(idx2.engine)
    swap_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    svc.search(q_dims, q_vals, q_dense)
    first_after_s = time.perf_counter() - t0
    emit("serve_refresh_swap", swap_s * 1e6,
         f"first_search_after_us={first_after_s * 1e6:.0f}")

    # -- packed vs unpacked across buckets (satellite of PR 3) -----------
    arr = idx2.engine.arrays
    eng_unpacked = ScoringEngine(arrays=arr, backend=Backend.PALLAS)
    eng_packed = ScoringEngine(
        arrays=dataclasses.replace(
            arr, codes=jnp.asarray(pack_codes(np.asarray(arr.codes))),
            codes_packed=True),
        backend=Backend.PALLAS_PACKED)
    packed = {}
    for bucket in BUCKETS:
        up = _engine_bucket_qps(eng_unpacked, q_dims, q_vals, q_dense,
                                bucket, repeat)
        pk = _engine_bucket_qps(eng_packed, q_dims, q_vals, q_dense,
                                bucket, repeat)
        packed[str(bucket)] = {"unpacked_qps": up, "packed_qps": pk,
                               "ratio": pk / up}
        emit(f"serve_packed_q{bucket}", 1e6 / pk,
             f"packed_qps={pk:.1f};unpacked_qps={up:.1f};"
             f"ratio={pk / up:.2f}x")

    out = {
        "workload": {"num_points": idx.num_points, "num_queries": nq,
                     "d_dense": 64, "h": H, "alpha": ALPHA, "beta": BETA},
        "single_query_loop_qps": qps_single,
        "service_qps": service_qps,
        "batched_speedup_q32": service_qps["32"] / qps_single,
        "cache": {"cold_qps": nq / cold_s, "warm_qps": nq / warm_s,
                  "hit_rate": info.hit_rate},
        "refresh": {"swap_s": swap_s, "first_search_after_s": first_after_s},
        "packed": packed,
        "obs": obs,
        "smoke": smoke,
    }
    with open(OUT_JSON, "w") as f:
        json.dump(out, f, indent=2)


def _sparse_stream_qps(svc: QueryService, q_sparse, q_dense,
                       chunk: int, repeat: int) -> float:
    """Stream RAW sparse queries through search_sparse in chunk-sized
    requests (per-generation encoding included: the compact column space
    changes at compaction, so this is what a streaming client pays)."""
    nq = q_sparse.shape[0]

    def run():
        for lo in range(0, nq, chunk):
            svc.search_sparse(q_sparse[lo:lo + chunk],
                              q_dense[lo:lo + chunk])

    run()  # warm the jit cache for this bucket / delta capacity
    secs, _ = timeit(run, repeat=repeat)
    return nq / secs


def stream_main(smoke: bool = False):
    """Streaming-mutation workload (DESIGN.md §6): QPS with and without a
    live delta shard, sustained insert+query interleave, compaction cost.
    Prints CSV rows and writes BENCH_stream.json."""
    repeat = 2 if smoke else 5
    chunk = 8
    n, d_s, nnz = (4000, 6000, 24) if smoke else (20000, 20000, 48)
    n_delta = 128 if smoke else 512
    ds = make_hybrid_dataset(num_points=n + n_delta, num_queries=32,
                             d_sparse=d_s, d_dense=64, nnz_per_row=nnz,
                             seed=3)
    idx = HybridIndex.build(ds.x_sparse[:n], ds.x_dense[:n],
                            HybridIndexParams(keep_top=96, head_dims=64,
                                              kmeans_iters=6),
                            mutable=True,
                            # pre-size the delta so the fill measures the
                            # steady-state append paths, not growth steps
                            delta_capacity=n_delta)
    svc = QueryService(index=idx, h=H, alpha=ALPHA, beta=BETA,
                       buckets=BUCKETS, cache_size=0, auto_compact=False)
    qs, qd = ds.q_sparse, np.asarray(ds.q_dense, np.float32)

    # -- baseline: no mutations yet ---------------------------------------
    qps_free = _sparse_stream_qps(svc, qs, qd, chunk, repeat)
    emit("stream_delta_free", 1e6 / qps_free, f"qps={qps_free:.1f}")

    # -- fill the delta, measure insert rate + structural upload volume:
    # full re-materialization vs fused dynamic_update_slice appends
    # (DESIGN.md §6.1; wall-clock off-TPU is a structural proxy — the
    # hardware claim is the bytes column) ---------------------------------
    delta = idx.mutable_state.delta

    def _insert_batch(s, incremental):
        delta.incremental = incremental
        b0 = delta.upload_bytes
        t0 = time.perf_counter()
        svc.insert(ds.x_sparse[n + s: n + s + 16],
                   ds.x_dense[n + s: n + s + 16])
        return time.perf_counter() - t0, delta.upload_bytes - b0

    # warm BOTH paths over the first half, then ALTERNATE mode batch-by-
    # batch over the second half so each path is timed at the same delta
    # sizes — timing them in disjoint windows flatters whichever runs
    # while the delta is smaller (the old rebuild-first ordering reported
    # incremental appends SLOWER than re-materialization)
    half = n_delta // 2
    for i, s in enumerate(range(0, half, 16)):
        _insert_batch(s, incremental=i % 2 == 0)
    elapsed = {True: 0.0, False: 0.0}
    volume = {True: 0.0, False: 0.0}
    rows = {True: 0, False: 0}
    for i, s in enumerate(range(half, n_delta, 16)):
        mode = i % 2 == 0
        dt, db = _insert_batch(s, mode)
        elapsed[mode] += dt
        volume[mode] += db
        rows[mode] += 16
    insert_rate = rows[True] / elapsed[True]
    rebuild_rate = rows[False] / elapsed[False]
    incr_bytes = volume[True] / rows[True]
    rebuild_bytes = volume[False] / rows[False]
    emit("stream_insert_incremental", 1e6 / insert_rate,
         f"rows_per_s={insert_rate:.1f};rebuild_rows_per_s="
         f"{rebuild_rate:.1f};speedup={insert_rate / rebuild_rate:.2f}x;"
         f"bytes_per_row={incr_bytes:.0f}_vs_{rebuild_bytes:.0f}")
    delta_rows = svc.stats()["delta_rows"]
    assert delta_rows == n_delta

    # -- QPS with the delta fanned in (the headline ratio) ----------------
    qps_delta = _sparse_stream_qps(svc, qs, qd, chunk, repeat)
    ratio = qps_delta / qps_free
    emit("stream_delta_live", 1e6 / qps_delta,
         f"qps={qps_delta:.1f};ratio_vs_free={ratio:.2f}x;"
         f"delta_rows={delta_rows}")

    # -- sustained interleave: insert batches racing the query stream -----
    rounds = 3 if smoke else 6
    t0 = time.perf_counter()
    done = 0
    for r in range(rounds):
        svc.insert(ds.x_sparse[n + (r % 8) * 8: n + (r % 8) * 8 + 8],
                   ds.x_dense[n + (r % 8) * 8: n + (r % 8) * 8 + 8],
                   ids=np.arange(n + n_delta + r * 8,
                                 n + n_delta + r * 8 + 8))
        for lo in range(0, 32, chunk):
            svc.search_sparse(qs[lo:lo + chunk], qd[lo:lo + chunk])
        done += 8
    wall = time.perf_counter() - t0
    sustained_qps = rounds * 32 / wall
    sustained_ins = done / wall
    emit("stream_sustained", 1e6 / sustained_qps,
         f"qps={sustained_qps:.1f};inserts_per_s={sustained_ins:.1f}")

    # -- compaction: rebuild vs merge fold-down ---------------------------
    folded = svc.stats()["delta_rows"]
    # rebuild cost on a DISCARDED result, so the serving index keeps its
    # delta and the merge below folds the identical state
    t0 = time.perf_counter()
    svc._index.mutable_state.compact(retrain=True)
    rebuild_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    svc.compact(retrain=False)              # the real refresh() swap
    merge_s = time.perf_counter() - t0
    qps_post = _sparse_stream_qps(svc, qs, qd, chunk, repeat)
    emit("stream_compaction", rebuild_s * 1e6,
         f"rows_folded={folded};post_compact_qps={qps_post:.1f}")
    emit("stream_merge_compaction", merge_s * 1e6,
         f"rows_folded={folded};"
         f"speedup_vs_rebuild={rebuild_s / merge_s:.2f}x")

    out = {
        "workload": {"num_points": n, "num_queries": 32, "d_dense": 64,
                     "h": H, "alpha": ALPHA, "beta": BETA, "chunk": chunk},
        "delta_free_qps": qps_free,
        "delta_qps": qps_delta,
        "delta_ratio": ratio,
        "delta_rows": int(delta_rows),
        "insert_rate_rows_per_s": insert_rate,
        # fused incremental appends vs per-insert re-materialization,
        # alternated batch-by-batch over one fill window (same delta sizes
        # for both modes); the bytes columns carry the hardware claim
        # (host->device structural upload per inserted row) independent of
        # interpret-mode wall clock
        "insert": {"incremental_rows_per_s": insert_rate,
                   "rebuild_rows_per_s": rebuild_rate,
                   "speedup": insert_rate / rebuild_rate,
                   "incremental_bytes_per_row": incr_bytes,
                   "rebuild_bytes_per_row": rebuild_bytes,
                   "upload_reduction": rebuild_bytes / max(incr_bytes, 1.0)},
        "sustained": {"qps": sustained_qps, "insert_rate": sustained_ins,
                      "rounds": rounds},
        "compaction": {"seconds": rebuild_s, "rows_folded": int(folded)},
        "merge_compaction": {"seconds": merge_s, "rows_folded": int(folded),
                             "speedup_vs_rebuild": rebuild_s / merge_s},
        "post_compact_qps": qps_post,
        "smoke": smoke,
    }
    with open(OUT_STREAM_JSON, "w") as f:
        json.dump(out, f, indent=2)
    svc.close()


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI scale: small index, fewer repeats")
    ap.add_argument("--stream", action="store_true",
                    help="run the streaming-mutation workload instead "
                         "(writes BENCH_stream.json)")
    args = ap.parse_args()
    if args.stream:
        print("name,us_per_call,derived")
        stream_main(smoke=args.smoke)
    else:
        main(smoke=args.smoke)
