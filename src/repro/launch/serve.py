"""Serving launchers.

LM mode (default): prefill a batch of prompts, decode N tokens, report
per-step latency — with either the exact head or the paper's PQ hybrid head.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-7b-smoke \
        --tokens 32 --batch 4 --pq-head

Retrieval mode (DESIGN.md §5): build a synthetic hybrid index, stand up the
batched QueryService, drive a ragged query stream through it twice (cold +
warm cache) with a mid-stream index refresh, and report QPS + cache + jit
stats.

    PYTHONPATH=src python -m repro.launch.serve --retrieval \
        --points 20000 --queries 64 --shards 4

Durable retrieval (DESIGN.md §7): ``--persist-dir DIR`` builds a mutable
index, bootstraps a snapshot store + mutation WAL there, and serves with
every mutation logged; ``--restore DIR`` resumes that store after a
crash/restart (snapshot load + WAL replay) and serves the recovered index.

    PYTHONPATH=src python -m repro.launch.serve --retrieval \
        --persist-dir /tmp/hybrid-store          # first run
    PYTHONPATH=src python -m repro.launch.serve --retrieval \
        --restore /tmp/hybrid-store              # after a restart

Cluster mode (DESIGN.md §8): ``--role shard`` runs ONE shard-server
process (primary / scorer / replica — the building block real deployments
lay out across hosts; delegates to ``repro.serve.cluster.shard_server``),
while ``--role router`` demos the whole tier locally: spawn a primary +
scorers (+ replicas) as subprocesses, route a query stream through the
fan-out with mutations interleaved, and report QPS + per-hop latency +
replication stats.

    PYTHONPATH=src python -m repro.launch.serve --role router \
        --points 2000 --cluster-scorers 2 --replicas 1
    PYTHONPATH=src python -m repro.launch.serve --role shard \
        --shard-role primary --store /tmp/hybrid-store --port 7001
"""

from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.models import Model
from repro.serve import greedy_generate


def run_lm(args) -> None:
    """Decode-loop latency probe (exact vs PQ hybrid head)."""
    cfg = get_config(args.arch)
    model = Model(cfg)
    key = jax.random.PRNGKey(args.seed)
    params = model.init(key)
    prompt = jax.random.randint(key, (args.batch, args.prompt_len), 0,
                                cfg.vocab_size)
    t0 = time.perf_counter()
    out = greedy_generate(model, params, prompt, args.tokens, args.max_len,
                          use_pq_head=args.pq_head, penalty=args.penalty)
    dt = time.perf_counter() - t0
    print(f"generated {out.shape} in {dt:.2f}s "
          f"({dt / args.tokens * 1e3:.1f} ms/step, "
          f"head={'pq-hybrid' if args.pq_head else 'exact'})")
    print("sample:", jnp.asarray(out)[0, :16].tolist())


def _maybe_metrics_server(args, registry):
    """Stand up the ``--metrics-port`` text endpoint (DESIGN.md §9.1) over
    ``registry``; None when the flag wasn't given.  Port 0 binds an
    ephemeral port (printed)."""
    if getattr(args, "metrics_port", None) is None:
        return None
    from repro.obs import start_metrics_server
    ms = start_metrics_server(registry, args.metrics_port)
    print(f"metrics endpoint: http://127.0.0.1:{ms.port}/metrics")
    return ms


def run_durable_retrieval(args) -> None:
    """Durable serving loop (DESIGN.md §7): bootstrap or restore a snapshot
    store + WAL, mutate under load, and report recovery/persistence stats."""
    from repro.core.hybrid import HybridIndex, HybridIndexParams
    from repro.data import make_hybrid_dataset
    from repro.serve import QueryService

    ds = make_hybrid_dataset(num_points=args.points, num_queries=args.queries,
                             d_sparse=args.points, d_dense=64,
                             nnz_per_row=48, seed=args.seed)
    n0 = args.points - 64
    if args.restore:
        print(f"recovering from {args.restore} ...")
        t0 = time.perf_counter()
        svc = QueryService(restore_from=args.restore, h=args.h,
                           auto_compact=False)
        print(f"recovered in {time.perf_counter() - t0:.2f}s; "
              f"stats: {svc.stats()}")
    else:
        print(f"building durable index: {n0} points -> {args.persist_dir}")
        params = HybridIndexParams(keep_top=96, head_dims=64, kmeans_iters=6)
        idx = HybridIndex.build(ds.x_sparse[:n0], ds.x_dense[:n0], params,
                                mutable=True)
        svc = QueryService(index=idx, h=args.h,
                           persist_dir=args.persist_dir, auto_compact=False)
        new = svc.insert(ds.x_sparse[n0:], ds.x_dense[n0:])
        svc.delete(new[:8])
        print(f"logged {len(new)} inserts + 8 deletes to the WAL; "
              f"stats: {svc.stats()}")
    ms = _maybe_metrics_server(args, svc.obs.metrics)
    t0 = time.perf_counter()
    s, ids = svc.search_sparse(ds.q_sparse, ds.q_dense)
    dt = time.perf_counter() - t0
    print(f"served {ids.shape[0]} queries in {dt:.2f}s "
          f"(top ids {ids[0, :5].tolist()})")
    if ms is not None:
        ms.close()
    svc.close()


def run_retrieval(args) -> None:
    """QueryService under a ragged query stream: QPS, cache, refresh."""
    import numpy as np

    from repro.core.hybrid import HybridIndex, HybridIndexParams
    from repro.core.sparse_index import sparse_queries_to_padded
    from repro.data import make_hybrid_dataset
    from repro.serve import QueryService

    if args.restore or args.persist_dir:
        return run_durable_retrieval(args)

    print(f"building index: {args.points} points, {args.shards} shard(s)...")
    ds = make_hybrid_dataset(num_points=args.points, num_queries=args.queries,
                             d_sparse=args.points, d_dense=64,
                             nnz_per_row=48, seed=args.seed)
    params = HybridIndexParams(keep_top=96, head_dims=64, kmeans_iters=6)
    idx = HybridIndex.build(ds.x_sparse, ds.x_dense, params)
    q_dims, q_vals = sparse_queries_to_padded(ds.q_sparse, idx.cols,
                                              nq_max=params.nq_max)
    q_dense = np.asarray(ds.q_dense, np.float32)
    svc = QueryService(idx.engine, h=args.h, buckets=(1, 8, 32),
                       cache_size=4 * args.queries, num_shards=args.shards,
                       id_map=idx.pi)
    ms = _maybe_metrics_server(args, svc.obs.metrics)

    rng = np.random.default_rng(args.seed)
    sizes = rng.integers(1, 33, 64)

    def stream():
        served = 0
        for q in sizes:
            rows = rng.integers(0, args.queries, int(q))
            svc.search(q_dims[rows], q_vals[rows], q_dense[rows])
            served += int(q)
        return served

    stream()                                    # jit warmup, cold cache
    t0 = time.perf_counter()
    n = stream()
    dt = time.perf_counter() - t0
    print(f"stream: {n} queries in {dt:.2f}s ({n / dt:.1f} QPS)")

    t0 = time.perf_counter()
    idx2 = HybridIndex.build(ds.x_sparse, ds.x_dense,
                             dataclasses.replace(params, seed=args.seed + 1))
    build_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    svc.refresh(idx2.engine, id_map=idx2.pi)
    swap_s = time.perf_counter() - t0
    print(f"refresh: rebuild {build_s:.2f}s off-path, swap {swap_s * 1e3:.2f} ms")

    info, jit = svc.cache_info(), svc.jit_cache_info()
    print(f"cache: {info.hits} hits / {info.misses} misses "
          f"(hit rate {info.hit_rate:.2f}, {info.evictions} evictions)")
    print(f"jit shapes: {jit.batch_shapes} (bound {jit.bound})")
    print("stats:", svc.stats())
    if ms is not None:
        ms.close()
    svc.close()


def run_router(args) -> None:
    """Local cluster demo (DESIGN.md §8): spawn the shard-server topology,
    drive mutations + searches through a ``ClusterRouter``, report stats."""
    import tempfile

    from repro.core.hybrid import HybridIndex, HybridIndexParams
    from repro.data import make_hybrid_dataset
    from repro.serve.cluster import LocalCluster

    n0 = args.points - 64
    ds = make_hybrid_dataset(num_points=args.points, num_queries=args.queries,
                             d_sparse=args.points, d_dense=64,
                             nnz_per_row=48, seed=args.seed)
    params = HybridIndexParams(keep_top=96, head_dims=64, kmeans_iters=6)
    idx = HybridIndex.build(ds.x_sparse[:n0], ds.x_dense[:n0], params,
                            mutable=True)
    root = tempfile.mkdtemp(prefix="cluster-demo-")
    print(f"spawning cluster: primary + {args.cluster_scorers} scorer(s) + "
          f"{args.replicas} replica(s) under {root}")
    with LocalCluster.launch(idx, root, num_scorers=args.cluster_scorers,
                             num_replicas=args.replicas) as cluster:
        router = cluster.router(h=args.h,
                                replica_max_lag=args.replica_max_lag)
        ms = _maybe_metrics_server(args, router.obs.metrics)
        new = router.insert(ds.x_sparse[n0:], ds.x_dense[n0:])
        router.delete(new[:8].tolist())
        t0 = time.perf_counter()
        s, ids = router.search_sparse(ds.q_sparse, ds.q_dense)
        dt = time.perf_counter() - t0
        print(f"served {ids.shape[0]} queries in {dt:.2f}s "
              f"(top ids {ids[0, :5].tolist()})")
        print("router status:", router.status())
        print("hop stage totals (s):", router.hops())
        if ms is not None:
            ms.close()
        router.close()


def main():
    """Parse args and dispatch to the LM, retrieval, or cluster launcher.

    ``--role shard`` short-circuits BEFORE the full parser: the remaining
    flags (with ``--shard-role`` mapped to the server's ``--role``) are
    handed verbatim to ``repro.serve.cluster.shard_server.main``, so one
    entry point launches any node of a hand-laid-out deployment."""
    import sys
    argv = sys.argv[1:]
    if "--role" in argv and argv[argv.index("--role") + 1] == "shard":
        from repro.serve.cluster import shard_server
        i = argv.index("--role")
        rest = argv[:i] + argv[i + 2:]
        rest = ["--role" if a == "--shard-role" else a for a in rest]
        return shard_server.main(rest)
    ap = argparse.ArgumentParser()
    ap.add_argument("--retrieval", action="store_true",
                    help="serve a hybrid retrieval index instead of an LM")
    # cluster mode (DESIGN.md §8)
    ap.add_argument("--role", choices=["router", "shard"],
                    help="cluster mode: 'shard' runs one shard-server "
                         "process; 'router' spawns and drives a local "
                         "cluster")
    ap.add_argument("--cluster-scorers", type=int, default=2)
    ap.add_argument("--replicas", type=int, default=0)
    ap.add_argument("--replica-max-lag", type=int, default=0)
    # LM mode
    ap.add_argument("--arch")
    ap.add_argument("--tokens", type=int, default=32)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--pq-head", action="store_true")
    ap.add_argument("--penalty", type=float, default=0.0)
    # retrieval mode
    ap.add_argument("--points", type=int, default=20000)
    ap.add_argument("--queries", type=int, default=64)
    ap.add_argument("--shards", type=int, default=1)
    ap.add_argument("--h", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--persist-dir",
                    help="bootstrap a durable snapshot store + WAL here "
                         "and serve with every mutation logged")
    ap.add_argument("--restore",
                    help="recover the index from this store (snapshot + "
                         "WAL replay) and serve it")
    ap.add_argument("--metrics-port", type=int, default=None,
                    help="expose the process's metrics registry as a text "
                         "endpoint on this port (0 = ephemeral; DESIGN.md "
                         "§9.1).  In --role shard mode the flag is "
                         "forwarded to the shard server")
    args = ap.parse_args()
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.role == "router":
        run_router(args)
    elif args.retrieval:
        run_retrieval(args)
    else:
        if not args.arch:
            ap.error("--arch is required in LM mode")
        run_lm(args)


if __name__ == "__main__":
    main()
