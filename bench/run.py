#!/usr/bin/env python3
"""One run of one benchmark cell, on the chip it is started on.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json`` at the root of
the checkout.  It names a configuration (``bench/configs/<name>.json``: the
data model, the index and search parameters, the limits of the check) and a
traffic mix (``bench/traffic/<name>.json``: loop kind, clients, batch,
rate).  Per-layer metrics are readers of their own,
``bench/metrics/<name>.py``, each with ``read(run) -> float | None``.  A
later cell, configuration or metric is new files and new entries.

A run: claim the chip (JAX held to the TPU; no accelerator, or fewer chips
than the cell asks for, exits non-zero with no result); make the corpus and
the queries from ``--seed``; build the index through the program's
``HybridIndex.build`` and serve it through ``QueryService``; warm the
cell's one batch shape; drive the window; then, with the program's state
freed, compare a sample of the served answers with the plain reference
(``reference.py``).  The last line of standard output is the result: the
end-to-end metrics with ``--trace 0``, the per-layer ones with
``--trace 1``, which also records a profiler trace of the window and
reduces it (``tracing.py``).
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse                                          # noqa: E402
import dataclasses                                       # noqa: E402
import gc                                                # noqa: E402
import importlib.util                                    # noqa: E402
import json                                              # noqa: E402
import math                                              # noqa: E402
import os                                                # noqa: E402
import shutil                                            # noqa: E402
import sys                                               # noqa: E402
import tempfile                                          # noqa: E402

import numpy as np                                       # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

import datagen                                           # noqa: E402
import drivers                                           # noqa: E402
import reference                                         # noqa: E402
import tracing                                           # noqa: E402


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list[dict]
    per_layer: list[dict]


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(root: str, name: str) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json`` with its files."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    with open(os.path.join(root, cfg_entry["file"])) as f:
        config = json.load(f)
    with open(os.path.join(root, "bench", "traffic",
                           w["traffic"] + ".json")) as f:
        traffic = json.load(f)
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic,
                end_to_end=[m for m in bench["end_to_end"]
                            if _applies(m, name)],
                per_layer=[m for m in bench["per_layer"]
                           if _applies(m, name)])


def load_reader(root: str, metric: str):
    """``read`` of ``<root>/bench/metrics/<metric>.py``, or, where there is
    none, of the file named by the metric's name up to its first ``.``:
    ``search_device_ms.py`` reads ``search_device_ms.bulk`` and
    ``search_device_ms.online``."""
    path = os.path.join(root, "bench", "metrics", metric + ".py")
    if not os.path.exists(path):
        path = os.path.join(root, "bench", "metrics",
                            metric.split(".")[0] + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def claim_devices(chips: int):
    """JAX on the TPU or nothing: the devices of the cell, or exit."""
    import jax
    jax.config.update("jax_platforms", "tpu")
    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise SystemExit(f"no TPU found: {e}")
    if devices[0].platform != "tpu" or len(devices) < chips:
        raise SystemExit(f"the cell needs {chips} TPU chip(s); JAX found "
                         f"{len(devices)} {devices[0].platform} device(s)")
    return devices[:chips]


@dataclasses.dataclass
class RunData:
    """What a per-layer reader may read.  ``trace`` is None in an untraced
    run; a reader that finds nothing returns None."""
    trace: tracing.Trace | None
    config: dict
    traffic: dict
    device_kind: str
    rows: int
    batch: int
    build_s: float
    queue_wait_s: np.ndarray | None


class GcWatch:
    """Records the garbage collector's pauses, to show what falls in the
    window."""

    def __init__(self):
        self.pauses: list[float] = []
        self._t0 = None
        gc.callbacks.append(self._on)

    def _on(self, phase, info):
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            self.pauses.append(time.perf_counter() - self._t0)


class CompileCounter:
    """Counts traces and backend compiles, to show none fall in the window."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event in self.EVENTS:
            self.count += 1


def _percentile(x: np.ndarray, q: float) -> float:
    return float(np.percentile(x, q)) if len(x) else math.inf


def _finite(x):
    """JSON has no infinity: a value that is not finite prints as null."""
    return x if isinstance(x, (int, np.integer)) or math.isfinite(x) \
        else None


@dataclasses.dataclass
class Setup:
    """Everything a window needs, made from the seed."""
    cell: Cell
    seed: int
    corpus: datagen.Corpus
    queries: datagen.QueryPool
    q_dims: np.ndarray
    q_vals: np.ndarray
    pool: int                         # window queries: pool rows 0..pool-1
    offsets: np.ndarray | None        # open loop: due offsets of the pool
    svc: object                       # the program's QueryService
    build_s: float
    setup_s: float

    @property
    def h(self) -> int:
        return int(self.cell.config["search"]["h"])

    @property
    def batch(self) -> int:
        return int(self.cell.traffic["batch"])

    @property
    def closed(self) -> bool:
        return self.cell.traffic["loop"] == "closed"


# the open loop's arrival processes, by the traffic file's ``arrivals``
ARRIVALS = {"poisson": drivers.poisson_schedule}


def arrival_schedule(traffic: dict):
    kind = traffic.get("arrivals", "poisson")
    if kind not in ARRIVALS:
        raise SystemExit(f"arrivals {kind!r} unknown; known: "
                         f"{sorted(ARRIVALS)}")
    return ARRIVALS[kind]


def set_up(cell: Cell, seed: int, seconds: float) -> Setup:
    """Data from the seed, the program's build, and the warm-up of the one
    batch shape the cell's traffic sends."""
    import jax
    from jax.profiler import TraceAnnotation

    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.core.hybrid import HybridIndex, HybridIndexParams
    from repro.core.sparse_index import sparse_queries_to_padded
    from repro.serve import QueryService

    cfg, traffic = cell.config, cell.traffic
    search = cfg["search"]
    spec = datagen.DataSpec.from_config(cfg)
    batch = int(traffic["batch"])
    t0 = time.perf_counter()
    with TraceAnnotation("bench.generate"):
        corpus = datagen.make_corpus(spec, seed)
        offsets = None
        if traffic["loop"] == "closed":
            pool = int(traffic["pool"])
        else:
            rng = np.random.default_rng(datagen.sub_seed(seed, 5))
            offsets = arrival_schedule(traffic)(rng, float(traffic["rate"]),
                                                seconds)
            pool = len(offsets)
        qp = datagen.make_queries(spec, corpus, pool + 3 * batch, seed, 0)
    t_data = time.perf_counter()
    params = HybridIndexParams(**cfg["index"])
    idx = HybridIndex.build(corpus.x_sparse, corpus.x_dense, params,
                            mutable=True)
    jax.block_until_ready(idx.engine.arrays)
    build_s = time.perf_counter() - t_data
    q_dims, q_vals = sparse_queries_to_padded(qp.q_sparse, idx.cols,
                                              nq_max=params.nq_max)
    svc = QueryService(index=idx, h=int(search["h"]),
                       alpha=int(search["alpha"]), beta=int(search["beta"]),
                       buckets=tuple(cfg["buckets"]))
    # warm-up queries lie past the pool, so the window never repeats them
    for k in range(2):
        rows = pool + k * batch + np.arange(batch)
        svc.search(q_dims[rows], q_vals[rows], qp.q_dense[rows])
    rows = pool + 2 * batch + np.arange(batch)
    svc.submit(q_dims[rows], q_vals[rows], qp.q_dense[rows]).result()
    # collect the build's garbage now, and keep what set-up made out of
    # later collections, so that none scans it in the window
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - T_PROCESS
    log(f"set-up {setup_s:.3f} s: data {t_data - t0:.3f} s, build "
        f"{build_s:.3f} s, queries and warm-up "
        f"{time.perf_counter() - t_data - build_s:.3f} s; "
        f"{idx.num_points} rows, {corpus.x_sparse.nnz} sparse nonzeros, "
        f"{pool} window queries of {batch}")
    return Setup(cell=cell, seed=seed, corpus=corpus, queries=qp,
                 q_dims=q_dims, q_vals=q_vals, pool=pool, offsets=offsets,
                 svc=svc, build_s=build_s, setup_s=setup_s)


@dataclasses.dataclass
class Window:
    result: object                    # drivers.ClosedLoopResult / OpenLoop
    served_s: np.ndarray              # (pool, h) scores served per query
    served_i: np.ndarray              # (pool, h) ids served per query
    done: np.ndarray                  # pool rows answered in the window
    attempted: int
    failed: int


def drive(st: Setup, seconds: float) -> Window:
    """The measured window through the program's QueryService."""
    from jax.profiler import TraceAnnotation

    svc, q_dims, q_vals, q_dense = (st.svc, st.q_dims, st.q_vals,
                                    st.queries.q_dense)
    served_s = np.full((st.pool, st.h), np.nan, np.float32)
    served_i = np.full((st.pool, st.h), -1, np.int64)
    with TraceAnnotation(tracing.WINDOW_SPAN):
        if st.closed:
            def call(rows):
                with TraceAnnotation("bench.search"):
                    s, i = svc.search(q_dims[rows], q_vals[rows],
                                      q_dense[rows])
                with TraceAnnotation("bench.result"):
                    served_s[rows], served_i[rows] = s, i

            res = drivers.closed_loop(call, st.pool, st.batch,
                                      int(st.cell.traffic["clients"]),
                                      seconds)
        else:
            marks = {}
            service_search = svc.search

            def entered_search(qd, qv, qe, **kw):
                mark = marks.pop(id(qd), None)
                if mark is not None:
                    mark()
                with TraceAnnotation("bench.search"):
                    return service_search(qd, qv, qe, **kw)

            # submit() runs self.search on a worker: mark when it begins
            svc.search = entered_search

            def submit(k, mark):
                qd = q_dims[k:k + 1]
                marks[id(qd)] = mark
                fut = svc.submit(qd, q_vals[k:k + 1], q_dense[k:k + 1])

                def store(f, k=k):
                    if f.exception() is None:
                        served_s[k], served_i[k] = (x[0] for x in f.result())
                fut.add_done_callback(store)
                return fut

            try:
                res = drivers.open_loop(submit, st.offsets)
            finally:
                del svc.search
    if st.closed:
        done = (np.unique(np.concatenate(res.done)) if res.done
                else np.empty(0, np.int64))
        failed = res.attempted - res.completed
        log(f"closed loop: {res.completed} queries in {len(res.done)} "
            f"batches over {res.seconds:.6f} s; {failed} failed"
            + (f" ({res.errors[0]!r})" if res.errors else ""))
        return Window(res, served_s, served_i, done, res.attempted, failed)
    late = res.lateness
    failed = int((~res.ok).sum())
    log(f"open loop: {len(res.ok)} requests at {st.cell.traffic['rate']} "
        f"req/s; {failed} failed; generator lateness p50 "
        f"{_percentile(late, 50) * 1e3:.6f} ms, p95 "
        f"{_percentile(late, 95) * 1e3:.6f} ms, max "
        f"{float(np.max(late, initial=0)) * 1e3:.6f} ms")
    return Window(res, served_s, served_i, np.flatnonzero(res.ok),
                  len(res.ok), failed)


def release(st: Setup, devices) -> int:
    """Read the device peak and the cache counters, then free the
    program's state so that the reference has the chip.  Returns the peak
    bytes in use on the fullest chip."""
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    cache = st.svc.cache_info()
    log(f"result cache (serve.cache.*): {cache.hits} hits, {cache.misses} "
        f"misses, {cache.evictions} evictions")
    st.svc.close()
    st.svc = None
    gc.unfreeze()
    gc.collect()
    return int(peak)


def draw_sample(st: Setup, done: np.ndarray) -> np.ndarray:
    """The answered window queries the check compares, drawn from the seed."""
    rng = np.random.default_rng(datagen.sub_seed(st.seed, 4))
    n = min(int(st.cell.config["sample"]), len(done))
    return np.sort(rng.choice(done, n, replace=False)) if n else done[:0]


def reference_top(st: Setup, sample: np.ndarray):
    """The exact top-h of the sampled queries, computed by the reference."""
    return reference.exact_topk(st.corpus.x_sparse, st.corpus.x_dense,
                                st.queries.q_sparse[sample],
                                st.queries.q_dense[sample], st.h)


def judge(st: Setup, sample: np.ndarray, served_s: np.ndarray,
          served_i: np.ndarray, exact, failed: int) -> dict:
    """The compared numbers and recall for answers to the sampled queries
    (rows of ``served_*`` follow ``sample``)."""
    if not len(sample):
        return {"score_err_rms": math.inf, "recall_miss": 1.0,
                "malformed": 0, "failed": failed, "recall_at_10": 0.0,
                "score_err_max": math.inf}
    pair = reference.pair_scores(st.corpus.x_sparse, st.corpus.x_dense,
                                 st.queries.q_sparse[sample],
                                 st.queries.q_dense[sample], served_i)
    return reference.compare(served_s, served_i, pair, *exact, failed)


def checks_of(cfg: dict, nums: dict) -> tuple[bool, dict]:
    limits = cfg["limits"]
    checks = {k: {"value": nums[k], "limit": limits[k]}
              for k in reference.CHECKS}
    ok = all(c["value"] <= c["limit"] for c in checks.values())
    return ok, checks


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = load_cell(ROOT, args.workload)
    devices = claim_devices(cell.chips)
    import jax

    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.compile_cache import enable_compile_cache

    log(f"cell {cell.name}: seed {args.seed}, {args.seconds} s, trace "
        f"{args.trace}; compile cache {enable_compile_cache()}")
    compiles = CompileCounter()
    st = set_up(cell, args.seed, args.seconds)
    gcw = GcWatch()

    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if args.trace \
        else None
    before = compiles.count
    if trace_dir:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        win = drive(st, args.seconds)
    finally:
        if trace_dir:
            jax.profiler.stop_trace()
    log(f"traces or compiles in the window: {compiles.count - before}; "
        f"garbage collections {len(gcw.pauses)}, longest "
        f"{max(gcw.pauses, default=0.0) * 1e3:.3f} ms")
    peak = release(st, devices)

    t_ref = time.perf_counter()
    sample = draw_sample(st, win.done)
    exact = reference_top(st, sample) if len(sample) else None
    nums = judge(st, sample, win.served_s[sample], win.served_i[sample],
                 exact, win.failed)
    correct, checks = checks_of(cell.config, nums)
    correct = correct and bool(len(sample))
    log(f"reference: {len(sample)} sampled answers of {len(win.done)} in "
        f"{time.perf_counter() - t_ref:.3f} s; recall@{st.h} "
        f"{nums['recall_at_10']}; widest relative score gap "
        f"{nums['score_err_max']}")

    out = {"correct": correct, "attempted": int(win.attempted),
           "failed": int(win.failed)}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": peak}
    metrics = {}
    res = win.result
    if not args.trace:
        if st.closed:
            e2e = {"qps": res.completed / res.seconds}
        else:
            lat = np.where(res.ok, res.latency, math.inf) * 1e3
            e2e = {"latency_p50_ms": _percentile(lat, 50),
                   "latency_p95_ms": _percentile(lat, 95)}
            log(f"latency from due time: p50 {e2e['latency_p50_ms']:.6f} "
                f"ms, p95 {e2e['latency_p95_ms']:.6f} ms over {len(lat)} "
                f"requests")
        e2e["recall_at_10"] = nums["recall_at_10"]
        e2e["setup_s"] = st.setup_s
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": _finite(e2e[m["name"]]),
                                  "unit": m["unit"]}
    else:
        path = tracing.find_xplane(trace_dir)
        trace = tracing.load(path) if path else None
        shutil.rmtree(trace_dir, ignore_errors=True)
        data = RunData(trace=trace, config=cell.config, traffic=cell.traffic,
                       device_kind=devices[0].device_kind,
                       rows=int(cell.config["num_rows"]), batch=st.batch,
                       build_s=st.build_s,
                       queue_wait_s=None if st.closed else res.queue_wait)
        for m in cell.per_layer:
            value = load_reader(ROOT, m["name"])(data)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        if trace is not None:
            device["busy_s"] = tracing.busy_s(trace)
            device["window_s"] = tracing.window_s(trace)
            out["breakdown"] = tracing.breakdown(trace)
    out["metrics"] = metrics
    out["device"] = device
    out["checks"] = {k: {"value": _finite(c["value"]), "limit": c["limit"]}
                     for k, c in checks.items()}
    for k, c in checks.items():
        log(f"check {k}: {c['value']} (limit {c['limit']})")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
