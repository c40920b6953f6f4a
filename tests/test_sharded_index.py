"""Row-sharded HybridIndex served through QueryService (paper §7.2): one
shard per device, built over its own rows, fanned out and merged on the
host.  Needs 4 devices, so it runs in a subprocess with
XLA_FLAGS=--xla_force_host_platform_device_count=4 (jax locks the device
count at first init); the subprocess reports its findings as JSON, and each
test below checks one of them."""

import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO_SRC = os.path.join(os.path.dirname(__file__), "..", "src")

# recall@10 of the served answers against exact float64 search over the
# whole corpus: 0.9875-1.0 over dataset seeds 3, 11, 23 at this size, as
# the unsharded index reads (0.9875-1.0).  0.9 leaves room for other data.
RECALL_FLOOR = 0.9
# served score minus the exact score of the same (query, row) pair, over
# the query's exact best score, worst pair: 1.5e-3 to 1.8e-3 over the same
# seeds (the int8 dense residual's rounding).  The same pairs scored with
# the dense rows rounded to 16 levels a dimension (int4), the precision
# below the stated one, read 5.7e-2 to 7.3e-2; 2e-2 lies between the two.
SCORE_REL_ERR = 2e-2

SCRIPT = """
import json, os, time
import numpy as np, jax, jax.numpy as jnp
from repro.core.distributed import merge_topk_host
from repro.core.hybrid import HybridIndex, HybridIndexParams
from repro.core.sparse_index import sparse_queries_to_padded
from repro.data import make_hybrid_dataset
from repro.obs import Observability
from repro.serve import QueryService

H = 10
out = {}
ds = make_hybrid_dataset(num_points=8000, num_queries=16, d_sparse=6000,
                         d_dense=32, nnz_per_row=30, seed=11)
params = HybridIndexParams(keep_top=64, head_dims=32, kmeans_iters=4,
                           backend="pallas-packed", num_shards=4)
jax.devices()
# JAX here is already on the CPU; the host-stage workers inherit this
# environment and must hold themselves to the CPU all the same, and the
# build must leave the environment as it found it
os.environ["JAX_PLATFORMS"] = "tpu"
obs = Observability(trace=True)
idx = HybridIndex.build(ds.x_sparse, ds.x_dense, params, mutable=True,
                        obs=obs)
out["environment_kept"] = os.environ["JAX_PLATFORMS"] == "tpu"
out["shard_rows"] = [s.num_points for s in idx.shards]
out["offsets"] = [int(o) for o in idx.offsets]
out["shard_devices"] = [
    sorted({d.id for leaf in jax.tree.leaves(e.arrays)
            for d in leaf.devices()}) for e in idx.engines]
out["committed"] = all(leaf.committed for e in idx.engines
                       for leaf in jax.tree.leaves(e.arrays))
# no one shard stands for the index: its engine is every shard's, and
# refuses to search or to be served as one engine
jax.block_until_ready(idx.engine.arrays)
out["engine_arrays"] = len(idx.engine.arrays)
out["per_row_artifacts"] = [
    getattr(idx, k) is None for k in ("inv_index", "head", "head_dim_ids",
                                      "sparse_residual", "codebooks",
                                      "codes", "dense_residual")]
refused = []
for attempt in (lambda: QueryService(engine=idx.engine, h=H),
                lambda: QueryService(engine=idx.engines[0], index=idx, h=H),
                lambda: idx.engine.search(None, None, None, h=H)):
    try:
        attempt()
        refused.append(False)
    except ValueError:
        refused.append(True)
out["refused"] = refused

svc = QueryService(index=idx, h=H, cache_size=0, obs=obs)
out["service_shards"] = svc.stats()["num_shards"]
qd, qv = sparse_queries_to_padded(ds.q_sparse, idx.cols,
                                  nq_max=params.nq_max)
qe = np.asarray(ds.q_dense, np.float32)
s, ids = svc.search(qd, qv, qe)

# the four shard engines searched one by one, merged on the host
parts = []
for e, off in zip(idx.engines, idx.offsets):
    ps, pi, _ = e.search(jnp.asarray(qd), jnp.asarray(qv), jnp.asarray(qe),
                         h=H, alpha=20, beta=5)
    parts.append((np.asarray(ps),
                  idx.mutable_state.id_map[np.asarray(pi) + off], True))
ms, mi = merge_topk_host(parts, H)
out["merge_ids_equal"] = bool(np.array_equal(ids, mi))
out["merge_scores_equal"] = bool(np.array_equal(s, ms))

# exact float64 brute force over the whole corpus
xs = ds.x_sparse.astype(np.float64)
qs = ds.q_sparse.astype(np.float64)
exact = (np.asarray((qs @ xs.T).todense())
         + qe.astype(np.float64) @ ds.x_dense.astype(np.float64).T)
top = np.argsort(-exact, axis=1)[:, :H]
out["recall"] = float(np.mean([len(set(a) & set(b)) / H
                               for a, b in zip(top, ids)]))
pair = np.take_along_axis(exact, ids, axis=1)
best = np.abs(exact.max(axis=1, keepdims=True))
out["score_rel_err"] = float(np.max(np.abs(s - pair) / best))

# mutation through the sharded service
new = svc.insert(ds.q_sparse[:2] * 1e3, ds.q_dense[:2])
gone = int(ids[5, 0])
svc.delete([gone])
s2, i2 = svc.search(qd, qv, qe)
# each inserted row is its own query scaled by 1e3, so it outscores every
# corpus row for that query (another inserted row may rank above it)
out["inserted_top"] = [bool(int(new[j]) in i2[j]
                            and int(i2[j, 0]) in set(map(int, new)))
                       for j in range(2)]
out["deleted_gone"] = gone not in i2
out["dispatches_with_delta"] = svc.metrics()["serve.shard_dispatches"]

traces = obs.tracer.take()
build = [t for t in traces if t["name"] == "index.build"][0]
out["build_span"] = {"tags": build["tags"],
                     "children": [(c["name"], c["tags"])
                                  for c in build["children"]]}
batch = [c for t in traces if t["name"] == "serve.search"
         for c in t["children"] if c["name"] == "serve.batch"]
out["batch_shards"] = sorted({c["tags"]["shards"] for c in batch})

svc.compact()
out["compacted_shards"] = [len(svc._index.shards),
                           svc.stats()["num_shards"],
                           svc.metrics()["index.shards"]]
out["compacted_devices"] = [
    sorted({d.id for leaf in jax.tree.leaves(e.arrays)
            for d in leaf.devices()}) for e in svc._index.engines]
s3, i3 = svc.search_sparse(ds.q_sparse, ds.q_dense)
out["after_compact"] = [int(new[0]) in i3[0], gone not in i3]
out["rebuild_span"] = any(t["name"] == "index.build"
                          for t in obs.tracer.take())
out["metrics"] = svc.metrics()
svc.close()
print("RESULT " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def found():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = REPO_SRC
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([sys.executable, "-c", textwrap.dedent(SCRIPT)],
                          capture_output=True, text=True, env=env,
                          timeout=560)
    assert proc.returncode == 0, proc.stdout + "\n" + proc.stderr[-4000:]
    line = [x for x in proc.stdout.splitlines() if x.startswith("RESULT ")]
    return json.loads(line[-1][len("RESULT "):])


def test_each_shard_sits_on_its_own_device(found):
    assert found["shard_rows"] == [2000] * 4
    assert found["offsets"] == [0, 2000, 4000, 6000]
    assert found["shard_devices"] == [[0], [1], [2], [3]]
    assert found["committed"]


def test_workers_hold_to_the_cpu_and_leave_the_environment(found):
    # the build ran with JAX_PLATFORMS=tpu in its environment on a machine
    # without one: its host-stage workers got through only on the CPU
    assert found["environment_kept"]


def test_sharded_index_has_no_one_engine(found):
    assert found["engine_arrays"] == 4
    assert all(found["per_row_artifacts"])
    assert found["refused"] == [True, True, True]


def test_service_returns_the_host_merge_of_the_shard_engines(found):
    assert found["service_shards"] == 4
    assert found["merge_ids_equal"]
    assert found["merge_scores_equal"]


def test_sharded_answers_against_exact_search(found):
    assert found["recall"] >= RECALL_FLOOR
    assert found["score_rel_err"] <= SCORE_REL_ERR


def test_insert_and_delete_reach_the_next_search(found):
    assert found["inserted_top"] == [True, True]
    assert found["deleted_gone"]


def test_compaction_keeps_four_shards_on_their_devices(found):
    assert found["compacted_shards"] == [4, 4, 4]
    assert found["compacted_devices"] == [[0], [1], [2], [3]]
    assert found["after_compact"] == [True, True]
    assert found["rebuild_span"]


def test_spans_and_counters_of_the_sharded_path(found):
    span = found["build_span"]
    assert span["tags"] == {"rows": 8000, "shards": 4}
    assert [name for name, _ in span["children"]] == \
        ["index.build.shard"] * 4
    tags = sorted((t for _, t in span["children"]), key=lambda t: t["shard"])
    assert [t["shard"] for t in tags] == [0, 1, 2, 3]
    assert [t["rows"] for t in tags] == [2000] * 4
    assert len({t["device"] for t in tags}) == 4
    assert all(t["host_s"] > 0 and t["device_s"] > 0 for t in tags)
    assert found["batch_shards"] == [4]
    m = found["metrics"]
    assert m["index.shards"] == 4
    # one search of 4 engines, then one of 4 engines and the live delta,
    # then one after compaction (delta empty again)
    assert found["dispatches_with_delta"] == 4 + 5
    assert m["serve.shard_dispatches"] == 4 + 5 + 4


def _build_as_before(x_sparse, x_dense, params):
    """The single-shard build as it ran before row shards existed, step by
    step from the building blocks: what ``num_shards=1`` must still give."""
    import jax.numpy as jnp
    import numpy as np

    from repro.core.cache_sort import cache_sort, dimension_activity
    from repro.core.engine import Backend, IndexArrays
    from repro.core.hybrid import _remap
    from repro.core.pq import (pq_decode, pq_encode, scalar_quantize,
                               train_codebooks)
    from repro.core.pruning import prune_split
    from repro.core.sparse_index import (build_compact_columns,
                                         build_padded_inverted_index,
                                         build_padded_rows,
                                         build_tile_sparse_head)
    x_sparse = x_sparse.tocsr()
    x_dense = np.asarray(x_dense, np.float32)
    pi = cache_sort(x_sparse)
    xs, xd = x_sparse[pi], x_dense[pi]
    split = prune_split(xs, keep_top=params.keep_top)
    cols, _ = build_compact_columns(xs)
    idx_compact = _remap(split.index, cols)
    res_compact = _remap(split.residual, cols)
    activity = dimension_activity(idx_compact)
    n_head = min(params.head_dims, cols.num_active)
    head_compact = np.sort(np.argsort(-activity)[:n_head]).astype(np.int32)
    head = build_tile_sparse_head(idx_compact, head_compact,
                                  block_rows=params.block_rows,
                                  block_cols=params.block_cols)
    tail = idx_compact.tolil()
    tail[:, head_compact] = 0
    tail = tail.tocsr()
    tail.eliminate_zeros()
    k_u = params.pq_subspaces or max(xd.shape[1] // 2, 1)
    cb = train_codebooks(jnp.asarray(xd), k_u, params.pq_codes,
                         iters=params.kmeans_iters, seed=params.seed)
    codes = pq_encode(jnp.asarray(xd), cb)
    recon = np.asarray(pq_decode(codes, cb))
    dres = scalar_quantize(jnp.asarray(xd - recon))
    backend = params.resolve_backend()
    arrays = IndexArrays.build(
        codebooks=cb, codes=codes, inv_index=build_padded_inverted_index(tail),
        head=head, dense_residual=dres,
        sparse_residual=build_padded_rows(res_compact),
        num_points=xs.shape[0], d_active=cols.num_active,
        with_bcsr=backend in (Backend.PALLAS, Backend.PALLAS_PACKED),
        pack=params.resolve_pack())
    return pi, cols, arrays


def test_one_shard_builds_the_arrays_it_built_before(small_hybrid):
    import jax
    import numpy as np

    from repro.core.hybrid import HybridIndex, HybridIndexParams
    ds = small_hybrid
    params = HybridIndexParams(keep_top=48, head_dims=48, kmeans_iters=6,
                               backend="pallas-packed")
    assert params.num_shards == 1
    idx = HybridIndex.build(ds.x_sparse, ds.x_dense, params)
    pi, cols, arrays = _build_as_before(ds.x_sparse, ds.x_dense, params)
    assert idx.shards is None and idx.engines == [idx.engine]
    np.testing.assert_array_equal(idx.pi, pi)
    np.testing.assert_array_equal(idx.cols.global_ids, cols.global_ids)
    got, want = idx.engine.arrays, arrays
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
