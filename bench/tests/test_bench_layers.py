"""The reductions of the program's own instrumentation: named scopes from
HLO text, device time by scope, and the serve.* span trees."""

from __future__ import annotations

import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(REPO, "bench"))

import layers                                         # noqa: E402
import tracing                                        # noqa: E402
from tracing import Event, Trace                      # noqa: E402

from test_bench_tracing import RECORDED                # noqa: E402


@pytest.mark.parametrize("op_name, scope", [
    ("jit(three_pass_search)/pass2/jit(dense_residual_scores)/mul", "pass2"),
    ("jit(three_pass_search)/pass1.sparse_bias/head/slice",
     "pass1.sparse_bias/head"),
    ("jit(three_pass_search)/pass1.sparse_bias/inverted/jit(score_inverted)",
     "pass1.sparse_bias/inverted"),
    ("jit(three_pass_search)/add", ""),
    ("q_vals", ""),
    ("scatter-add", ""),
])
def test_scope_of(op_name, scope):
    assert layers.scope_of(op_name) == scope


HLO = """HloModule jit_f, entry_computation_layout={(f32[8]{0})->f32[8]{0}}

%fused_computation (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  ROOT %add.1 = f32[8]{0} add(%param_0, %param_0), metadata={op_name="jit(f)/pass2/add"}
}

%body (p: (u32[], f32[8])) -> (u32[], f32[8]) {
  %p = (u32[], f32[8]) parameter(0)
  ROOT %tuple.2 = (u32[], f32[8]) tuple(%p)
}

%cond (p.1: (u32[], f32[8])) -> pred[] {
  %p.1 = (u32[], f32[8]) parameter(0)
  ROOT %constant.3 = pred[] constant(true)
}

ENTRY %main (x: f32[8]) -> f32[8] {
  %x = f32[8]{0} parameter(0), metadata={op_name="x"}
  %copy.1 = f32[8]{0} copy(%x)
  %fusion.1 = f32[8]{0} fusion(%copy.1), kind=kLoop, calls=%fused_computation
  %tuple.1 = (u32[], f32[8]) tuple(%fusion.1)
  %while.1 = (u32[], f32[8]) while(%tuple.1), condition=%cond, body=%body
  %get-tuple-element.1 = f32[8]{0} get-tuple-element(%while.1), index=1
  %neg.1 = f32[8]{0} negate(%get-tuple-element.1), metadata={op_name="jit(f)/pass3/neg"}
  %neg.2 = f32[8]{0} negate(%x), metadata={op_name="jit(f)/pass1.lut/neg"}
  ROOT %add.2 = f32[8]{0} add(%neg.1, %neg.2)
}
"""


def test_scope_map_of_hand_made_hlo():
    """Own metadata first; a fusion takes its computation's scope; a copy
    the one scope of its users; a loop the scope its neighbours agree on,
    and its body the loop's; where neighbours disagree, none."""
    m = layers.scope_map(HLO)
    assert m["add.1"] == m["fusion.1"] == "pass2"
    assert m["copy.1"] == "pass2"
    assert m["neg.1"] == "pass3" and m["neg.2"] == "pass1.lut"
    assert m["while.1"] == m["tuple.1"] == "pass3"
    assert m["tuple.2"] == m["p"] == m["constant.3"] == "pass3"
    assert m["x"] == m["add.2"] == ""


@pytest.mark.parametrize("backend", ["ref", "pallas-packed"])
def test_scope_map_of_the_compiled_search(backend):
    """``ScoringEngine.search_hlo`` of a CPU build carries every scope of
    ``three_pass_search``: the five top-level ones and the two inside
    ``pass1.sparse_bias``."""
    import jax.numpy as jnp
    import numpy as np
    sys.path.insert(0, os.path.join(REPO, "src"))
    from repro.core.hybrid import HybridIndex, HybridIndexParams
    from repro.core.sparse_index import sparse_queries_to_padded
    from repro.data import make_hybrid_dataset
    ds = make_hybrid_dataset(num_points=2048, num_queries=8, d_sparse=3000,
                             d_dense=64, nnz_per_row=20, seed=1)
    idx = HybridIndex.build(ds.x_sparse, ds.x_dense, HybridIndexParams(
        keep_top=64, head_dims=16, kmeans_iters=2, backend=backend))
    qd, qv = sparse_queries_to_padded(ds.q_sparse, idx.cols,
                                      nq_max=idx.params.nq_max)
    text = idx.engine.search_hlo(
        jnp.asarray(qd), jnp.asarray(qv),
        jnp.asarray(np.asarray(ds.q_dense, np.float32)),
        h=10, alpha=4, beta=2)
    scopes = set(layers.scope_map(text).values())
    assert {s.split("/")[0] for s in scopes} - {""} == set(layers.TOP_SCOPES)
    assert {"pass1.sparse_bias/inverted",
            "pass1.sparse_bias/head"} <= scopes


def _trace():
    # two search runs [10, 50) and [60, 80); a loop [20, 40) holds two body
    # ops; an op of another program runs at 85; the window is [0, 100)
    ops = [Event("%fusion.1", 10, 10), Event("%while.1", 20, 20),
           Event("%body.1", 22, 8), Event("%body.2", 30, 5),
           Event("%lut16_adc_topk_pallas.1", 40, 10),
           Event("%fusion.1", 60, 10), Event("%copy.2", 70, 10),
           Event("%other", 85, 5)]
    modules = [Event("jit_three_pass_search(1)", 10, 40),
               Event("jit_three_pass_search(2)", 60, 20),
               Event("jit_other(3)", 85, 5)]
    return Trace(ops={"/device:TPU:0": ops},
                 modules={"/device:TPU:0": modules},
                 spans=[Event("bench.window", 0, 100)])


SCOPES = {"fusion.1": "pass1.sparse_bias/inverted",
          "while.1": "pass1.sparse_bias/inverted",
          "body.1": "pass1.sparse_bias/inverted", "body.2": "pass2",
          "lut16_adc_topk_pallas.1": "pass1.scan", "copy.2": ""}


def test_scope_seconds_counts_self_time_per_run():
    t = _trace()
    assert [e.name for e in layers.search_ops(t)] == [
        "%fusion.1", "%while.1", "%body.1", "%body.2",
        "%lut16_adc_topk_pallas.1", "%fusion.1", "%copy.2"]
    sec = layers.scope_seconds(t, SCOPES)
    # the loop's 20 ns hold its body's 8 + 5: self time 7
    assert sec == pytest.approx({"pass1.sparse_bias/inverted":
                                 (10 + 7 + 8 + 10) / 2 * 1e-9,
                                 "pass2": 5 / 2 * 1e-9,
                                 "pass1.scan": 10 / 2 * 1e-9,
                                 "": 10 / 2 * 1e-9})
    assert layers.seconds_under(sec, "pass1.sparse_bias") == \
        pytest.approx(17.5e-9)
    assert layers.seconds_under(sec, "pass2", "pass3") == \
        pytest.approx(2.5e-9)
    assert layers.seconds_under(sec, "pass1") == 0
    assert layers.seconds_under(None, "pass2") is None


def test_scope_seconds_refuses_a_map_of_another_program():
    t = _trace()
    stale = {k: v for k, v in SCOPES.items() if k != "copy.2"}
    assert layers.scope_seconds(t, stale) is None
    assert layers.scope_seconds(Trace(ops={}, modules={}, spans=[]),
                                SCOPES) is None


def test_scope_seconds_on_the_recorded_chip_trace():
    """Every operation inside the recorded runs, under one made-up scope,
    adds up to the device time of the search program per run (its ops
    cover the program's time up to the gaps between them)."""
    t = tracing.load(RECORDED)
    names = {e.name.lstrip("%") for e in layers.search_ops(t)}
    sec = layers.scope_seconds(t, dict.fromkeys(names, "all"))
    search = tracing.time_per_call(t, tracing.SEARCH_MODULE,
                                   level="modules")
    assert list(sec) == ["all"]
    assert 0.95 * search < sec["all"] <= search
    names.discard("lut16_adc_topk_pallas.1")
    assert layers.scope_seconds(t, dict.fromkeys(names, "all")) is None


def _span(name, dur, children=(), **tags):
    return {"name": name, "duration_s": dur, "tags": tags,
            "children": list(children)}


def test_span_self_seconds_and_root_tags():
    roots = [
        _span("serve.search", 10.0, [
            _span("serve.batch", 8.0, [_span("serve.pad", 1.0),
                                       _span("serve.merge", 2.0)])],
            queue_s=0.5),
        _span("serve.search", 4.0, [
            _span("serve.batch", 3.0, [_span("serve.merge", 1.5)])]),
    ]
    assert layers.self_seconds(roots, "serve.merge") == [2.0, 1.5]
    assert layers.self_seconds(roots, "serve.batch") == [5.0, 1.5]
    assert layers.self_seconds(roots, "serve.search") == [2.0, 1.0]
    assert layers.root_tags(roots, "queue_s") == [0.5]
    assert layers.root_tags(roots, "missing") == []
