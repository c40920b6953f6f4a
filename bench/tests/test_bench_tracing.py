"""The trace reduction on hand-made events and on a recorded chip trace."""

from __future__ import annotations

import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(REPO, "bench"))

import tracing                                        # noqa: E402
from tracing import Event, Trace                      # noqa: E402

RECORDED = os.path.join(REPO, "bench", "tests", "data", "querysim_small",
                        "trace.xplane.pb")


def _trace():
    # window [0, 100) ns; ops cover [10, 30) and [25, 40) and [60, 70);
    # one op reaches past the window's end
    kernel = "%lut16_adc_topk_pallas.1"
    ops = [Event("fusion.1", 10, 20), Event(kernel, 25, 15),
           Event(kernel, 60, 10), Event("copy", 95, 20)]
    modules = [Event("jit_three_pass_search(1)", 10, 30),
               Event("jit_three_pass_search(2)", 60, 10),
               Event("jit_other(3)", 95, 20)]
    spans = [Event("bench.window", 0, 100), Event("bench.search", 0, 45),
             Event("bench.result", 40, 25), Event("bench.next", 70, 30)]
    return Trace(ops={"/device:TPU:0": ops},
                 modules={"/device:TPU:0": modules}, spans=spans)


def test_union_and_gaps():
    t = _trace()
    evs = t.ops["/device:TPU:0"]
    assert tracing.union_ns(evs, 0, 100) == 20 + 10 + 10 + 5
    assert tracing.idle_gaps(evs, 0, 100) == [(0, 10), (40, 60), (70, 95)]
    assert tracing.union_ns([], 0, 100) == 0


def test_busy_window_and_breakdown():
    t = _trace()
    assert tracing.window_s(t) == pytest.approx(100e-9)
    assert tracing.busy_s(t) == pytest.approx(45e-9)
    b = tracing.breakdown(t)
    assert b["device_ops"][0] == ["%lut16_adc_topk_pallas.1",
                                  pytest.approx(25e-9)]
    # the longest gap (70, 95) falls in bench.next; (40, 60) mostly in
    # bench.result; (0, 10) in bench.search
    assert [g[0] for g in b["idle_gaps"]] == ["bench.next", "bench.result",
                                             "bench.search"]
    assert b["idle_gaps"][0][1] == pytest.approx(25e-9)


def test_time_per_call():
    t = _trace()
    assert tracing.calls(t) == 2
    assert tracing.time_per_call(t, tracing.PASS1_KERNEL) == \
        pytest.approx(25e-9 / 2)
    assert tracing.time_per_call(t, tracing.SEARCH_MODULE,
                                 level="modules") == pytest.approx(20e-9)
    assert tracing.time_per_call(t, "no_such_kernel") is None
    empty = Trace(ops={}, modules={}, spans=[])
    assert tracing.busy_s(empty) is None
    assert tracing.time_per_call(empty, tracing.PASS1_KERNEL) is None


def test_label_prefers_the_innermost_span():
    spans = [Event("bench.window", 0, 100), Event("bench.search", 0, 45),
             Event("bench.result", 10, 10)]
    assert tracing.label_gap((12, 18), spans) == "bench.result"
    assert tracing.label_gap((5, 40), spans) == "bench.search"
    assert tracing.label_gap((50, 60), spans) == "none"


def test_recorded_chip_trace():
    """A 1-second traced window of querysim-1m.bulk32 cut to 65,536 rows,
    recorded on one TPU v5e: two clients, 55 searches of 32 queries."""
    t = tracing.load(RECORDED)
    assert list(t.ops) == ["/device:TPU:0"]
    assert {s.name for s in t.spans} == {"bench.window", "bench.search",
                                         "bench.next"}
    assert tracing.calls(t) == 55
    assert tracing.window_s(t) == pytest.approx(1.027600902)
    assert tracing.busy_s(t) == pytest.approx(1.017324599)
    kernel = tracing.time_per_call(t, tracing.PASS1_KERNEL)
    search = tracing.time_per_call(t, tracing.SEARCH_MODULE,
                                   level="modules")
    assert kernel == pytest.approx(0.006635728781818183)
    assert search == pytest.approx(0.01849761681818182)
    b = tracing.breakdown(t)
    assert len(b["device_ops"]) == len(b["idle_gaps"]) == 10
    assert b["device_ops"][1] == ["%lut16_adc_topk_pallas.1",
                                  pytest.approx(55 * kernel)]
    assert b["idle_gaps"][0] == ["bench.search", pytest.approx(0.007342376)]
