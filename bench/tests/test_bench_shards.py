"""The per-chip readers of a row-sharded cell, on hand-made events:
``shard_search_ms`` (the slowest chip's device time per search run),
``shard_skew`` (that time over the chips' mean) and
``pass1_kernel_ms.fanout4`` (the slowest chip's pass-1 kernel time per
search run)."""

from __future__ import annotations

import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(REPO, "bench"))

import run                                            # noqa: E402
import shards                                         # noqa: E402
import tracing                                        # noqa: E402
from tracing import Event, Trace                      # noqa: E402

MS = 1e6      # ns


def _search(i: int, start_ms: float, dur_ms: float) -> Event:
    return Event(f"jit_three_pass_search({i})", start_ms * MS, dur_ms * MS)


def _run(trace):
    return run.RunData(trace=trace, config={}, traffic={}, device_kind="",
                       rows=0, batch=32, build_s=1.0, queue_wait_s=None)


def _trace(chip0_extra: int = 0) -> Trace:
    """Window [0, 1000) ms on two chips.  Chip 0 runs the search program
    4 times at 40 ms, chip 1 4 times at 50 ms (one starts before the window
    and is not counted, one runs past its end and is counted up to it).
    ``chip0_extra`` more 2 ms runs on chip 0 stand for the delta shard's
    engine, which lives there."""
    chip0 = [_search(i, 100 + 200 * i, 40) for i in range(4)]
    chip0 += [_search(10 + i, 900 + 10 * i, 2) for i in range(chip0_extra)]
    chip1 = [_search(20, -30, 50)]
    chip1 += [_search(21 + i, 100 + 200 * i, 50) for i in range(3)]
    chip1 += [_search(30, 960, 50), Event("jit_other(9)", 500, 70)]
    return Trace(ops={}, modules={"/device:TPU:0": chip0,
                                  "/device:TPU:1": chip1},
                 spans=[Event("bench.window", 0, 1000 * MS)])


def test_search_ms_per_chip_counts_each_chip_over_its_own_runs():
    per_chip = shards.ms_per_search(_trace())
    assert per_chip["/device:TPU:0"] == pytest.approx(40.0)
    # 3 runs of 50 ms and one cut to 40 ms by the window's end
    assert per_chip["/device:TPU:1"] == pytest.approx((3 * 50 + 40) / 4)


def test_slowest_chip_and_skew():
    read_ms = run.load_reader(REPO, "shard_search_ms.fanout4")
    read_skew = run.load_reader(REPO, "shard_skew.fanout4")
    slow = (3 * 50 + 40) / 4
    assert read_ms(_run(_trace())) == pytest.approx(slow)
    assert read_skew(_run(_trace())) == pytest.approx(slow / ((40 + slow) / 2))


def test_extra_runs_on_chip_0_leave_the_other_chips_count():
    """The delta engine's short runs on chip 0 lower chip 0's mean and
    nothing else: chip 1 is still counted over its own 4 runs, so the
    slowest chip's time does not move."""
    per_chip = shards.ms_per_search(_trace(chip0_extra=6))
    assert per_chip["/device:TPU:1"] == pytest.approx((3 * 50 + 40) / 4)
    assert per_chip["/device:TPU:0"] == pytest.approx((4 * 40 + 6 * 2) / 10)
    read_ms = run.load_reader(REPO, "shard_search_ms.fanout4")
    assert read_ms(_run(_trace(chip0_extra=6))) == \
        pytest.approx((3 * 50 + 40) / 4)


def _kernel(start_ms: float, dur_ms: float) -> Event:
    return Event("lut16_adc_topk_pallas.3", start_ms * MS, dur_ms * MS)


def test_pass1_kernel_ms_reads_the_slowest_chip_over_its_own_runs():
    """Each search run holds one kernel call: 8 ms on chip 0, 10 ms on chip
    1.  The delta engine's extra runs on chip 0, each with a 1 ms kernel
    call, lower chip 0's time and leave chip 1's."""
    trace = _trace(chip0_extra=6)
    trace.ops["/device:TPU:0"] = (
        [_kernel(100 + 200 * i, 8) for i in range(4)]
        + [_kernel(900 + 10 * i, 1) for i in range(6)])
    # chip 1's kernel of the run begun before the window is not counted
    trace.ops["/device:TPU:1"] = (
        [_kernel(-30, 10)] + [_kernel(100 + 200 * i, 10) for i in range(3)]
        + [_kernel(960, 10), Event("fusion.7", 500 * MS, 30 * MS)])
    per_chip = shards.ms_per_search(trace, tracing.PASS1_KERNEL, level="ops")
    assert per_chip["/device:TPU:0"] == pytest.approx((4 * 8 + 6) / 10)
    assert per_chip["/device:TPU:1"] == pytest.approx(10.0)
    read = run.load_reader(REPO, "pass1_kernel_ms.fanout4")
    assert read(_run(trace)) == pytest.approx(10.0)


def test_nothing_to_read():
    empty = Trace(ops={}, modules={}, spans=[])
    for name in ("shard_search_ms.fanout4", "shard_skew.fanout4",
                 "pass1_kernel_ms.fanout4"):
        read = run.load_reader(REPO, name)
        assert read(_run(None)) is None
        assert read(_run(empty)) is None
        no_runs = Trace(ops={}, modules={"/device:TPU:0": [
            Event("jit_other(1)", 0, 5)]},
            spans=[Event("bench.window", 0, 1000)])
        assert read(_run(no_runs)) is None
