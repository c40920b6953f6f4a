"""The load generators against a fake service with a fixed service time.

The open loop must time each request from its due time, so a request that
waits behind others carries the wait the queue implies, and must report how
late it sent each request; the closed loop must count all the work and all
the time of its window.
"""

from __future__ import annotations

import os
import sys
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(REPO, "bench"))

import drivers                                        # noqa: E402

SERVICE = 0.05          # seconds per request, one worker


def _fake_submit(pool):
    def serve(mark):
        mark()
        time.sleep(SERVICE)
        return "answer"

    def submit(k, mark):
        return pool.submit(serve, mark)
    return submit


def test_open_loop_latency_is_the_queue_from_due_time():
    # arrivals every 20 ms against 50 ms of service: each request waits
    # for all before it, so request k finishes 50 (k + 1) ms after the start
    offsets = np.arange(8) * 0.02
    with ThreadPoolExecutor(max_workers=1) as pool:
        res = drivers.open_loop(_fake_submit(pool), offsets)
    assert res.ok.all()
    lat = res.latency
    implied = SERVICE * (np.arange(8) + 1) - offsets
    # each sleep may overrun; the overruns add up along the queue
    assert np.all(lat >= implied - 1e-3)
    assert np.all(lat <= implied + 0.01 * (np.arange(8) + 1) + 0.02)
    # a request is begun when the one before it ends, not when it is due
    assert np.all(res.queue_wait >= implied - SERVICE - 1e-3)
    assert np.all(res.latency - res.queue_wait >= SERVICE - 1e-3)
    assert np.all(np.diff(res.finished) >= SERVICE - 1e-3)
    assert np.all(res.lateness >= 0) and np.all(res.lateness < 0.05)


def test_open_loop_counts_an_answer_that_never_comes():
    def submit(k, mark):
        from concurrent.futures import Future
        fut = Future()
        if k % 2 == 0:
            fut.set_result("answer")
        return fut

    res = drivers.open_loop(submit, np.zeros(4), grace=0.2)
    assert res.ok.tolist() == [True, False, True, False]
    assert np.isnan(res.latency[1]) and np.isfinite(res.latency[0])


def test_poisson_schedule_is_fixed_by_the_seed():
    a = drivers.poisson_schedule(np.random.default_rng(9), 50.0, 20.0)
    b = drivers.poisson_schedule(np.random.default_rng(9), 50.0, 20.0)
    c = drivers.poisson_schedule(np.random.default_rng(10), 50.0, 20.0)
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    assert len(a) == len(c) == 1000
    assert np.all(np.diff(a) >= 0) and a[0] >= 0 and a[-1] < 20.0
    gaps = np.diff(a)
    # exponential gaps: mean 1/rate and coefficient of variation about 1
    assert abs(gaps.mean() - 0.02) < 0.003
    assert abs(gaps.std() / gaps.mean() - 1.0) < 0.15


def test_closed_loop_counts_all_work_and_time():
    lock = threading.Lock()
    seen = []

    def call(rows):
        time.sleep(0.01)
        with lock:
            seen.append(rows)

    res = drivers.closed_loop(call, pool=100, batch=8, clients=2,
                              seconds=0.3)
    assert res.completed == 8 * len(seen) == res.attempted
    # no batch starts after the window's length; the last ones finish it
    assert 0.3 <= res.seconds < 0.3 + 0.2
    firsts = {int(r[0]) for r in seen}
    assert {0, 8, 16} <= firsts
    assert all(((r - r[0]) % 100 == range(8)).all() for r in seen)


def test_closed_loop_counts_failures():
    def call(rows):
        raise RuntimeError("down")

    res = drivers.closed_loop(call, pool=16, batch=4, clients=1,
                              seconds=0.05)
    assert res.completed == 0 and res.attempted > 0
    assert isinstance(res.errors[0], RuntimeError)
