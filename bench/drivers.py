"""Load generators: a closed loop of waiting clients and an open loop of
scheduled arrivals.  Both drive any callable, so tests run them against a
fake service; both wrap their host work in ``bench.*`` trace spans.

Closed loop: ``clients`` threads each take the next ``batch`` queries of the
pool, call (the call spans its own work), and wait for the reply; no batch
starts after the window's length has passed, and the window ends when the
last batch returns, so the rate is all the work over all the time.

Open loop: arrivals on a schedule fixed in advance, sent whether or not
earlier requests have finished.  Latency runs from the due time, so a stall
charges every request that waits behind it; how late the generator sent
each request is recorded apart.
"""

from __future__ import annotations

import dataclasses
import threading
import time

import numpy as np
from jax.profiler import TraceAnnotation

__all__ = ["ClosedLoopResult", "OpenLoopResult", "closed_loop", "open_loop",
           "poisson_schedule"]


@dataclasses.dataclass
class ClosedLoopResult:
    start: float
    stop: float
    done: list[np.ndarray]    # query rows of each batch that returned
    failed: list[np.ndarray]  # query rows of each batch that raised
    errors: list[BaseException] = dataclasses.field(default_factory=list)

    @property
    def seconds(self) -> float:
        return self.stop - self.start

    @property
    def completed(self) -> int:
        return int(sum(len(r) for r in self.done))

    @property
    def attempted(self) -> int:
        return self.completed + int(sum(len(r) for r in self.failed))


def closed_loop(call, pool: int, batch: int, clients: int, seconds: float,
                clock=time.perf_counter) -> ClosedLoopResult:
    """``call(rows)`` serves the pool rows ``rows``; batch ``i`` is rows
    ``i*batch .. i*batch+batch-1`` of the pool, taken modulo its size."""
    lock = threading.Lock()
    state = {"next": 0}
    done, failed = [], []
    errors = []
    start = clock()
    end = start + seconds

    def client():
        while True:
            with TraceAnnotation("bench.next"):
                if clock() >= end:
                    return
                with lock:
                    i = state["next"]
                    state["next"] += 1
                rows = np.arange(i * batch, (i + 1) * batch) % pool
            try:
                call(rows)
            except Exception as e:      # a failed request is counted
                with lock:
                    failed.append(rows)
                    errors.append(e)
                continue
            with lock:
                done.append(rows)

    threads = [threading.Thread(target=client, name=f"bench-client-{k}")
               for k in range(clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return ClosedLoopResult(start=start, stop=clock(), done=done,
                            failed=failed, errors=errors)


def poisson_schedule(rng: np.random.Generator, rate: float,
                     seconds: float) -> np.ndarray:
    """Arrival offsets of a Poisson process of ``rate`` over ``seconds``,
    conditioned on its expected count: ``round(rate * seconds)`` sorted
    uniform times.  Every seed then sends the same number of requests."""
    count = int(round(rate * seconds))
    return np.sort(rng.uniform(0.0, seconds, count))


@dataclasses.dataclass
class OpenLoopResult:
    start: float
    due: np.ndarray           # (M,) absolute due times
    sent: np.ndarray          # (M,) when the generator handed each over
    entered: np.ndarray       # (M,) when the server began it (NaN: never)
    finished: np.ndarray      # (M,) when its answer was ready (NaN: never)
    ok: np.ndarray            # (M,) bool: answered without an error

    @property
    def latency(self) -> np.ndarray:
        return self.finished - self.due

    @property
    def lateness(self) -> np.ndarray:
        return self.sent - self.due

    @property
    def queue_wait(self) -> np.ndarray:
        return self.entered - self.due


def open_loop(submit, offsets: np.ndarray, *, grace: float = 60.0,
              clock=time.perf_counter) -> OpenLoopResult:
    """Send request ``k`` at ``start + offsets[k]`` through
    ``submit(k, mark)``, which returns a ``concurrent.futures.Future``; the
    server side calls ``mark()`` when it begins the request.  Answers are
    awaited up to ``grace`` seconds past the last due time: one that comes
    later never came."""
    m = len(offsets)
    sent = np.full(m, np.nan)
    entered = np.full(m, np.nan)
    finished = np.full(m, np.nan)
    ok = np.zeros(m, bool)
    futures = []
    start = clock()
    due = start + np.asarray(offsets, float)

    def on_done(k, fut):
        with TraceAnnotation("bench.result"):
            finished[k] = clock()
            ok[k] = fut.exception() is None

    def marker(k):
        def mark():
            entered[k] = clock()
        return mark

    for k in range(m):
        with TraceAnnotation("bench.arrival_wait"):
            while True:
                wait = due[k] - clock()
                if wait <= 0:
                    break
                time.sleep(min(wait, 0.002) if wait > 0.0005 else 0)
        with TraceAnnotation("bench.submit"):
            sent[k] = clock()
            fut = submit(k, marker(k))
            fut.add_done_callback(lambda f, k=k: on_done(k, f))
            futures.append(fut)
    deadline = (due[-1] if m else start) + grace
    for fut in futures:
        try:
            fut.exception(timeout=max(deadline - clock(), 0.0))
        except TimeoutError:
            pass
    late = ~np.isfinite(finished) | (finished > deadline)
    ok &= ~late
    return OpenLoopResult(start=start, due=due, sent=sent, entered=entered,
                          finished=finished, ok=ok)
