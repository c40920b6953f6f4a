"""Per-chip device times, for cells whose index is row-sharded over the
chips: each chip runs its own shard's search, and the slowest chip sets
each request's pace.

Each chip's time per search is its own summed device time over its own
runs of the search program in the window, so extra runs on one chip (the
delta shard's engine lives on chip 0) do not change another chip's count.
"""

from __future__ import annotations

import re

import tracing


def ms_per_search(trace: tracing.Trace, pattern: str = tracing.SEARCH_MODULE,
                  *, level: str = "modules") -> dict[str, float]:
    """Device plane -> device ms of the events matching ``pattern``
    (programs, or operations with ``level="ops"``) per run of the search
    program on that chip, for each chip that ran both in the window.
    Events and runs count when they start inside the window; an event's
    time is cut at the window's end."""
    w = trace.window
    if w is None:
        return {}
    rx, search = re.compile(pattern), re.compile(tracing.SEARCH_MODULE)
    src = trace.modules if level == "modules" else trace.ops
    out = {}
    for plane, programs in trace.modules.items():
        runs = sum(1 for e in programs
                   if search.search(e.name) and w[0] <= e.start_ns < w[1])
        total = sum(min(e.end_ns, w[1]) - e.start_ns
                    for e in src.get(plane, ())
                    if rx.search(e.name) and w[0] <= e.start_ns < w[1])
        if runs and total > 0:
            out[plane] = total / runs * 1e-6
    return out
