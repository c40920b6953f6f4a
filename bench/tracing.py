"""Reduction of a profiler trace to the benchmark's device numbers.

A traced run records one ``.xplane.pb`` with the device's operations and the
benchmark's own host spans (``jax.profiler.TraceAnnotation`` names starting
``bench.``) on one clock.  ``load`` reads it with ``jax.profiler.ProfileData``
into plain event lists; everything else here works on those lists, so the
tests can hand it events made up by hand.

* busy time is the union of the intervals in which an operation ran on a
  device, inside the ``bench.window`` span; the idle share is 1 - busy /
  window, averaged over the devices;
* an idle gap is labelled with the host span that overlaps it most (the
  window span itself excluded), so that the gap says what the host was
  doing while the device waited;
* a kernel's or a program's time per call is its summed device time over
  the number of times the search program ran.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re

__all__ = ["Event", "Trace", "load", "find_xplane", "union_ns", "idle_gaps",
           "label_gap", "busy_s", "window_s", "breakdown", "time_per_call",
           "calls"]

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
# line names of a TPU plane in the profiler's trace
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SEARCH_MODULE = r"^jit_three_pass_search\b"
# the fused pass-1 LUT16 scan-and-select kernel: its custom call carries the
# name of the jitted wrapper, kernels/lut16.py::lut16_adc_topk_pallas
PASS1_KERNEL = r"^%?lut16_adc_topk_pallas\b"


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclasses.dataclass
class Trace:
    ops: dict[str, list[Event]]       # device plane -> its operations
    modules: dict[str, list[Event]]   # device plane -> its programs
    spans: list[Event]                # the benchmark's host spans

    @property
    def window(self) -> tuple[float, float] | None:
        w = [e for e in self.spans if e.name == WINDOW_SPAN]
        if not w:
            return None
        return min(e.start_ns for e in w), max(e.end_ns for e in w)


def find_xplane(log_dir: str) -> str | None:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    return paths[-1] if paths else None


def load(path: str) -> Trace:
    """Read device operations, device programs and ``bench.*`` host spans
    from an ``.xplane.pb``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    ops: dict[str, list[Event]] = {}
    modules: dict[str, list[Event]] = {}
    spans: list[Event] = []
    for plane in data.planes:
        device = plane.name.startswith("/device:") and \
            not plane.name.startswith("/device:CPU")
        for line in plane.lines:
            if device and line.name in (OPS_LINE, MODULES_LINE):
                out = ops if line.name == OPS_LINE else modules
                # an operation's event is named by its whole HLO line:
                # keep the op's own name, the text before " = "
                out.setdefault(plane.name, []).extend(
                    Event(e.name.split(" = ", 1)[0], float(e.start_ns),
                          float(e.duration_ns))
                    for e in line.events)
            elif not device:
                spans.extend(Event(e.name, float(e.start_ns),
                                   float(e.duration_ns))
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIX))
    return Trace(ops=ops, modules=modules, spans=spans)


def union_ns(events, lo: float, hi: float) -> float:
    """Length of the union of the events' intervals, clipped to [lo, hi]."""
    total, cur_lo, cur_hi = 0.0, None, None
    for e in sorted(events, key=lambda e: e.start_ns):
        a, b = max(e.start_ns, lo), min(e.end_ns, hi)
        if b <= a:
            continue
        if cur_hi is None or a > cur_hi:
            if cur_hi is not None:
                total += cur_hi - cur_lo
            cur_lo, cur_hi = a, b
        else:
            cur_hi = max(cur_hi, b)
    if cur_hi is not None:
        total += cur_hi - cur_lo
    return total


def idle_gaps(events, lo: float, hi: float) -> list[tuple[float, float]]:
    """The intervals of [lo, hi] in which no event runs."""
    gaps, t = [], lo
    for e in sorted(events, key=lambda e: e.start_ns):
        if e.end_ns <= t:
            continue
        if e.start_ns > t:
            gaps.append((t, min(e.start_ns, hi)))
        t = max(t, e.end_ns)
        if t >= hi:
            break
    if t < hi:
        gaps.append((t, hi))
    return [(a, b) for a, b in gaps if b > a]


def label_gap(gap: tuple[float, float], spans) -> str:
    """The name of the host span that overlaps ``gap`` most (of equals, the
    innermost, which started last), or "none"."""
    best, best_key = "none", (0.0, 0.0)
    for s in spans:
        if s.name == WINDOW_SPAN:
            continue
        key = (min(gap[1], s.end_ns) - max(gap[0], s.start_ns), s.start_ns)
        if key[0] > 0 and key > best_key:
            best, best_key = s.name, key
    return best


def window_s(trace: Trace) -> float | None:
    w = trace.window
    return None if w is None else (w[1] - w[0]) * 1e-9


def busy_s(trace: Trace) -> float | None:
    """Seconds in which an operation ran, averaged over the devices."""
    w = trace.window
    if w is None or not trace.ops:
        return None
    per = [union_ns(evs, *w) for evs in trace.ops.values()]
    return sum(per) / len(per) * 1e-9


def breakdown(trace: Trace, top: int = 10) -> dict | None:
    """The device operations that took most time, and the longest idle
    gaps labelled by what the host was doing, each as [name, seconds]."""
    w = trace.window
    if w is None or not trace.ops:
        return None
    totals: dict[str, float] = {}
    gaps = []
    for evs in trace.ops.values():
        inside = [e for e in evs if e.end_ns > w[0] and e.start_ns < w[1]]
        for e in inside:
            d = min(e.end_ns, w[1]) - max(e.start_ns, w[0])
            totals[e.name] = totals.get(e.name, 0.0) + d
        gaps += idle_gaps(inside, *w)
    ops = sorted(totals.items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
    return {"device_ops": [[n, t * 1e-9] for n, t in ops],
            "idle_gaps": [[label_gap(g, trace.spans), (g[1] - g[0]) * 1e-9]
                          for g in gaps]}


def calls(trace: Trace, module: str = SEARCH_MODULE) -> int:
    """Runs of the program matching ``module`` inside the window, counted
    on the device that ran most."""
    w = trace.window
    if w is None:
        return 0
    rx = re.compile(module)
    return max((sum(1 for e in evs if rx.search(e.name)
                    and w[0] <= e.start_ns < w[1])
                for evs in trace.modules.values()), default=0)


def time_per_call(trace: Trace, pattern: str, *, level: str = "ops",
                  module: str = SEARCH_MODULE) -> float | None:
    """Device seconds of the events matching ``pattern`` (operations, or
    programs with ``level="modules"``) per run of the search program, or
    None when the trace has no such event or no such run."""
    w = trace.window
    n = calls(trace, module)
    if w is None or n == 0:
        return None
    rx = re.compile(pattern)
    src = trace.ops if level == "ops" else trace.modules
    total = sum(min(e.end_ns, w[1]) - max(e.start_ns, w[0])
                for evs in src.values() for e in evs
                if rx.search(e.name) and e.end_ns > w[0] and e.start_ns < w[1])
    if total <= 0:
        return None
    return total / n * 1e-9
