"""Profiling hooks (DESIGN.md §9.3): opt-in ``jax.profiler`` capture
around engine dispatch, plus per-pass device-time attribution that
feeds the roofline tables.
"""

from __future__ import annotations

import contextlib
import time

import jax.profiler

__all__ = ["device_trace", "StepAnnotation", "pass_breakdown"]


@contextlib.contextmanager
def device_trace(log_dir: str | None):
    """Context manager wrapping ``jax.profiler.trace(log_dir)`` around a
    region of engine dispatches.  A None ``log_dir`` (the default
    everywhere — profiling is opt-in) makes this a no-op, so benchmarks
    can wrap their hot loops unconditionally."""
    if log_dir is None:
        yield
        return
    with jax.profiler.trace(log_dir):
        yield


# names one engine dispatch inside a device trace so per-pass device time
# is attributable in the captured timeline
StepAnnotation = jax.profiler.StepTraceAnnotation


def pass_breakdown(engine, q_dims, q_vals, q_dense, *, h: int,
                   alpha: int, beta: int, iters: int = 3) -> dict:
    """Per-pass device-time attribution for one engine + query batch:
    times pass-1-only top-k (the scan the roofline models) against the
    full three-pass search, both with ``block_until_ready``, and reports
    the pass-1 fraction — the measured companion to the predicted
    bytes/point in ``src/repro/roofline/`` (DESIGN.md §9.3).

    Returns ``{"pass1_s", "full_s", "pass23_s", "pass1_fraction",
    "iters", "backend"}`` (best-of-``iters`` wall seconds)."""
    import jax
    from ..core.pq import adc_lut

    c1, _ = engine.candidate_counts(h, alpha, beta)
    lut = adc_lut(q_dense, engine.arrays.codebooks)

    def _time(fn):
        best = None
        for _ in range(max(1, iters)):
            t0 = time.perf_counter()
            out = fn()
            jax.block_until_ready(out)
            dt = time.perf_counter() - t0
            best = dt if best is None or dt < best else best
        return best

    # warm both compiles outside the timed loop
    jax.block_until_ready(engine.pass1_topk(q_dims, q_vals, lut, c1))
    jax.block_until_ready(engine.search(q_dims, q_vals, q_dense,
                                        h=h, alpha=alpha, beta=beta))
    pass1_s = _time(lambda: engine.pass1_topk(q_dims, q_vals, lut, c1))
    full_s = _time(lambda: engine.search(q_dims, q_vals, q_dense,
                                         h=h, alpha=alpha, beta=beta))
    pass23_s = max(0.0, full_s - pass1_s)
    # wall-clock jitter can time pass-1 alone above the fused full pass;
    # an attribution fraction is [0, 1] by definition, so clamp
    frac = min(1.0, pass1_s / full_s) if full_s > 0 else 0.0
    return {"pass1_s": pass1_s, "full_s": full_s, "pass23_s": pass23_s,
            "pass1_fraction": frac,
            "iters": iters, "backend": str(engine.backend)}
