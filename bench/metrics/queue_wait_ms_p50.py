"""Median, in milliseconds, of the wait from a request's due time to when a
QueryService worker begins its search (the benchmark's clock)."""

import numpy as np


def read(run):
    w = run.queue_wait_s
    if w is None:
        return None
    w = w[np.isfinite(w)]
    return float(np.percentile(w, 50)) * 1e3 if len(w) else None
