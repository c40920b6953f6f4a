"""Percent of the traced window in which no operation ran on the device."""

import tracing


def read(run):
    if run.trace is None:
        return None
    busy, window = tracing.busy_s(run.trace), tracing.window_s(run.trace)
    if busy is None or not window:
        return None
    return (1.0 - busy / window) * 100.0
