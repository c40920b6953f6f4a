"""Device milliseconds of one run of the jitted three-pass search program
(core/engine.py::three_pass_search), averaged over the window's runs."""

import tracing


def read(run):
    if run.trace is None:
        return None
    s = tracing.time_per_call(run.trace, tracing.SEARCH_MODULE,
                              level="modules")
    return None if s is None else s * 1e3
