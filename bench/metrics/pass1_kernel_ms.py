"""Device milliseconds of the fused pass-1 LUT16 scan-and-select kernel per
search call, from the trace (kernels/lut16.py::lut16_adc_topk_pallas)."""

import tracing


def read(run):
    if run.trace is None:
        return None
    s = tracing.time_per_call(run.trace, tracing.PASS1_KERNEL)
    return None if s is None else s * 1e3
