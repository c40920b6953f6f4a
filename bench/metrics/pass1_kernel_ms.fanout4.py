"""Device milliseconds of the fused pass-1 LUT16 scan-and-select kernel
(kernels/lut16.py::lut16_adc_topk_pallas) per search run on the slowest
chip of a row-sharded cell, each chip's time counted over that chip's own
runs (``shards.ms_per_search``)."""

import shards
import tracing


def read(run):
    if run.trace is None:
        return None
    per_chip = shards.ms_per_search(run.trace, tracing.PASS1_KERNEL,
                                    level="ops")
    return max(per_chip.values()) if per_chip else None
