"""The fused pass-1 kernel's share of its roofline, in percent: the least
time its work could take on this chip (roofline.pass1_work over the peaks
in peaks.json) over its device time per search call in the trace."""

import roofline
import tracing


def read(run):
    if run.trace is None:
        return None
    kernel_s = tracing.time_per_call(run.trace, tracing.PASS1_KERNEL)
    if kernel_s is None:
        return None
    idx, search = run.config["index"], run.config["search"]
    k = idx.get("pq_subspaces") or max(run.config["d_dense"] // 2, 1)
    c1 = min(max(search["alpha"] * search["h"], search["h"]), run.rows)
    ops, nbytes = roofline.pass1_work(run.batch, run.rows, k,
                                      idx.get("pq_codes", 16), c1)
    least = roofline.least_seconds(ops, nbytes,
                                   roofline.peaks(run.device_kind))
    return least / kernel_s * 100.0
