#!/usr/bin/env python3
"""Find the highest rate an open-loop cell sustains, with one set-up.

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds <s> \
        --rates 30,40,50,60

Builds the cell once (``run.set_up``) and drives one open-loop window per
rate through the same ``QueryService``, each with its own queries.  A rate
is sustained when the answers keep up with the arrivals: the completion
rate matches the offered one and the latency of the window's last quarter
is no worse than that of its first.  Prints one JSON line per rate.  The
benchmark's own runs never run this; the cell's traffic file takes the rate
it picks.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import datagen                                           # noqa: E402
import run                                               # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    rates = [float(r) for r in args.rates.split(",")]

    cell = run.load_cell(run.ROOT, args.workload)
    run.claim_devices(cell.chips)
    sys.path.insert(0, os.path.join(run.ROOT, "src"))
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    # one set-up whose pool holds every rate's requests, back to back
    total = sum(int(round(r * args.seconds)) for r in rates)
    cell.traffic = dict(cell.traffic, rate=total / args.seconds)
    st = run.set_up(cell, args.seed, args.seconds)
    rng = np.random.default_rng(datagen.sub_seed(args.seed, 6))
    first = 0
    for rate in rates:
        offsets = run.arrival_schedule(cell.traffic)(rng, rate, args.seconds)
        view = run.Setup(**{**st.__dict__,
                            "cell": dataclasses.replace(
                                cell, traffic=dict(cell.traffic, rate=rate)),
                            "q_dims": st.q_dims[first:],
                            "q_vals": st.q_vals[first:],
                            "queries": datagen.QueryPool(
                                st.queries.q_sparse[first:],
                                st.queries.q_dense[first:],
                                st.queries.src[first:]),
                            "pool": len(offsets), "offsets": offsets})
        win = run.drive(view, args.seconds)
        first += len(offsets)
        res = win.result
        lat = np.where(res.ok, res.latency, np.inf) * 1e3
        q = max(len(lat) // 4, 1)
        done_rate = res.ok.sum() / (np.nanmax(res.finished) - res.start)
        print(json.dumps({
            "rate": rate, "requests": len(lat), "failed": win.failed,
            "completed_per_s": float(done_rate),
            "p50_ms": float(np.percentile(lat, 50)),
            "p95_ms": float(np.percentile(lat, 95)),
            "p95_first_quarter_ms": float(np.percentile(lat[:q], 95)),
            "p95_last_quarter_ms": float(np.percentile(lat[-q:], 95)),
            "queue_wait_p95_ms": float(np.nanpercentile(
                res.queue_wait, 95) * 1e3)}), flush=True)
    st.svc.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
