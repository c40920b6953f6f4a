#!/usr/bin/env python3
"""Readings that set a cell's limits: the program over many seeds, the
control, and the faults planted in the reference's place, which must fail.

    python3 bench/control.py --workload <cell> --seconds <s> --seeds 1,2,3

For each seed, in one process: the cell's set-up and a window through the
timed path, as ``run.py`` makes them; then, with the program's state freed,
the numbers ``run.py`` compares for several sets of answers to the same
sampled queries:

* ``program``: what the timed path served (the lower reading);
* ``control``: the plain reference put in the program's place, with its
  dense side in int4, the precision below the int8 the configuration
  states for the dense side (the upper reading);
* ``bf16``: the reference with every input in bfloat16, accumulated in
  float32: a reading only, of the step below float32 accumulation;
* faults, each in the reference's place, the later passes re-scoring the
  rows they get exactly: ``altered``, one answer's ids moved to the next
  row, its scores kept; ``rows_half``, pass 1 scans only the first half of
  the rows; ``bias_dropped``, pass 1 ranks by the dense half alone and
  keeps its ``alpha * h`` best; ``wrong_query``, each query gets the
  ``alpha * h`` candidates of the next sampled query.

One JSON line per seed and answer set goes to standard output, and the last
line sums them up.  The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import reference                                         # noqa: E402
import run                                               # noqa: E402


def altered(served_s: np.ndarray, served_i: np.ndarray, n: int):
    """One answer's ids moved to the next row, its scores kept."""
    s, i = served_s.copy(), served_i.copy()
    i[0] = (i[0] + 1) % n
    return s, i


def rescored(st: run.Setup, sample: np.ndarray, cand: np.ndarray,
             chunk: int = 64):
    """The best ``h`` of each query's candidate rows ``cand`` (S, c) by
    their exact scores: what passes 2 and 3 would make of them, were they
    exact."""
    out_s, out_i = [], []
    for lo in range(0, len(sample), chunk):
        rows = sample[lo:lo + chunk]
        c = cand[lo:lo + chunk]
        s = reference.pair_scores(st.corpus.x_sparse, st.corpus.x_dense,
                                  st.queries.q_sparse[rows],
                                  st.queries.q_dense[rows], c)
        at = np.argsort(-s, axis=1, kind="stable")[:, :st.h]
        out_s.append(np.take_along_axis(s, at, 1).astype(np.float32))
        out_i.append(np.take_along_axis(c, at, 1))
    return np.concatenate(out_s), np.concatenate(out_i)


def answer_sets(st: run.Setup, win: run.Window, sample: np.ndarray):
    """(the exact top-h, {name: (scores, ids)}) for the sampled queries."""
    cfg = st.cell.config
    n = int(cfg["num_rows"])
    c1 = min(int(cfg["search"]["alpha"]) * st.h, n)
    corpus = reference.DeviceCorpus(st.corpus.x_sparse, st.corpus.x_dense)
    qs, qd = st.queries.q_sparse[sample], st.queries.q_dense[sample]
    top_s, top_i = corpus.topk(qs, qd, c1)
    exact = (top_s[:, :st.h], top_i[:, :st.h])
    served = (win.served_s[sample], win.served_i[sample])
    sets = {
        "program": served,
        "control": corpus.topk(qs, qd, st.h, dense_bits=4),
        "bf16": corpus.topk(qs, qd, st.h, bf16=True),
        "altered": altered(*served, n),
        "rows_half": corpus.topk(qs, qd, st.h, rows=n // 2),
        "bias_dropped": rescored(st, sample,
                                 corpus.topk(qs, qd, c1, sparse=False)[1]),
        "wrong_query": rescored(st, sample, np.roll(top_i, -1, axis=0)),
    }
    return exact, sets


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds, one set-up each")
    args = ap.parse_args(argv)

    cell = run.load_cell(run.ROOT, args.workload)
    devices = run.claim_devices(cell.chips)
    sys.path.insert(0, os.path.join(run.ROOT, "src"))
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    readings: dict[str, list[dict]] = {}
    for seed in (int(x) for x in args.seeds.split(",")):
        st = run.set_up(cell, seed, args.seconds)
        win = run.drive(st, args.seconds)
        run.release(st, devices)
        sample = run.draw_sample(st, win.done)
        exact, sets = answer_sets(st, win, sample)
        for name, (s, i) in sets.items():
            nums = run.judge(st, sample, s, i, exact, win.failed)
            ok, _ = run.checks_of(cell.config, nums)
            nums.update(seed=seed, answers=name, correct=ok,
                        sample=int(len(sample)))
            readings.setdefault(name, []).append(nums)
            print(json.dumps(nums), flush=True)
        del st, win, sets
    summary = {"workload": cell.name, "seeds": len(readings["program"])}
    for name, rows in readings.items():
        for key in ("score_err_rms", "score_err_max", "recall_miss"):
            vals = [r[key] for r in rows]
            summary[f"{name}.{key}"] = [min(vals), max(vals)]
        summary[f"{name}.correct"] = sum(r["correct"] for r in rows)
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
