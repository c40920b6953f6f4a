import os
os.environ["JAX_PLATFORMS"] = "cpu"        # a host compile probe, never a chip
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")

# Perf-iteration probe: measure one cell's roofline terms quickly (unrolled
# 1x/2x-pattern extrapolation, no full-depth compile) under config/rule
# overrides, and append the result to results/perf_log.jsonl.
#
#   PYTHONPATH=src python -m repro.launch.perf_probe --arch qwen3-moe-235b-a22b \
#       --shape train_4k --set attn_chunk=1024 --note "bigger attn chunk"
#
# This is the §Perf inner loop: hypothesis -> --set change -> measure -> log.

import argparse
import dataclasses as dc
import json
import time

from repro.configs import SHAPES, get_config
from repro.launch.dryrun import OPT_CFG, _cost_tuple, build_lowered
from repro.launch.mesh import make_production_mesh
from repro.models import Model
from repro.roofline import V5E, model_flops


def parse_overrides(pairs):
    out = {}
    for p in pairs or []:
        k, v = p.split("=", 1)
        for cast in (int, float):
            try:
                v = cast(v)
                break
            except ValueError:
                continue
        if v == "true":
            v = True
        if v == "false":
            v = False
        out[k] = v
    return out


def probe(arch: str, shape_name: str, overrides: dict | None = None,
          rules: dict | None = None, verbose: bool = True) -> dict:
    cfg = get_config(arch)
    if overrides:
        cfg = dc.replace(cfg, **overrides)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh()
    plen = len(Model(cfg).pattern)

    t0 = time.time()
    c1 = _cost_tuple(build_lowered(
        dc.replace(cfg, num_layers=plen, unroll=True), shape, mesh,
        microbatches=1, opt_cfg=OPT_CFG).compile())
    c2 = _cost_tuple(build_lowered(
        dc.replace(cfg, num_layers=2 * plen, unroll=True), shape, mesh,
        microbatches=1, opt_cfg=OPT_CFG).compile())
    reps = cfg.num_layers / plen
    flops, bytes_, coll = (a + (reps - 1.0) * max(b - a, 0.0)
                           for a, b in zip(c1, c2))
    terms = {
        "arch": arch, "shape": shape_name,
        "overrides": overrides or {},
        "compute_s": flops / V5E["peak_flops"],
        "memory_s": bytes_ / V5E["hbm_bw"],
        "collective_s": coll / V5E["ici_bw"],
        "model_flops": model_flops(cfg, shape),
        "hlo_flops_job": flops * mesh.size,
        "probe_s": time.time() - t0,
    }
    terms["dominant"] = max(
        ("compute", "memory", "collective"),
        key=lambda k: terms[f"{k}_s"])
    terms["useful_ratio"] = (terms["model_flops"] / terms["hlo_flops_job"]
                             if terms["hlo_flops_job"] else 0.0)
    if verbose:
        print(f"{arch} × {shape_name} {overrides or ''}: "
              f"compute {terms['compute_s']*1e3:.2f}ms "
              f"memory {terms['memory_s']*1e3:.2f}ms "
              f"collective {terms['collective_s']*1e3:.2f}ms "
              f"dominant={terms['dominant']} "
              f"useful={terms['useful_ratio']:.3f} "
              f"[probe {terms['probe_s']:.0f}s]")
    return terms


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--set", nargs="*", default=None,
                    help="config overrides k=v (e.g. attn_chunk=1024)")
    ap.add_argument("--note", default="")
    ap.add_argument("--log", default="results/perf_log.jsonl")
    args = ap.parse_args()
    terms = probe(args.arch, args.shape, parse_overrides(args.set))
    terms["note"] = args.note
    os.makedirs(os.path.dirname(args.log) or ".", exist_ok=True)
    with open(args.log, "a") as f:
        f.write(json.dumps(terms) + "\n")


if __name__ == "__main__":
    main()
