import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# ^ MUST precede any jax-importing import: jax locks the device count at
# first init, and the production meshes below need 512 host placeholders.
# The dry-run is a host compile proof, so it pins itself to the CPU backend
# and never takes a chip.  (Only the dry-run does this — tests and benches
# see 1 device.)

# Multi-pod dry-run: lower + compile every (architecture × input shape)
# cell on the single-pod (16×16) and multi-pod (2×16×16) production meshes,
# print memory_analysis / cost_analysis, and emit the roofline table rows.
#
#   PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-7b --shape train_4k
#   PYTHONPATH=src python -m repro.launch.dryrun --all --multi-pod both \
#       --out results/dryrun.json
#
# Failures here (sharding mismatch, OOM at compile, unsupported collective)
# are bugs in the system, not in the harness.

import argparse
import json
import time
import traceback
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCH_IDS, SHAPES, get_config
from repro.data.pipeline import input_specs_for_shape
from repro.launch.mesh import make_production_mesh
from repro.models import Model
from repro.models.common import sharding_rules
from repro.models.shardings import (batch_pspecs, param_pspecs, state_pspecs,
                                    tree_pspecs)
from repro.optim import AdamWConfig, adamw_init
from repro.roofline import model_flops, roofline_from_compiled
from repro.train import make_train_step

OPT_CFG = AdamWConfig()


def skip_reason(cfg, shape) -> str | None:
    if shape.name == "long_500k" and not cfg.sub_quadratic:
        return ("skipped: pure full-attention arch — 524288-token dense KV "
                "cache requires sub-quadratic attention (DESIGN.md "
                "§Arch-applicability)")
    return None


def input_specs(arch: str, shape_name: str = "train_4k") -> dict:
    """ShapeDtypeStruct stand-ins for every model input of the cell."""
    cfg = get_config(arch)
    return input_specs_for_shape(cfg, SHAPES[shape_name])


def _shard(tree, spec_tree, mesh):
    return jax.tree.map(
        lambda s, spec: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=NamedSharding(mesh, spec)),
        tree, spec_tree)


def build_lowered(cfg, shape, mesh, *, microbatches: int = 1,
                  opt_cfg: AdamWConfig | None = None):
    """Lower the cell's step (train_step / prefill / serve_step) with full
    sharding annotations.  Returns the jax.stages.Lowered."""
    opt_cfg = opt_cfg or OPT_CFG
    model = Model(cfg)
    params_sds = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    pspecs = param_pspecs(params_sds, mesh)
    params_in = _shard(params_sds, pspecs, mesh)

    with sharding_rules(mesh):
        if shape.kind == "train":
            batch_sds = input_specs_for_shape(cfg, shape)
            batch_in = _shard(batch_sds, batch_pspecs(batch_sds, mesh), mesh)
            opt_sds = jax.eval_shape(partial(adamw_init, cfg=opt_cfg),
                                     params_sds)
            ospecs = tree_pspecs(opt_sds, mesh, params_sds)
            opt_in = _shard(opt_sds, ospecs, mesh)
            step = make_train_step(model, opt_cfg, microbatches=microbatches,
                                   cast_params_bf16=cfg.params_bf16_cast)
            return jax.jit(step, donate_argnums=(0, 1)).lower(
                params_in, opt_in, batch_in)
        if shape.kind == "prefill":
            batch_sds = input_specs_for_shape(cfg, shape)
            batch_in = _shard(batch_sds, batch_pspecs(batch_sds, mesh), mesh)

            def prefill_step(params, batch):
                return model.prefill(params, batch, shape.seq_len)

            return jax.jit(prefill_step).lower(params_in, batch_in)
        # decode
        b = shape.global_batch
        cond_sds = None
        if cfg.num_cond_tokens:
            cond_sds = jax.ShapeDtypeStruct(
                (b, cfg.num_cond_tokens, cfg.d_model), jnp.bfloat16)
        state_sds = jax.eval_shape(
            partial(model.init_decode_state, batch_size=b,
                    max_len=shape.seq_len),
            params_sds, cond=cond_sds)
        state_in = _shard(state_sds, state_pspecs(state_sds, mesh), mesh)
        tok_sds = input_specs_for_shape(cfg, shape)["token"]
        tok_specs = batch_pspecs({"token": tok_sds}, mesh)["token"]
        tok_in = _shard(tok_sds, tok_specs, mesh)

        def serve_step(params, state, token):
            return model.decode_step(params, state, token)

        return jax.jit(serve_step, donate_argnums=(1,)).lower(
            params_in, state_in, tok_in)


HBM_BYTES = 16 * 1024 ** 3          # v5e
HBM_FIT = int(15.5 * 1024 ** 3)     # leave headroom for runtime buffers


def _mem_per_device(compiled) -> int:
    mem = compiled.memory_analysis()
    return int(getattr(mem, "temp_size_in_bytes", 0)
               + getattr(mem, "argument_size_in_bytes", 0)
               + getattr(mem, "output_size_in_bytes", 0)
               - getattr(mem, "alias_size_in_bytes", 0))


def _cost_tuple(compiled):
    cost = compiled.cost_analysis()
    if isinstance(cost, list):
        cost = cost[0]
    from repro.roofline import collective_bytes_from_hlo
    coll = collective_bytes_from_hlo(compiled.as_text())
    return (float(cost.get("flops", 0.0)),
            float(cost.get("bytes accessed", 0.0)),
            float(coll["total"]))


def lower_cell(arch: str, shape_name: str, *, multi_pod: bool,
               verbose: bool = True, probes: bool = True,
               fit_hint: dict | None = None) -> dict:
    """Full-config compile (memory proof) + two reduced-depth compiles for
    the scan-body extrapolation (XLA cost_analysis counts a while body once
    regardless of trip count — measured; see EXPERIMENTS.md §Roofline
    methodology), + auto-microbatch fit for training cells.

    probes=False skips the roofline extrapolation (multi-pod pass only needs
    the compile/memory proof).  fit_hint seeds (microbatches, opt_moments)
    from a previous sweep to avoid re-searching."""
    import dataclasses as dc
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    mesh_name = "2x16x16" if multi_pod else "16x16"
    reason = skip_reason(cfg, shape)
    if reason:
        return {"arch": arch, "shape": shape_name, "mesh": mesh_name,
                "status": "skip", "reason": reason}

    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.size
    plen = len(Model(cfg).pattern)

    # ---- full-config compile: the memory/sharding proof -------------------
    # auto-fit: escalate microbatches (keeping per-ub batch >= data shards so
    # DP stays intact); if the fp32 optimizer alone exceeds HBM, fall back to
    # bf16 moments (sharding-transparent compression).
    data_shards = mesh.shape.get("data", 1) * mesh.shape.get("pod", 1)
    max_mb = max(shape.global_batch // data_shards, 1)
    t0 = time.time()
    microbatches, opt_cfg = 1, OPT_CFG
    if fit_hint:
        microbatches = min(int(fit_hint.get("microbatches", 1)), max_mb)
        if fit_hint.get("opt_moments") == "bfloat16":
            opt_cfg = AdamWConfig(moment_dtype="bfloat16")
    seen = {}
    while True:
        lowered = build_lowered(cfg, shape, mesh, microbatches=microbatches,
                                opt_cfg=opt_cfg)
        compiled = lowered.compile()
        mem_dev = _mem_per_device(compiled)
        seen[microbatches] = mem_dev
        if shape.kind != "train" or mem_dev <= HBM_FIT:
            break
        if microbatches < max_mb:
            if len(seen) >= 2:
                # temp(mb) ~ fixed + act/mb: solve from two samples and jump
                mbs = sorted(seen)[-2:]
                m1, m2 = seen[mbs[0]], seen[mbs[1]]
                act = (m1 - m2) / (1.0 / mbs[0] - 1.0 / mbs[1]) \
                    if mbs[0] != mbs[1] else 0.0
                fixed = m1 - act / mbs[0]
                target = microbatches * 2
                while (fixed + act / target > HBM_FIT
                       and target < max_mb):
                    target *= 2
                microbatches = min(target, max_mb)
            else:
                microbatches = min(microbatches * 2, max_mb)
            if verbose:
                print(f"  {mem_dev/2**30:.1f} GiB > fit; retry "
                      f"microbatches={microbatches}")
            continue
        if opt_cfg.moment_dtype == "float32":
            opt_cfg = AdamWConfig(moment_dtype="bfloat16")
            if verbose:
                print(f"  {mem_dev/2**30:.1f} GiB > fit at max microbatches; "
                      f"retry with bf16 optimizer moments")
            continue
        break                           # report honestly as not fitting
    t_compile = time.time() - t0
    mem = compiled.memory_analysis()

    # ---- reduced-depth UNROLLED compiles: per-layer extrapolation ----------
    # (XLA cost_analysis counts a lax.scan body once regardless of trip
    # count, so depth information must come from unrolled probes: cost at
    # 1×pattern and 2×pattern unrolled gives the per-repeat delta.)
    if not probes:
        mem_dev = _mem_per_device(compiled)
        row = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
               "status": "ok", "compile_s": t_compile,
               "microbatches": microbatches,
               "opt_moments": opt_cfg.moment_dtype,
               "bytes_per_device": float(mem_dev),
               "fits_hbm": bool(mem_dev <= HBM_BYTES),
               "mem_temp": int(getattr(mem, "temp_size_in_bytes", 0)),
               "mem_argument": int(getattr(mem, "argument_size_in_bytes", 0))}
        if verbose:
            print(f"--- {arch} × {shape_name} × {mesh_name} ---")
            print(f"compile {t_compile:.1f}s microbatches={microbatches} "
                  f"bytes/dev={mem_dev/2**30:.2f}GiB fits={row['fits_hbm']}")
        return row

    # probes run microbatches=1: a microbatch lax.scan would re-hide the
    # layer costs inside a while body; total math FLOPs are identical.
    cfg1 = dc.replace(cfg, num_layers=plen, unroll=True)
    cfg2 = dc.replace(cfg, num_layers=2 * plen, unroll=True)
    c1 = _cost_tuple(build_lowered(cfg1, shape, mesh, microbatches=1,
                                   opt_cfg=opt_cfg).compile())
    c2 = _cost_tuple(build_lowered(cfg2, shape, mesh, microbatches=1,
                                   opt_cfg=opt_cfg).compile())
    reps_total = cfg.num_layers / plen          # fractional incl. remainder
    flops_dev, bytes_dev, coll_dev = (
        base + (reps_total - 1.0) * max(two - base, 0.0)
        for base, two in zip(c1, c2))

    from repro.roofline import V5E, RooflineTerms
    mf = model_flops(cfg, shape)
    terms = RooflineTerms(
        arch=arch, shape=shape_name, mesh=mesh_name, chips=chips,
        hlo_flops=flops_dev * chips, hlo_bytes=bytes_dev * chips,
        collective_bytes=coll_dev * chips,
        compute_s=flops_dev / V5E["peak_flops"],
        memory_s=bytes_dev / V5E["hbm_bw"],
        collective_s=coll_dev / V5E["ici_bw"],
        model_flops=mf, bytes_per_device=float(_mem_per_device(compiled)))

    row = terms.row()
    row.update({
        "status": "ok", "compile_s": t_compile,
        "microbatches": microbatches,
        "opt_moments": opt_cfg.moment_dtype,
        "collective_bytes": terms.collective_bytes,
        "hlo_bytes": terms.hlo_bytes,
        "fits_hbm": bool(terms.bytes_per_device <= HBM_BYTES),
        "mem_argument": int(getattr(mem, "argument_size_in_bytes", 0)),
        "mem_temp": int(getattr(mem, "temp_size_in_bytes", 0)),
        "mem_output": int(getattr(mem, "output_size_in_bytes", 0)),
        "mem_alias": int(getattr(mem, "alias_size_in_bytes", 0)),
    })
    if verbose:
        print(f"--- {arch} × {shape_name} × {mesh_name} ---")
        print(f"compile {t_compile:.1f}s microbatches={microbatches}")
        print(mem)
        print(f"roofline: compute {terms.compute_s * 1e3:.2f}ms "
              f"memory {terms.memory_s * 1e3:.2f}ms "
              f"collective {terms.collective_s * 1e3:.2f}ms "
              f"dominant={terms.dominant} useful={terms.useful_ratio:.3f} "
              f"bytes/dev={terms.bytes_per_device/2**30:.2f}GiB "
              f"fits={row['fits_hbm']}")
    return row


def lower_retrieval(*, multi_pod: bool, num_points: int = 2 ** 30,
                    verbose: bool = True) -> dict:
    """Dry-run of the paper's own system at production scale: 1B hybrid
    vectors sharded across the mesh 'data' axis.  Compiles BOTH the pass-1
    fan-out (LUT16 ADC + inverted index + local top-k + all-gather merge)
    and the full three-pass engine search (+ per-shard dense/sparse residual
    refinement, paper §5/§7.2)."""
    from repro.core.distributed import (make_sharded_search3_fn,
                                        make_sharded_search_fn)
    mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_name = "2x16x16" if multi_pod else "16x16"
    shards = mesh.shape["data"]
    n = num_points - num_points % (shards * 128)
    k_pq, l = 100, 16                  # 200 dense dims -> K=100 subspaces
    d_dense = 200
    d_active, l_max = 65536, 256       # per-shard compact columns
    r_max = 64                         # sparse residual entries per row
    q, nq = 128, 256

    def sds(shape, dtype, spec):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, spec))

    codes = sds((n, k_pq), jnp.uint8, P("data"))
    lut = sds((q, k_pq, l), jnp.float32, P())
    inv_rows = sds((shards * d_active, l_max), jnp.int32, P("data"))
    inv_vals = sds((shards * d_active, l_max), jnp.float32, P("data"))
    q_dims = sds((q, nq), jnp.int32, P())
    q_vals = sds((q, nq), jnp.float32, P())
    row_off = sds((shards,), jnp.int32, P("data"))

    t0 = time.time()
    fn1 = make_sharded_search_fn(mesh, k=100, adc="onehot-mxu")
    fn1.lower(codes, lut, inv_rows, inv_vals, q_dims, q_vals,
              row_off).compile()
    dt1 = time.time() - t0

    t0 = time.time()
    fn3 = make_sharded_search3_fn(mesh, h=100, alpha=5, beta=2,
                                  adc="onehot-mxu")
    compiled = fn3.lower(
        codes, lut, inv_rows, inv_vals,
        sds((n, d_dense), jnp.int8, P("data")),              # dense residual
        sds((d_dense,), jnp.float32, P()),
        sds((d_dense,), jnp.float32, P()),
        sds((n, r_max), jnp.int32, P("data")),               # sparse residual
        sds((n, r_max), jnp.float32, P("data")),
        q_dims, q_vals,
        sds((q, d_dense), jnp.float32, P()),
        sds((q, d_active + 1), jnp.float32, P()),
        row_off).compile()
    dt3 = time.time() - t0
    mem = compiled.memory_analysis()
    if verbose:
        print(f"--- retrieval 1B × {mesh_name}: pass-1 {dt1:.1f}s, "
              f"three-pass {dt3:.1f}s ---")
        print(mem)
    return {"arch": "hybrid-retrieval-1b", "shape": "search_q128",
            "mesh": mesh_name, "status": "ok", "compile_s": dt1 + dt3,
            "compile_pass1_s": dt1, "compile_three_pass_s": dt3}


# cheap-to-compile archs first so partial sweeps cover the most cells
_SWEEP_ORDER = [
    "stablelm-1.6b", "mamba2-780m", "qwen2-moe-a2.7b", "musicgen-medium",
    "qwen2-7b", "recurrentgemma-9b", "qwen2.5-14b", "deepseek-67b",
    "qwen3-moe-235b-a22b", "llama-3.2-vision-90b",
]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", choices=("off", "on", "both"),
                    default="off")
    ap.add_argument("--retrieval", action="store_true")
    ap.add_argument("--out", default=None)
    ap.add_argument("--skip-done", action="store_true",
                    help="skip cells already present in --out (JSONL resume)")
    ap.add_argument("--no-probes", action="store_true",
                    help="compile/memory proof only (no roofline probes)")
    ap.add_argument("--fit-from", default=None,
                    help="JSONL from a prior sweep: reuse fit decisions")
    args = ap.parse_args()

    hints = {}
    if args.fit_from and os.path.exists(args.fit_from):
        with open(args.fit_from) as f:
            for line in f:
                if line.strip():
                    r = json.loads(line)
                    hints[(r["arch"], r["shape"])] = r

    pods = {"off": [False], "on": [True], "both": [False, True]}[
        args.multi_pod]
    cells = []
    if args.all:
        for arch in _SWEEP_ORDER:
            for shape in SHAPES:
                cells.append((arch, shape))
    elif args.arch:
        shapes = [args.shape] if args.shape else list(SHAPES)
        cells = [(args.arch, s) for s in shapes]

    done = set()
    rows = []
    if args.out and os.path.exists(args.out) and args.skip_done:
        with open(args.out) as f:
            for line in f:
                if line.strip():
                    r = json.loads(line)
                    rows.append(r)
                    done.add((r["arch"], r["shape"], r["mesh"]))

    def record(row):
        rows.append(row)
        if args.out:
            os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
            with open(args.out, "a") as f:
                f.write(json.dumps(row, default=str) + "\n")

    for multi_pod in pods:
        mesh_name = "2x16x16" if multi_pod else "16x16"
        if args.retrieval and ("hybrid-retrieval-1b", "search_q128",
                               mesh_name) not in done:
            record(lower_retrieval(multi_pod=multi_pod))
        for arch, shape in cells:
            if (arch, shape, mesh_name) in done:
                continue
            try:
                record(lower_cell(arch, shape, multi_pod=multi_pod,
                                  probes=not args.no_probes,
                                  fit_hint=hints.get((arch, shape))))
            except Exception as e:  # a failure is a bug; record and continue
                traceback.print_exc()
                record({"arch": arch, "shape": shape, "mesh": mesh_name,
                        "status": "fail", "error": repr(e)})
            import sys
            sys.stdout.flush()
    fails = [r for r in rows if r.get("status") == "fail"]
    print(f"\n{len(rows)} cells: "
          f"{sum(r.get('status') == 'ok' for r in rows)} ok, "
          f"{sum(r.get('status') == 'skip' for r in rows)} skip, "
          f"{len(fails)} fail")
    if fails:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
