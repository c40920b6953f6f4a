"""Kernel-level microbenchmarks (paper §4.1.2's LUT16 throughput claim and
§3's cache-line model, TPU-adapted).

interpret-mode wall time is NOT a TPU estimate — the structural metrics are
the point here:
  * lut16: bytes streamed per score vs a dense f32 matmul (the paper's 16x
    index-size reduction => 16x fewer HBM bytes on the scan);
  * block_sparse: tiles stored/streamed after cache sorting vs unsorted —
    the exact counter the Eq. 4/5 model predicts (DMA traffic on TPU).
"""

from __future__ import annotations

import numpy as np
import scipy.sparse as sp
import jax.numpy as jnp

import repro.core.cache_sort as cs
from repro.kernels.block_sparse import dense_to_bcsr
from repro.kernels.ops import lut16_adc
from repro.kernels.ref import lut16_adc_ref

from .common import emit, timeit


def bench_lut16():
    rng = np.random.default_rng(0)
    n, k, l, q = 20000, 32, 16, 16
    d_dense = k * 2
    codes = jnp.asarray(rng.integers(0, l, (n, k)).astype(np.uint8))
    lut = jnp.asarray(rng.normal(size=(q, k, l)).astype(np.float32))

    s_ref, _ = timeit(lambda: lut16_adc_ref(codes, lut).block_until_ready())
    s_ker, _ = timeit(lambda: lut16_adc(codes, lut).block_until_ready())
    # packed 4-bit path (paper's storage; halves the HBM stream again) —
    # through the same ops wrapper the engine's pallas-packed backend uses
    from repro.kernels.ops import pack_codes
    packed = jnp.asarray(pack_codes(np.asarray(codes)))
    s_pk, _ = timeit(lambda: lut16_adc(
        packed, lut, bq=8, bn=512, packed=True).block_until_ready())
    # structural: bytes per datapoint scanned
    pq_bytes = k                      # uint8 per subspace
    packed_bytes = packed.shape[1]    # two 4-bit codes per byte
    dense_bytes = d_dense * 4
    emit("lut16_ref_scan", s_ref / (n * q) * 1e6,
         f"bytes_per_point={pq_bytes}")
    emit("lut16_kernel_scan", s_ker / (n * q) * 1e6,
         f"bytes_per_point={pq_bytes};dense_equiv={dense_bytes};"
         f"traffic_reduction={dense_bytes / pq_bytes:.0f}x")
    emit("lut16_kernel_packed4bit", s_pk / (n * q) * 1e6,
         f"bytes_per_point={packed_bytes};dense_equiv={dense_bytes};"
         f"index_bytes={packed.nbytes};unpacked_index_bytes={codes.nbytes};"
         f"traffic_reduction={dense_bytes / packed_bytes:.0f}x")


def autotune_fused_blocks(*, n: int = 8192, k: int = 32, l: int = 16,
                          q: int = 8, topk: int = 128,
                          packed: bool = False) -> dict:
    """Sweep the fused kernel's (bq, bn) grid under the VMEM budget and
    time each candidate on a small workload.  Returns the swept candidates
    (with VMEM estimates), the fastest config, and the budget — recorded in
    BENCH_engine.json so the shipped defaults are an audited choice, not a
    guess.  Interpret-mode timings rank relative block overheads only; the
    VMEM feasibility column is hardware-independent."""
    from repro.kernels.lut16 import (VMEM_BUDGET, candidate_buffer_width,
                                     vmem_bytes)
    from repro.kernels.ops import lut16_adc_topk
    rng = np.random.default_rng(7)
    codes_np = rng.integers(0, l, (n, k)).astype(np.uint8)
    if packed:
        from repro.kernels.ops import pack_codes
        codes = jnp.asarray(pack_codes(codes_np))
    else:
        codes = jnp.asarray(codes_np)
    lut = jnp.asarray(rng.normal(size=(q, k, l)).astype(np.float32))
    cbuf = candidate_buffer_width(topk)

    candidates = []
    best = None
    for bq in (8,):
        for bn in (128, 256, 512, 1024):
            vmem = vmem_bytes(bq, bn, -(-k // 2) if packed else k, l=l,
                              packed=packed, cbuf=cbuf)
            entry = {"bq": bq, "bn": bn, "vmem_bytes": vmem,
                     "fits": vmem <= VMEM_BUDGET}
            if entry["fits"]:
                fn = lambda: lut16_adc_topk(
                    codes, lut, topk, bq=bq, bn=bn,
                    packed=packed)[0].block_until_ready()
                fn()                          # warmup/compile
                secs, _ = timeit(fn, repeat=3)
                entry["us"] = secs * 1e6
                if best is None or entry["us"] < best["us"]:
                    best = entry
            candidates.append(entry)
    return {"workload": {"n": n, "k": k, "l": l, "q": q, "topk": topk,
                         "packed": packed},
            "budget_bytes": VMEM_BUDGET, "candidates": candidates,
            "best": best}


def bench_fused_topk():
    """Fused scan-and-select vs materialize + top_k, unpacked and packed —
    the tentpole A/B.  Off-TPU the wall times are interpret proxies; the
    honest claims are the byte columns (packed stream strictly half) and
    the structural no-materialization assertion (test_kernels)."""
    from repro.kernels.ops import lut16_adc_topk, pack_codes
    rng = np.random.default_rng(2)
    n, k, l, q, topk = 20000, 32, 16, 16, 128
    codes_np = rng.integers(0, l, (n, k)).astype(np.uint8)
    codes = jnp.asarray(codes_np)
    packed = jnp.asarray(pack_codes(codes_np))
    lut = jnp.asarray(rng.normal(size=(q, k, l)).astype(np.float32))

    runs = {
        "fused": lambda: lut16_adc_topk(
            codes, lut, topk, fused=True)[0].block_until_ready(),
        "materialize": lambda: lut16_adc_topk(
            codes, lut, topk, fused=False)[0].block_until_ready(),
        "fused_packed": lambda: lut16_adc_topk(
            packed, lut, topk, packed=True, fused=True)[0].block_until_ready(),
    }
    secs = {}
    for name, fn in runs.items():
        fn()
        secs[name], _ = timeit(fn, repeat=3)
    emit("lut16_fused_topk", secs["fused"] / (n * q) * 1e6,
         f"vs_materialize={secs['materialize'] / secs['fused']:.2f}x")
    emit("lut16_fused_topk_packed", secs["fused_packed"] / (n * q) * 1e6,
         f"bytes_per_point={packed.shape[1]};"
         f"unpacked_bytes_per_point={k};"
         f"vs_unpacked_fused={secs['fused'] / secs['fused_packed']:.2f}x")


def bench_value_forward():
    """SINDI-style value-forward sparse pass-1 vs the gather/scatter-add
    reference on a power-law inverted index."""
    from repro.core.sparse_index import (build_compact_columns,
                                         build_padded_inverted_index,
                                         score_inverted,
                                         sparse_queries_to_padded)
    from repro.kernels.ops import score_inverted_vf
    rng = np.random.default_rng(3)
    n, d, qn = 8192, 2000, 16
    pj = np.minimum(1.0, cs.power_law_probs(d, 2.0) * 4)
    x = sp.csr_matrix(((rng.random((n, d)) < pj[None, :])
                       * rng.lognormal(0, 1, (n, d))).astype(np.float32))
    cols, xc = build_compact_columns(x)
    inv = build_padded_inverted_index(xc)
    qs = sp.csr_matrix(((rng.random((qn, d)) < pj[None, :] * 0.5)
                        * rng.lognormal(0, 1, (qn, d))).astype(np.float32))
    qd, qv = sparse_queries_to_padded(qs, cols, nq_max=128)
    qdj, qvj = jnp.asarray(qd), jnp.asarray(qv)

    ref = lambda: score_inverted(inv, qdj, qvj).block_until_ready()
    vf = lambda: score_inverted_vf(inv, qd, qv).block_until_ready()
    ref(); vf()
    s_ref, _ = timeit(ref, repeat=3)
    s_vf, _ = timeit(vf, repeat=3)
    l_max = int(np.asarray(inv.rows).shape[1])
    emit("sparse_inverted_gather", s_ref / qn * 1e6,
         f"gather_rect={qn}x{qd.shape[1]}x{l_max}")
    emit("sparse_value_forward", s_vf / qn * 1e6,
         f"vs_gather={s_ref / s_vf:.2f}x;includes_host_plan=true")


def bench_block_sparse():
    """Tile counts on the *pruned* head matrix — the object the real pipeline
    builds (HybridIndex eta-prunes before tiling; unpruned dense-ish columns
    are exactly what the paper's hyper-sparse first-pass index removes).
    Tile = 8 rows × 128 lanes (TPU sublane×lane granularity; B=8 in Eq. 4/5
    terms)."""
    from repro.core.pruning import prune_split
    rng = np.random.default_rng(1)
    n, d = 8192, 512
    pj = np.minimum(1.0, cs.power_law_probs(d, 2.0) * 4)
    x = sp.csr_matrix(((rng.random((n, d)) < pj[None, :])
                       * rng.lognormal(0, 1, (n, d))).astype(np.float32))
    pruned = prune_split(x, keep_top=192).index
    dense = pruned.toarray()
    br, bc = 8, 128
    tiles_un, _, _ = dense_to_bcsr(dense, br, bc)
    pi = cs.cache_sort(pruned)
    tiles_so, _, _ = dense_to_bcsr(dense[pi], br, bc)
    total_tiles = (n // br) * (d // bc)
    emit("block_sparse_tiles_unsorted", 0.0,
         f"tiles={tiles_un.shape[0]}/{total_tiles}")
    emit("block_sparse_tiles_cache_sorted", 0.0,
         f"tiles={tiles_so.shape[0]}/{total_tiles};"
         f"dma_reduction={tiles_un.shape[0] / max(tiles_so.shape[0], 1):.2f}x")


def main():
    bench_lut16()
    bench_fused_topk()
    bench_value_forward()
    bench_block_sparse()


if __name__ == "__main__":
    main()
