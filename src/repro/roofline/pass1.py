"""Pass-1 roofline: predicted vs measured HBM bytes for the LUT16 scan
(paper §4.1.2's single-stream bound; DESIGN.md §2.5).

The fused scan-and-select changes pass 1's byte equation: the materialize
path writes AND re-reads the (Q, N) fp32 score matrix on its way to top-k,
while the fused path's HBM traffic is just the code stream (halved again by
4-bit packing), the per-query LUTs, and the (Q, cbuf) candidate buffers.
``predicted_pass1_bytes`` is that analytic model; ``measured_bytes`` pulls
the compiler's own "bytes accessed" from ``cost_analysis()`` so the two can
sit side by side in BENCH_engine.json (benchmarks/roofline_table.py renders
the comparison).  In interpret mode the measured number reflects the CPU
lowering, so the bench labels it ``"interpret": true`` — a proxy, not a TPU
measurement.
"""

from __future__ import annotations

import jax

__all__ = ["predicted_pass1_bytes", "measured_bytes"]


def predicted_pass1_bytes(*, q: int, n: int, k_codes: int, l: int = 16,
                          packed: bool = False, fused: bool = True,
                          cbuf: int | None = None) -> int:
    """Analytic HBM bytes for one pass-1 dispatch of the dense ADC scan.

    q queries, n rows, k_codes PQ subspaces (the STORED code width: pass
    ceil(K/2) when packed), l codewords; cbuf the candidate-buffer width
    (defaults to 128, the floor of kernels.lut16.candidate_buffer_width).

    materialize (fused=False) adds the (q, n) fp32 score matrix twice —
    once written by the scan kernel, once re-read by top_k — which is the
    term that made packed *slower* than unpacked end to end: the score
    round-trip dwarfed the halved code stream."""
    if cbuf is None:
        cbuf = 128
    codes = n * k_codes                       # uint8 stream (already halved
    lut = q * k_codes * l * 4                 # when packed: k_codes=ceil(K/2))
    lut *= 2 if packed else 1                 # packed LUT pairs nibble halves
    out = q * cbuf * (4 + 4)                  # f32 scores + i32 ids
    total = codes + lut + out
    if not fused:
        total += 2 * q * n * 4                # write + re-read (Q, N) scores
    return int(total)


def measured_bytes(fn, *args) -> float | None:
    """Compiler-reported "bytes accessed" for ``jit(fn)(*args)``.

    Returns None when the backend's cost model doesn't expose the key
    (missing on some CPU interpret lowerings)."""
    compiled = jax.jit(fn).lower(*args).compile()
    val = compiled.cost_analysis().get("bytes accessed")
    return None if val is None else float(val)
