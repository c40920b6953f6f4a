"""The least time any implementation of a kernel's work could take on a chip.

Operations and bytes are counted from the work, not from the program's
implementation: what every implementation must read and write, and the
multiply-adds the result needs.  The peaks come from ``peaks.json``, keyed
by the device kind JAX reports; a device that is not there is an error.
"""

from __future__ import annotations

import json
import os

__all__ = ["peaks", "pass1_work", "least_seconds"]

_HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str) -> dict:
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json; known: {sorted(table)}")
    return table[device_kind]


def pass1_work(q: int, n: int, k: int, l: int, c1: int) -> tuple[float, float]:
    """(operations, bytes) of pass 1's dense scan-and-select for ``q``
    queries over ``n`` rows of ``k`` 4-bit PQ codes with ``l`` codewords,
    keeping ``c1`` candidates per query.

    Bytes: the packed codes (n * ceil(k/2)), the float32 LUTs (q * k * l *
    4) and the candidates written out (q * c1 * (4 + 4)).  Operations: one
    multiply-add per (query, row, subspace), 2 * q * n * k.  The sparse
    bias is left out, so the count is the same whatever implements it."""
    ops = 2.0 * q * n * k
    nbytes = float(n * -(-k // 2) + q * k * l * 4 + q * c1 * 8)
    return ops, nbytes


def least_seconds(ops: float, nbytes: float, peak: dict) -> float:
    """The larger of the compute bound (bf16 peak) and the HBM bound."""
    return max(ops / peak["bf16_flops"], nbytes / peak["hbm_bytes_per_s"])
