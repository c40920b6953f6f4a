"""Jit'd public wrappers around the Pallas kernels.

Handle padding to block multiples, host-side BCSR conversion, and the
interpret-mode switch (``lut16.default_interpret``: compiled on TPU,
interpret mode on the CPU, an error anywhere else).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .block_sparse import (block_sparse_matmul_pallas, dense_to_bcsr,
                           inverted_value_forward_pallas)
from .lut16 import (VMEM_BUDGET, candidate_buffer_width,
                    default_interpret as _interpret, lut16_adc_pallas,
                    lut16_adc_topk_pallas, pack_codes, unpack_codes,
                    vmem_bytes)
from .ref import lut16_adc_ref

__all__ = ["lut16_adc", "lut16_adc_topk", "lut16_adc_onehot",
           "block_sparse_matmul", "block_sparse_matmul_bcsr",
           "bcsr_from_head", "pack_codes", "unpack_codes",
           "score_inverted_vf", "dense_scores_materialized",
           "MAX_FUSED_CANDIDATES"]

# Fused-select candidate-buffer cap (DESIGN.md §2.5): (bq, cbuf) score+id
# buffers must stay VMEM-resident next to the (bq, bn) score block, and the
# max-and-mask merge and final sort take one (bq, cbuf) pass per inserted or
# sorted candidate — past ~1k candidates they dominate the scan and
# materialize-then-topk wins anyway, so lut16_adc_topk falls back above this.
MAX_FUSED_CANDIDATES = 1024


def _pad_to(x: np.ndarray | jax.Array, axis: int, mult: int, value=0):
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x, size
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value), size


def _resolve_lut16_blocks(q: int, n: int, kc: int, *, l: int, packed: bool,
                          cbuf: int, bq: int | None, bn: int,
                          compute_dtype=jnp.float32):
    """One block-size resolution for BOTH the materialize and the fused LUT16
    wrappers, so their per-block fp32 partial sums are bit-identical.

    bq=None takes the whole batch as one query block, so each one-hot tile
    is built and contracted once per row block for every query.  Only when
    that would not fit ``VMEM_BUDGET`` (K, bn and cbuf set the step's
    footprint, ``lut16.vmem_bytes``) is Q split into the fewest blocks of a
    multiple of 8 rows that fit.  An explicit bq is kept (up to Q).  bn
    clamps against the 128-lane-rounded row count so small inputs get one
    (partial) block instead of a full one."""
    q = max(1, q)
    bn = min(bn, max(-(-n // 128) * 128, 128))
    if bq is not None:
        return min(bq, q), bn

    def fits(b):
        return vmem_bytes(b, bn, kc, l=l, packed=packed, cbuf=cbuf,
                          compute_dtype=compute_dtype) <= VMEM_BUDGET

    if fits(q):
        return q, bn
    cap = 8
    while cap + 8 < q and fits(cap + 8):
        cap += 8
    per_block = -(-q // -(-q // cap))
    return min(q, -(-per_block // 8) * 8), bn


def _validate_packed(kc: int, k: int, l: int, lut: jax.Array,
                     packed: bool) -> jax.Array:
    """Shared packed-storage validation + odd-K phantom-subspace LUT pad."""
    if packed:
        if l != 16:
            raise ValueError(f"packed codes require l == 16, got l={l}")
        if not 0 <= 2 * kc - k <= 1:
            raise ValueError(
                f"packed codes (N, {kc}) cannot hold a {k}-subspace LUT")
        if k < 2 * kc:                  # odd K: phantom subspace scores zero
            lut = jnp.pad(lut, ((0, 0), (0, 2 * kc - k), (0, 0)))
    elif k != kc:
        raise ValueError(f"codes (N, {kc}) do not match a {k}-subspace LUT")
    return lut


def lut16_adc(codes: jax.Array, lut: jax.Array, *, bq: int | None = None,
              bn: int = 1024, compute_dtype=jnp.float32,
              packed: bool = False) -> jax.Array:
    """LUT16 ADC: codes (N, K) uint8, lut (Q, K, l) or (K, l) -> (Q, N).

    Pads Q to a block multiple and routes through the Pallas kernel (a
    partial last row block needs no padding of the codes).  bq=None: the
    whole batch in one query block while it fits VMEM
    (``_resolve_lut16_blocks``).

    packed=True: codes hold TWO 4-bit subspace codes per byte, shape
    (N, ceil(K/2)) from pack_codes — HBM streams half the bytes; the kernel
    unpacks in VMEM.  Requires l == 16.  Odd K is handled here by padding the
    LUT with a zero phantom subspace so the pad nibble (code 0) scores 0."""
    single = lut.ndim == 2
    if single:
        lut = lut[None]
    lut = jnp.asarray(lut, jnp.float32)
    q, k, l = lut.shape
    n, kc = codes.shape                 # kc: stored (byte) subspace axis
    lut = _validate_packed(kc, k, l, lut, packed)
    bq, bn = _resolve_lut16_blocks(q, n, kc, l=l, packed=packed, cbuf=0,
                                   bq=bq, bn=bn, compute_dtype=compute_dtype)
    lut_p, q0 = _pad_to(lut, 0, bq)
    out = lut16_adc_pallas(jnp.asarray(codes), lut_p, bq=bq, bn=bn,
                           interpret=_interpret(), compute_dtype=compute_dtype,
                           packed=packed)
    out = out[:q0]
    return out[0] if single else out


def lut16_adc_topk(codes: jax.Array, lut: jax.Array, k: int, *,
                   bias: jax.Array | None = None,
                   row_mask: jax.Array | None = None,
                   bq: int | None = None, bn: int = 1024,
                   compute_dtype=jnp.float32, packed: bool = False,
                   fused: bool = True):
    """Pass-1 scan-and-select: top-k of ``bias + row_mask + codes·lut``
    (DESIGN.md §2.5).

    codes (N, Kc) uint8 (packed two-per-byte when packed=True), lut
    (Q, K, l) f32, bias optional (Q, N) f32 (the engine's sparse+head term),
    row_mask optional (N,) f32 additive mask (0 live / -inf tombstoned).
    Returns ``(scores (Q, k) f32, ids (Q, k) int32)``; entries whose score is
    non-finite get id -1, in BOTH paths, so tombstoned rows never surface as
    candidates.

    fused=True routes through the fused Pallas kernel: the (Q, N) score
    matrix is never materialized — the kernel's only outputs are the
    (Q, cbuf) candidate buffers.  The fallback (fused=False, or
    k > MAX_FUSED_CANDIDATES: the candidate buffer would not fit the select)
    materializes scores with the SAME block sizes and adds the bias in the
    SAME fp32 order, so the two paths return bit-identical (scores, ids)."""
    lut = jnp.asarray(lut, jnp.float32)
    q, kl, l = lut.shape
    n, kc = codes.shape
    if not 0 < k <= n:
        raise ValueError(f"top-k needs 0 < k <= N rows, got k={k}, N={n}")
    lut = _validate_packed(kc, kl, l, lut, packed)
    bq, bn = _resolve_lut16_blocks(q, n, kc, l=l, packed=packed,
                                   cbuf=candidate_buffer_width(k), bq=bq,
                                   bn=bn, compute_dtype=compute_dtype)

    def _normalize(s, ids):
        return s, jnp.where(jnp.isfinite(s), ids, -1)

    base = None if bias is None else jnp.asarray(bias, jnp.float32)
    if row_mask is not None:
        rm = jnp.asarray(row_mask, jnp.float32)[None, :]
        base = rm if base is None else base + rm

    if not (fused and k <= MAX_FUSED_CANDIDATES):
        # materialize-then-topk fallback: bias-first addition order matches
        # the fused kernel's select step bit-for-bit.
        dense = lut16_adc(codes, lut[:, :kl], bq=bq, bn=bn,
                          compute_dtype=compute_dtype, packed=packed)
        total = dense if base is None else base + dense
        s, ids = jax.lax.top_k(total, k)
        return _normalize(s, ids)

    lut_p, _ = _pad_to(lut, 0, bq)
    if base is None:
        # no per-query bias: a (1, N) zero row is enough — the fused jaxpr
        # then contains NO (Q, N)-shaped value at all (the structural claim
        # dense_scores_materialized checks).
        base = jnp.zeros((1, n), jnp.float32)
    elif base.shape[0] > 1:
        # padded query rows score -inf: their buffers stay (-inf, -1) and
        # are sliced off below.
        base = jnp.pad(base, ((0, lut_p.shape[0] - q), (0, 0)),
                       constant_values=-jnp.inf)
    s, ids = lut16_adc_topk_pallas(jnp.asarray(codes), lut_p, base, k=k,
                                   bq=bq, bn=bn, interpret=_interpret(),
                                   compute_dtype=compute_dtype, packed=packed)
    return _normalize(s[:q, :k], ids[:q, :k])


def _walk_jaxpr_eqns(jaxpr):
    from jax.extend.core import ClosedJaxpr, Jaxpr
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "pallas_call":
            # a pallas_call's body jaxpr manipulates VMEM *blocks*; only its
            # outvars (checked above) land in HBM.  Descending would flag
            # per-block temporaries that never exist at HBM scale.
            continue
        for v in eqn.params.values():
            for sub in jax.tree.leaves(
                    v, is_leaf=lambda x: isinstance(x, (Jaxpr, ClosedJaxpr))):
                if isinstance(sub, ClosedJaxpr):
                    yield from _walk_jaxpr_eqns(sub.jaxpr)
                elif isinstance(sub, Jaxpr):
                    yield from _walk_jaxpr_eqns(sub)


def dense_scores_materialized(fn, *args) -> bool:
    """Structural check for the fused-select claim (DESIGN.md §2.5): trace
    ``fn(*args)`` and report whether any equation in the jaxpr (recursively
    through pjit sub-jaxprs; pallas_call bodies are VMEM block scale and
    skipped, their HBM outvars are checked) PRODUCES a float32 value of shape
    (Q > 1, >= N) — i.e. a full per-query score matrix.  N is taken from the
    first argument's leading dim (the codes row count).  A (1, N) row mask is
    allowed: it is O(N) storage, not the O(Q·N) matrix the fused path
    eliminates.  True for materialize-then-topk, False for the fused path."""
    n = args[0].shape[0]
    closed = jax.make_jaxpr(fn)(*args)
    for eqn in _walk_jaxpr_eqns(closed.jaxpr):
        for var in eqn.outvars:
            aval = getattr(var, "aval", None)
            if (aval is not None and getattr(aval, "ndim", 0) == 2
                    and aval.dtype == jnp.float32
                    and aval.shape[0] > 1 and aval.shape[1] >= n):
                return True
    return False


def score_inverted_vf(index, q_dims, q_vals, *, bq: int = 8, bn: int = 512,
                      chunk: int = 128) -> jax.Array:
    """Value-forward inverted-index scoring (SINDI-style; DESIGN.md §2.5):
    host-plans a row-sorted (row, query, contribution) stream per
    (query-block, row-block) and consumes it with MXU one-hot dots — no
    (Q, nq, L_max) gather rectangle and no (Q, N) scatter-add.

    Matches ``core.sparse_index.score_inverted`` on the same
    ``PaddedInvertedIndex``.  The stream layout depends on the query batch's
    nonzeros, so this op is HOST-PLANNED: it cannot sit inside the jitted
    three-pass search and serves the benchmarks/offline scans instead."""
    from repro.core.sparse_index import build_value_forward_stream
    st = build_value_forward_stream(index, q_dims, q_vals, bq=bq, bn=bn,
                                    chunk=chunk)
    out = inverted_value_forward_pallas(
        st.ptr, st.rows, st.qidx, st.contrib, bq=st.bq, bn=st.bn,
        chunk=st.chunk, num_row_blocks=st.num_row_blocks,
        max_steps=st.max_steps, interpret=_interpret())
    return out[:st.num_queries, :st.num_points]


@jax.jit
def lut16_adc_onehot(codes: jax.Array, lut: jax.Array) -> jax.Array:
    """MXU one-hot ADC: codes (N, K) uint8, lut (Q, K, l) or (K, l) -> (Q, N).

    The LUT16 kernel's contraction expressed in jnp: codes expand to one-hot
    and contract against the LUT as a single matmul — no (Q, N, K) gather
    intermediate, systolic-friendly on TPU (bf16 operands, f32 accumulate)."""
    single = lut.ndim == 2
    lut3 = lut[None] if single else lut                       # (Q, K, l)
    n = codes.shape[0]
    l = lut3.shape[-1]
    onehot = (codes[:, :, None] ==
              jnp.arange(l, dtype=codes.dtype)).astype(jnp.bfloat16)
    out = jax.lax.dot_general(
        lut3.reshape(lut3.shape[0], -1).astype(jnp.bfloat16),
        onehot.reshape(n, -1),
        (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)                   # (Q, N)
    return out[0] if single else out


def bcsr_from_head(head) -> tuple[jax.Array, jax.Array, jax.Array, int]:
    """TileSparseHead -> (tiles, tile_ptr, tile_col, max_steps) host-side."""
    block = np.asarray(head.block, np.float32)
    tiles, ptr, col = dense_to_bcsr(block, head.block_rows, head.block_cols)
    max_steps = int(np.max(ptr[1:] - ptr[:-1], initial=1))
    return (jnp.asarray(tiles), jnp.asarray(ptr), jnp.asarray(col), max_steps)


def block_sparse_matmul_bcsr(q_head: jax.Array, tiles: jax.Array,
                             ptr: jax.Array, col: jax.Array, *,
                             max_steps: int, bq: int = 8) -> jax.Array:
    """Tile-skipping head scoring over prebuilt BCSR arrays: pads the query
    block, runs the Pallas kernel, trims the padding.  Jit-safe."""
    qp, q0 = _pad_to(jnp.asarray(q_head, jnp.float32), 0, bq)
    out = block_sparse_matmul_pallas(qp, tiles, ptr, col, bq=bq,
                                     max_steps=max_steps,
                                     interpret=_interpret())
    return out[:q0]


def block_sparse_matmul(q_head: jax.Array, head, *, bq: int = 8) -> jax.Array:
    """Tile-skipping head scoring: q_head (Q, D_pad) × TileSparseHead -> (Q, N).

    Matches sparse_index.score_head_ref on the stored block matrix."""
    tiles, ptr, col, max_steps = bcsr_from_head(head)
    return block_sparse_matmul_bcsr(q_head, tiles, ptr, col,
                                    max_steps=max_steps, bq=bq)
