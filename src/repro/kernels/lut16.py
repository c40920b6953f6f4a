"""LUT16 ADC scan as a Pallas TPU kernel (paper §4.1.2, TPU-adapted).

x86 lineage: AVX2 PSHUFB performs 32 parallel 16-way lookups of 8-bit LUT
values per instruction; accumulation needs the unsigned width-extension trick.

TPU re-derivation (DESIGN.md §2): the MXU *is* a register-bandwidth shuffle
engine — contracting a 0/1 one-hot matrix against the LUT performs 128-wide
16-way lookup-accumulate per pass, with fp32 accumulation for free (so the
paper's bias/overflow fix-up is unnecessary).  Codes are kept uint8 in HBM
(the stream that bounds single-query throughput, §4.1.2) and expanded to
one-hot only inside VMEM.

The expansion works on 2-D tiles only, the shapes Mosaic lowers: for each
chunk of ``128 // l`` subspaces a 0/1 selector matmul copies every code of
the chunk onto its ``l`` one-hot rows, ``rep (128, bn)`` — exact, since the
codes fit bf16 — and ``rep == row % l`` is the one-hot, rows on sublanes and
datapoints on lanes.  Packed nibbles are split in-register (``rep & 15`` and
``rep >> 4``), each contracted against its own half of the chunk's LUT.

Two kernels share one accumulation body (``_block_partial``):

* ``lut16_adc_pallas``      — materialize the full (Q, N) score matrix;
* ``lut16_adc_topk_pallas`` — fused scan-and-select (DESIGN.md §2.5): the
  same accumulation, but survivors are selected against a VMEM-resident
  candidate buffer in the same grid pass, so the (Q, N) matrix never exists
  in HBM.  The select is a max-and-mask loop (no sort primitive).

Contract (matches kernels/ref.py::lut16_adc_ref):
  codes (N, K) uint8 in [0, l)   PQ codes, row-major over datapoints
  lut   (Q, K, l) float32        per-query per-subspace inner products
  out   (Q, N) float32           out[q, n] = sum_k lut[q, k, codes[n, k]]

Grid: (Q/bq, cdiv(N, bn)); a code block spans the whole stored K axis, so
its lane dimension is always the full array width.  The last row block may
be partial: its out-of-range rows are dropped (materialize) or masked to
-inf (select).  VMEM per step at K=100, bq=8, bn=512: 50 KiB codes + 52 KiB
LUT + 256 KiB one-hot + 16 KiB scores, far under v5e's scoped 16 MiB.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["lut16_adc_pallas", "lut16_adc_topk_pallas", "candidate_buffer_width",
           "pack_codes", "unpack_codes", "default_interpret"]

# one-hot rows per MXU pass: the chunk of ``_CHUNK // l`` subspaces whose
# one-hot fills one 128-deep contraction
_CHUNK = 128
_INT_MAX = jnp.iinfo(jnp.int32).max


def default_interpret() -> bool:
    """The one backend rule for the Pallas kernels: compiled on ``tpu``,
    interpret mode on ``cpu`` (tests, host rehearsals), and an error on any
    other platform — never a silent interpret run on an accelerator."""
    platform = jax.default_backend()
    if platform == "tpu":
        return False
    if platform == "cpu":
        return True
    raise RuntimeError(f"the Pallas kernels compile for 'tpu' and interpret "
                       f"on 'cpu'; the default backend is {platform!r}")


def candidate_buffer_width(k: int) -> int:
    """VMEM candidate-buffer width for a top-``k`` fused select: ``k``
    rounded up to the 128-lane granularity (DESIGN.md §2.5)."""
    return max(-(-k // 128) * 128, 128)


def _lut_chunks(lut: jax.Array, kc: int, packed: bool):
    """(Q, K, l) LUT -> (Q, G * width) chunk-major rows, G chunks of
    ``_CHUNK // l`` stored subspaces; the kernel slices one chunk's lanes
    per MXU pass.  Packed: chunk g holds bytes [8g, 8g + 8) as the
    low-nibble (even subspace) LUT rows, then the high-nibble (odd) ones.
    Subspaces past K are zero-padded, so their (zero) codes add nothing."""
    q, k, l = lut.shape
    if _CHUNK % l:
        raise ValueError(f"l={l} codewords must divide {_CHUNK}")
    spc = _CHUNK // l
    g = -(-kc // spc)
    if packed:
        x = lut.reshape(q, kc, 2, l)
        x = jnp.pad(x, ((0, 0), (0, g * spc - kc), (0, 0), (0, 0)))
        x = x.reshape(q, g, spc, 2, l).transpose(0, 1, 3, 2, 4)
        return x.reshape(q, g * 2 * _CHUNK), g
    x = jnp.pad(lut, ((0, 0), (0, g * spc - k), (0, 0)))
    return x.reshape(q, g * _CHUNK), g


def _block_partial(codes_ref, lut_ref, *, l: int, num_chunks: int,
                   packed: bool, compute_dtype):
    """One (bq, bn) block of ``codes · lut`` on the MXU, chunk by chunk.

    compute_dtype=float32 contracts at ``Precision.HIGHEST`` (true f32 on
    the MXU); bfloat16 rounds the LUT and takes a single pass."""
    bq = lut_ref.shape[0]
    codes = codes_ref[...].astype(jnp.int32).astype(jnp.float32)
    codes = codes.astype(jnp.bfloat16)                        # exact: < 256
    bn, kc = codes.shape
    spc = _CHUNK // l
    shift = l.bit_length() - 1
    row = jax.lax.broadcasted_iota(jnp.int32, (_CHUNK, kc), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (_CHUNK, kc), 1)
    level = jax.lax.broadcasted_iota(jnp.int32, (_CHUNK, bn), 0) & (l - 1)
    precision = (jax.lax.Precision.HIGHEST if compute_dtype == jnp.float32
                 else jax.lax.Precision.DEFAULT)
    width = 2 * _CHUNK if packed else _CHUNK

    def lookup(lut_rows, nib):
        onehot = (nib == level).astype(compute_dtype)         # (128, bn)
        return jax.lax.dot_general(
            lut_rows.astype(compute_dtype), onehot, (((1,), (0,)), ((), ())),
            precision=precision, preferred_element_type=jnp.float32)

    def chunk(g, acc):
        # selector (128, kc): one-hot row r copies code g*spc + r // l
        sel = (col == g * spc + (row >> shift)).astype(jnp.bfloat16)
        rep = jax.lax.dot_general(
            sel, codes, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32).astype(jnp.int32)
        start = pl.multiple_of(g * width, width)
        if packed:
            acc = acc + lookup(lut_ref[:, pl.ds(start, _CHUNK)], rep & 15)
            return acc + lookup(lut_ref[:, pl.ds(start + _CHUNK, _CHUNK)],
                                rep >> 4)
        return acc + lookup(lut_ref[:, pl.ds(start, _CHUNK)], rep)

    return jax.lax.fori_loop(0, num_chunks, chunk,
                             jnp.zeros((bq, bn), jnp.float32))


def _kernel(codes_ref, lut_ref, out_ref, **kw):
    out_ref[...] = _block_partial(codes_ref, lut_ref, **kw)


def _check_shapes(codes, lut, packed: bool):
    n, kc = codes.shape
    q, k, l = lut.shape
    if packed:
        assert l == 16 and k == 2 * kc, (codes.shape, lut.shape)
    else:
        assert kc == k, (codes.shape, lut.shape)
    return n, kc, q, l


_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "arbitrary"))


@functools.partial(jax.jit,
                   static_argnames=("bq", "bn", "interpret", "compute_dtype",
                                    "packed"))
def lut16_adc_pallas(codes: jax.Array, lut: jax.Array, *, bq: int = 8,
                     bn: int = 512, interpret: bool,
                     compute_dtype=jnp.float32,
                     packed: bool = False) -> jax.Array:
    """Pallas LUT16 ADC.  codes: (N, K) uint8; lut: (Q, K, l) f32 -> (Q, N)
    f32.  Q must be a multiple of bq (ops.py pads the LUT); N need not be a
    multiple of bn.  ``interpret`` comes from the caller
    (``default_interpret()``).

    compute_dtype=bfloat16 rounds the LUT to bf16 (the paper's 8-bit
    quantized LUT accuracy budget) for a single MXU pass; float32 keeps
    full f32 products.

    packed=True: codes hold TWO 4-bit subspace codes per byte (shape
    (N, K/2); the paper's storage format) — HBM streams half the bytes and
    the kernel unpacks in-register.  Requires l == 16 and K even."""
    n, kc, q, l = _check_shapes(codes, lut, packed)
    assert q % bq == 0, (q, bq)
    lut2, num_chunks = _lut_chunks(lut, kc, packed)
    return pl.pallas_call(
        functools.partial(_kernel, l=l, num_chunks=num_chunks, packed=packed,
                          compute_dtype=compute_dtype),
        grid=(q // bq, pl.cdiv(n, bn)),
        in_specs=[
            pl.BlockSpec((bn, kc), lambda iq, jn: (jn, 0)),
            pl.BlockSpec((bq, lut2.shape[1]), lambda iq, jn: (iq, 0)),
        ],
        out_specs=pl.BlockSpec((bq, bn), lambda iq, jn: (iq, jn)),
        out_shape=jax.ShapeDtypeStruct((q, n), jnp.float32),
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )(codes, lut2)


# ---------------------------------------------------------------------------
# Fused scan-and-select (DESIGN.md §2.5)
# ---------------------------------------------------------------------------

def _merge_block(total, ids, buf_s, buf_i):
    """Insert a score block's survivors into the unsorted candidate buffer.

    Max-and-mask: each step takes the block's best remaining (score, lowest
    id) per query and, if it STRICTLY beats the buffer's minimum, overwrites
    the worst buffer slot (lowest score, then highest id).  Buffer entries
    always hold lower ids than the block's rows (blocks sweep rows in
    order), so this keeps exactly ``lax.top_k``'s lowest-index tie-break.
    The loop runs until no query's block maximum beats its threshold."""
    cbuf = buf_s.shape[1]
    lane = jax.lax.broadcasted_iota(jnp.int32, buf_s.shape, 1)

    def pending(carry):
        blk, bs, _ = carry
        beats = (jnp.max(blk, axis=1, keepdims=True)
                 > jnp.min(bs, axis=1, keepdims=True))
        return jnp.max(beats.astype(jnp.int32)) > 0

    def insert(carry):
        blk, bs, bi = carry
        thresh = jnp.min(bs, axis=1, keepdims=True)
        v = jnp.max(blk, axis=1, keepdims=True)
        vid = jnp.min(jnp.where(blk == v, ids, _INT_MAX), axis=1,
                      keepdims=True)
        blk = jnp.where(ids == vid, -jnp.inf, blk)
        at_min = bs == thresh
        worst = jnp.max(jnp.where(at_min, bi, -2), axis=1, keepdims=True)
        slot = jnp.min(jnp.where(at_min & (bi == worst), lane, cbuf), axis=1,
                       keepdims=True)
        put = (v > thresh) & (lane == slot)
        return blk, jnp.where(put, v, bs), jnp.where(put, vid, bi)

    _, buf_s, buf_i = jax.lax.while_loop(pending, insert,
                                         (total, buf_s, buf_i))
    return buf_s, buf_i


def _sort_buffer(buf_s, buf_i):
    """Order the buffer like ``lax.top_k``: score descending, lower id
    first among equal scores.  Taken slots become (-inf, -1), which is
    what a non-finite entry normalizes to anyway."""
    cbuf = buf_s.shape[1]
    lane = jax.lax.broadcasted_iota(jnp.int32, buf_s.shape, 1)

    def take(j, carry):
        bs, bi, out_s, out_i = carry
        m = jnp.max(bs, axis=1, keepdims=True)
        mid = jnp.min(jnp.where(bs == m, bi, _INT_MAX), axis=1,
                      keepdims=True)
        slot = jnp.min(jnp.where((bs == m) & (bi == mid), lane, cbuf),
                       axis=1, keepdims=True)
        out_s = jnp.where(lane == j, m, out_s)
        out_i = jnp.where(lane == j, mid, out_i)
        gone = lane == slot
        return (jnp.where(gone, -jnp.inf, bs), jnp.where(gone, -1, bi),
                out_s, out_i)

    _, _, out_s, out_i = jax.lax.fori_loop(0, cbuf, take,
                                           (buf_s, buf_i, buf_s, buf_i))
    return out_s, out_i


def _fused_kernel(codes_ref, lut_ref, base_ref, out_s_ref, out_i_ref, *,
                  n: int, bn: int, **kw):
    """Score one (bq, bn) block, then merge it into the per-query candidate
    buffer — the (Q, N) matrix never leaves VMEM.

    The buffer (out_s/out_i, shape (bq, cbuf)) is the OUTPUT block; its index
    map ignores jn, so Pallas keeps it VMEM-resident across the whole row
    sweep and writes it back to HBM once per query block, sorted at the
    last row block."""
    jn = pl.program_id(1)

    @pl.when(jn == 0)
    def _init_buffer():
        out_s_ref[...] = jnp.full_like(out_s_ref, -jnp.inf)
        out_i_ref[...] = jnp.full_like(out_i_ref, -1)

    # bias is added once per row block, so the fp32 addition order is
    # ``base + partial`` — bit-identical to the materialize-then-topk path
    total = base_ref[...] + _block_partial(codes_ref, lut_ref, **kw)
    ids = jn * bn + jax.lax.broadcasted_iota(jnp.int32, total.shape, 1)
    total = jnp.where(ids < n, total, -jnp.inf)      # partial last block
    buf_s, buf_i = _merge_block(total, ids, out_s_ref[...], out_i_ref[...])
    out_s_ref[...] = buf_s
    out_i_ref[...] = buf_i

    @pl.when(jn == pl.num_programs(1) - 1)
    def _finish():
        out_s_ref[...], out_i_ref[...] = _sort_buffer(out_s_ref[...],
                                                      out_i_ref[...])


@functools.partial(jax.jit,
                   static_argnames=("k", "bq", "bn", "interpret",
                                    "compute_dtype", "packed"))
def lut16_adc_topk_pallas(codes: jax.Array, lut: jax.Array, base: jax.Array,
                          *, k: int, bq: int = 8, bn: int = 512,
                          interpret: bool, compute_dtype=jnp.float32,
                          packed: bool = False):
    """Fused LUT16 scan + top-k select (DESIGN.md §2.5).

    Scores ``base + codes·lut`` and returns the per-query top candidates
    WITHOUT materializing the (Q, N) score matrix: the only outputs are the
    (Q, cbuf) candidate score/id buffers, cbuf = ``candidate_buffer_width(k)``,
    sorted like ``lax.top_k``.  Callers slice ``[:, :k]``.

    base: additive bias, broadcast against the score block — either (Q, N)
    f32 (sparse+head+tombstones, the engine's pass-1 bias) or (1, N) f32 (a
    row mask only, e.g. -inf padding).  -inf rows can never enter the buffer
    ahead of finite ones; never-filled buffer slots stay (-inf, -1).

    Q must be a multiple of bq (ops.lut16_adc_topk pads); N need not be a
    multiple of bn."""
    n, kc, q, l = _check_shapes(codes, lut, packed)
    assert q % bq == 0, (q, bq)
    assert base.ndim == 2 and base.shape[1] == n and base.shape[0] in (1, q), \
        (base.shape, q, n)
    assert 0 < k <= n, (k, n)
    cbuf = candidate_buffer_width(k)
    lut2, num_chunks = _lut_chunks(lut, kc, packed)
    per_query = base.shape[0] != 1
    out_s, out_i = pl.pallas_call(
        functools.partial(_fused_kernel, n=n, bn=bn, l=l,
                          num_chunks=num_chunks, packed=packed,
                          compute_dtype=compute_dtype),
        grid=(q // bq, pl.cdiv(n, bn)),
        in_specs=[
            pl.BlockSpec((bn, kc), lambda iq, jn: (jn, 0)),
            pl.BlockSpec((bq, lut2.shape[1]), lambda iq, jn: (iq, 0)),
            pl.BlockSpec((bq if per_query else 1, bn),
                         lambda iq, jn: (iq if per_query else 0, jn)),
        ],
        out_specs=[
            pl.BlockSpec((bq, cbuf), lambda iq, jn: (iq, 0)),
            pl.BlockSpec((bq, cbuf), lambda iq, jn: (iq, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct((q, cbuf), jnp.float32),
                   jax.ShapeDtypeStruct((q, cbuf), jnp.int32)],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )(codes, lut2, base)
    return out_s, out_i


def pack_codes(codes):
    """(N, K) codes in [0, 16) -> (N, ceil(K/2)) uint8, two codes per byte.

    Subspace 2j sits in the low nibble of byte j, subspace 2j+1 in the high
    nibble (paper §6.1.1's storage).  Odd K is zero-padded with one phantom
    subspace in the last byte's high nibble; scoring wrappers
    (ops.lut16_adc(packed=True) / unpack_codes) zero the phantom LUT column
    or slice it off, so the pad contributes nothing.  Values outside [0, 16)
    would silently corrupt the neighbouring nibble, so they are rejected.
    Host-side (numpy): runs once at index-construction time."""
    import numpy as np
    codes = np.asarray(codes)
    if codes.ndim != 2:
        raise ValueError(f"codes must be 2-D (N, K), got shape {codes.shape}")
    if codes.size and (codes.min() < 0 or codes.max() > 15):
        raise ValueError(
            "pack_codes requires 4-bit codes in [0, 16); got range "
            f"[{int(codes.min())}, {int(codes.max())}]")
    if codes.shape[1] % 2:
        codes = np.pad(codes, ((0, 0), (0, 1)))
    lo = codes[:, 0::2].astype(np.uint8)
    hi = codes[:, 1::2].astype(np.uint8)
    return (lo | (hi << 4)).astype(np.uint8)


def unpack_codes(packed, k: int):
    """(N, Kp) packed bytes -> (N, k) uint8 codes; inverse of pack_codes.

    k is the LOGICAL subspace count: 2*Kp, or 2*Kp - 1 when the trailing
    high nibble is odd-K padding (which is sliced off here).  jnp-traceable —
    the engine's unpack-then-score path runs it inside jit, so the non-Pallas
    backends score packed storage bit-for-bit like unpacked storage."""
    kp = packed.shape[1]
    if not 0 <= 2 * kp - k <= 1:
        raise ValueError(
            f"(N, {kp}) packed bytes cannot hold {k} subspace codes")
    lo = packed & 0x0F
    hi = packed >> 4
    out = jnp.stack([lo, hi], axis=2).reshape(packed.shape[0], 2 * kp)
    return out[:, :k].astype(jnp.uint8)
