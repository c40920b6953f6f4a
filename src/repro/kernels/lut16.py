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

The one-hot is built in bf16 (0/1 is exact there) once per row block and
contracted in ONE default-precision MXU pass against every LUT row of the
query block.  For f32 compute the wrapper splits the f32 LUT into three
bf16 terms, ``hi + mid + lo == lut`` exactly (``split_bf16x3``), stacked on
the contraction's M axis: each product is exact and the MXU accumulates in
f32, so the scores are f32 sums of the f32 LUT without a multi-pass f32
emulation.  bfloat16 compute rounds the LUT to its one leading term.

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
-inf (select).  ``vmem_bytes`` estimates one grid step; ops.py takes the
whole batch as one query block (bq = Q) while that fits ``VMEM_BUDGET``.
At K=384 packed, bq=32, bn=1024: 2.25 MiB of (96, 6144) bf16 LUT terms
(×2 buffers), a 2 MiB one-hot working set, 6.4 MiB in all.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["lut16_adc_pallas", "lut16_adc_topk_pallas", "candidate_buffer_width",
           "pack_codes", "unpack_codes", "default_interpret", "split_bf16x3",
           "vmem_bytes", "VMEM_BUDGET"]

# one-hot rows per MXU pass: the chunk of ``_CHUNK // l`` subspaces whose
# one-hot fills one 128-deep contraction
_CHUNK = 128
# each LUT term's rows pad to a multiple of the f32 sublane tile, so the
# term slices of the (terms * bqp, bn) accumulator start on tile boundaries
_TERM_ROWS = 8
_INT_MAX = jnp.iinfo(jnp.int32).max
# the share of v5e's 16 MiB scoped VMEM one grid step may plan for; the
# rest is left to Mosaic's own temporaries
VMEM_BUDGET = 8 * 2 ** 20


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


def split_bf16x3(x: jax.Array):
    """f32 -> (hi, mid, lo) bf16 with ``hi + mid + lo == x`` exactly.

    Each term is the leading 8 significant bits of what the terms before it
    left, cut by masking the f32 bit pattern: 3 × 8 bits cover the f32
    significand's 24, and every term is exactly a bf16 value, so no rounding
    happens that a compiler keeping excess precision could skip.  Exact for
    |x| >= 2**-102, where the last term is still a normal number."""
    def lead(v):
        bits = jax.lax.bitcast_convert_type(v, jnp.uint32)
        return jax.lax.bitcast_convert_type(bits & jnp.uint32(0xFFFF0000),
                                            jnp.float32)
    hi = lead(x)
    rest = x - hi
    mid = lead(rest)
    return (hi.astype(jnp.bfloat16), mid.astype(jnp.bfloat16),
            (rest - mid).astype(jnp.bfloat16))


def _num_terms(compute_dtype) -> int:
    if compute_dtype == jnp.float32:
        return 3
    if compute_dtype == jnp.bfloat16:
        return 1
    raise ValueError(f"compute_dtype must be float32 or bfloat16, "
                     f"got {compute_dtype}")


def _term_rows(bq: int) -> int:
    return -(-bq // _TERM_ROWS) * _TERM_ROWS


def _lut_terms(lut2: jax.Array, bq: int, compute_dtype):
    """(Q, W) f32 chunk rows -> (Q/bq * T * bqp, W) bf16: per query block,
    its T bf16 terms (``split_bf16x3`` for f32, the rounded LUT for bf16),
    each zero-padded to bqp = ``_term_rows(bq)`` rows."""
    terms = (split_bf16x3(lut2) if _num_terms(compute_dtype) == 3
             else (lut2.astype(jnp.bfloat16),))
    q, w = lut2.shape
    bqp = _term_rows(bq)
    x = jnp.stack(terms).reshape(len(terms), q // bq, bq, w)
    x = jnp.pad(x, ((0, 0), (0, 0), (0, bqp - bq), (0, 0)))
    return x.transpose(1, 0, 2, 3).reshape(-1, w)


def _lut_width(kc: int, l: int, packed: bool) -> int:
    """Lanes of one query's chunked LUT row (``_lut_chunks``)."""
    g = -(-kc // (_CHUNK // l))
    return g * (2 * _CHUNK if packed else _CHUNK)


def vmem_bytes(bq: int, bn: int, kc: int, *, l: int, packed: bool,
               cbuf: int, compute_dtype=jnp.float32) -> int:
    """VMEM one grid step of either LUT16 kernel plans for (inputs and
    outputs ×2 for Pallas' pipeline): the code block, the bf16 LUT terms,
    the bias block, one chunk's selector/one-hot working set, the
    (T * bqp, bn) accumulator with one matmul result, and the candidate
    buffers with the merge loop's block-sized temporaries (cbuf=0: the
    materialize kernel's (bq, bn) output)."""
    rows = _num_terms(compute_dtype) * _term_rows(bq)
    bqp = _term_rows(bq)
    codes = 2 * bn * (-(-kc // 128) * 128)
    lut = 2 * rows * _lut_width(kc, l, packed) * 2
    bias = 2 * bqp * bn * 4
    onehot = 4 * _CHUNK * bn * 4
    acc = 2 * rows * bn * 4
    select = 2 * 2 * bqp * cbuf * 4 + 4 * bqp * bn * 4
    return codes + lut + bias + onehot + acc + select


def _block_partial(codes_ref, lut_ref, *, bq: int, terms: int, l: int,
                   num_chunks: int, packed: bool):
    """One (bq, bn) block of ``codes · lut`` on the MXU, chunk by chunk.

    lut_ref holds the block's LUT as ``terms`` bf16 terms of bqp rows each
    (``_lut_terms``).  Each chunk's bf16 one-hot is built once and
    contracted in one DEFAULT-precision pass against all terms * bqp rows:
    bf16 × {0, 1} products are exact and accumulate in f32.  The term sums
    are added at the end, so f32 compute gives f32 sums of the f32 LUT."""
    codes = codes_ref[...].astype(jnp.int32).astype(jnp.float32)
    codes = codes.astype(jnp.bfloat16)                        # exact: < 256
    bn, kc = codes.shape
    rows = lut_ref.shape[0]
    bqp = rows // terms
    spc = _CHUNK // l
    shift = l.bit_length() - 1
    row = jax.lax.broadcasted_iota(jnp.int32, (_CHUNK, kc), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (_CHUNK, kc), 1)
    level = jax.lax.broadcasted_iota(jnp.int32, (_CHUNK, bn), 0) & (l - 1)
    width = 2 * _CHUNK if packed else _CHUNK

    def lookup(lut_rows, nib):
        onehot = (nib == level).astype(jnp.bfloat16)          # (128, bn)
        return jax.lax.dot_general(
            lut_rows, onehot, (((1,), (0,)), ((), ())),
            precision=jax.lax.Precision.DEFAULT,
            preferred_element_type=jnp.float32)

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

    acc = jax.lax.fori_loop(0, num_chunks, chunk,
                            jnp.zeros((rows, bn), jnp.float32))
    total = acc[:bqp]
    for t in range(1, terms):
        total = total + acc[t * bqp:(t + 1) * bqp]
    return total[:bq]


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
                     bn: int = 1024, interpret: bool,
                     compute_dtype=jnp.float32,
                     packed: bool = False) -> jax.Array:
    """Pallas LUT16 ADC.  codes: (N, K) uint8; lut: (Q, K, l) f32 -> (Q, N)
    f32.  Q must be a multiple of bq (ops.py pads the LUT); N need not be a
    multiple of bn.  ``interpret`` comes from the caller
    (``default_interpret()``).

    compute_dtype=bfloat16 rounds the LUT to bf16 (the paper's 8-bit
    quantized LUT accuracy budget); float32 contracts the LUT's three exact
    bf16 terms in the same single MXU pass, so the sums are of f32 values.

    packed=True: codes hold TWO 4-bit subspace codes per byte (shape
    (N, K/2); the paper's storage format) — HBM streams half the bytes and
    the kernel unpacks in-register.  Requires l == 16 and K even."""
    n, kc, q, l = _check_shapes(codes, lut, packed)
    assert q % bq == 0, (q, bq)
    lut2, num_chunks = _lut_chunks(lut, kc, packed)
    lut3 = _lut_terms(lut2, bq, compute_dtype)
    terms = _num_terms(compute_dtype)
    return pl.pallas_call(
        functools.partial(_kernel, bq=bq, terms=terms, l=l,
                          num_chunks=num_chunks, packed=packed),
        grid=(q // bq, pl.cdiv(n, bn)),
        in_specs=[
            pl.BlockSpec((bn, kc), lambda iq, jn: (jn, 0)),
            pl.BlockSpec((terms * _term_rows(bq), lut3.shape[1]),
                         lambda iq, jn: (iq, 0)),
        ],
        out_specs=pl.BlockSpec((bq, bn), lambda iq, jn: (iq, jn)),
        out_shape=jax.ShapeDtypeStruct((q, n), jnp.float32),
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )(codes, lut3)


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
                          *, k: int, bq: int = 8, bn: int = 1024,
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
    lut3 = _lut_terms(lut2, bq, compute_dtype)
    terms = _num_terms(compute_dtype)
    per_query = base.shape[0] != 1
    out_s, out_i = pl.pallas_call(
        functools.partial(_fused_kernel, n=n, bn=bn, bq=bq, terms=terms, l=l,
                          num_chunks=num_chunks, packed=packed),
        grid=(q // bq, pl.cdiv(n, bn)),
        in_specs=[
            pl.BlockSpec((bn, kc), lambda iq, jn: (jn, 0)),
            pl.BlockSpec((terms * _term_rows(bq), lut3.shape[1]),
                         lambda iq, jn: (iq, 0)),
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
    )(codes, lut3, base)
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
