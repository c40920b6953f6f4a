"""Device-resident three-pass scoring engine (the paper's full search loop).

One layer owns the paper's scorer instead of three call-site copies
(HybridIndex.search, distributed._pass1_local, serve/hybrid_head):

* ``IndexArrays`` — a pytree-registered dataclass holding every
  device-resident index structure: PQ codes + LUT-ready codebooks, the padded
  inverted index, the tile head both as a dense block (ref path) and in BCSR
  form (Pallas path), the int8 dense residual and the padded sparse residual.
  Being a pytree it moves through ``jax.jit`` / ``shard_map`` / donation as a
  single argument.

* ``ScoringEngine`` — runs the ENTIRE three-pass search (pass 1 approximate
  sparse+dense scores → pass 2 dense residual → pass 3 sparse residual, with
  ``lax.top_k`` between passes) as ONE jitted function: no host transfer or
  dispatch between passes.

* ``Backend`` — pluggable scoring backend (DESIGN.md §3):
    ref           pure-jnp gather ADC + dense head matmul (bit-tight oracle)
    onehot-mxu    MXU one-hot contraction ADC (kernels/ops.lut16_adc_onehot)
    pallas        LUT16 + block-sparse Pallas kernels (kernels/ops)
    pallas-packed LUT16 over packed 4-bit codes, two per byte (§6.1.1's
                  storage): the pass-1 HBM code stream — the bound on
                  single-query throughput (§4.1.2) — halves.  IndexArrays
                  built with ``pack=True`` stores ONLY the packed form;
                  ref/onehot backends unpack in-jit (bit-for-bit vs unpacked).

Call sites: core/hybrid.py (build/permute wrapper), core/distributed.py
(shard_map over pass-1 and the full three-pass refinement), and
serve/hybrid_head.py (ADC + residual reorder over an LM head).
"""

from __future__ import annotations

import dataclasses
import enum
import hashlib
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from . import residual as res
from .pq import PQCodebooks, ScalarQuant, adc_lut, adc_scores_ref
from .sparse_index import (PaddedInvertedIndex, PaddedSparseRows,
                           TileSparseHead, score_head_ref, score_inverted)

__all__ = [
    "Backend", "IndexArrays", "ScoringEngine", "adc_scores",
    "scatter_queries_compact", "scatter_head_queries", "pass1_bias",
    "pass1_scores", "three_pass_search", "query_fingerprint",
    "release_index_arrays", "tombstone_mask",
]


class Backend(enum.Enum):
    """Which implementation scores pass 1 (dense ADC + head block)."""
    REF = "ref"
    ONEHOT = "onehot-mxu"
    PALLAS = "pallas"
    PALLAS_PACKED = "pallas-packed"

    @classmethod
    def from_name(cls, name: "Backend | str | None") -> "Backend":
        if name is None:
            return cls.REF
        if isinstance(name, Backend):
            return name
        aliases = {"ref": cls.REF, "gather": cls.REF,
                   "onehot": cls.ONEHOT, "onehot-mxu": cls.ONEHOT,
                   "pallas": cls.PALLAS, "lut16": cls.PALLAS,
                   "pallas-packed": cls.PALLAS_PACKED,
                   "packed": cls.PALLAS_PACKED,
                   "lut16-packed": cls.PALLAS_PACKED}
        try:
            return aliases[name]
        except KeyError:
            raise ValueError(
                f"unknown backend {name!r}; expected one of {sorted(aliases)}"
            ) from None


def adc_scores(codes: jax.Array, lut: jax.Array,
               backend: Backend = Backend.REF, *,
               packed: bool | None = None) -> jax.Array:
    """Dense ADC scan codes × (Q, K, l) LUT -> (Q, N), by backend.

    packed: codes hold two 4-bit subspace codes per byte, (N, ceil(K/2)) from
    kernels pack_codes.  None => packed iff backend is PALLAS_PACKED.  The
    Pallas backends unpack in VMEM (half the HBM stream); ref/onehot unpack
    in-jit first and then score exactly like the unpacked path — bit-for-bit,
    so packed storage stays comparable against the oracle."""
    if packed is None:
        packed = backend is Backend.PALLAS_PACKED
    if backend in (Backend.PALLAS, Backend.PALLAS_PACKED):
        from repro.kernels.ops import lut16_adc
        return lut16_adc(codes, lut, packed=packed)
    if packed:
        from repro.kernels.ops import unpack_codes
        codes = unpack_codes(codes, lut.shape[-2])
    if backend is Backend.ONEHOT:
        from repro.kernels.ops import lut16_adc_onehot
        return lut16_adc_onehot(codes, lut)
    return adc_scores_ref(codes, lut)


# ---------------------------------------------------------------------------
# IndexArrays — everything search needs, resident on device
# ---------------------------------------------------------------------------

@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class IndexArrays:
    codebooks: PQCodebooks             # LUT-ready PQ codebooks (K, l, p)
    codes: jax.Array                   # (N, K) uint8 PQ codes, or
                                       # (N, ceil(K/2)) when codes_packed
    inv_index: PaddedInvertedIndex     # tail dims of the pruned data index
    head: TileSparseHead | None        # head dims (None => no head block)
    head_pos: jax.Array                # (d_active+1,) compact dim -> head slot
    head_tiles: jax.Array              # BCSR tiles (T, Br, Bc) of the head
    head_ptr: jax.Array                # (N_pad/Br + 1,) int32
    head_col: jax.Array                # (T,) int32
    dense_residual: ScalarQuant        # int8 residual of the dense component
    sparse_residual: PaddedSparseRows  # eps-pruned sparse residual rows
    num_points: int = dataclasses.field(metadata=dict(static=True))
    d_active: int = dataclasses.field(metadata=dict(static=True))
    head_max_steps: int = dataclasses.field(metadata=dict(static=True))
    codes_packed: bool = dataclasses.field(
        default=False, metadata=dict(static=True))
    # (N,) float32 additive row mask: 0 for live rows, -inf for tombstoned or
    # not-yet-filled slots (DESIGN.md §6).  None (the default, and the only
    # value the batch build produces) means every row is live.  The mask is a
    # pytree leaf, so a delta shard can retire rows without reshaping — the
    # jit cache only grows when the capacity doubles.
    valid_mask: jax.Array | None = None

    @classmethod
    def build(cls, *, codebooks: PQCodebooks, codes: jax.Array,
              inv_index: PaddedInvertedIndex, head: TileSparseHead | None,
              dense_residual: ScalarQuant, sparse_residual: PaddedSparseRows,
              num_points: int, d_active: int,
              with_bcsr: bool = True, pack: bool = False,
              pre_packed: bool = False,
              valid_mask: jax.Array | None = None) -> "IndexArrays":
        """Host-side assembly: derives the head query scatter table and the
        BCSR form once, so search never leaves the device.

        with_bcsr=False skips the BCSR conversion (build time + HBM) for
        engines that never take the Pallas head path; _head_scores falls back
        to the dense matmul when the tiles are absent.

        pack=True stores the dense PQ codes packed two-per-byte (paper
        §6.1.1) — the ONLY resident copy, halving the code HBM footprint and
        the pass-1 scan stream.  Requires l <= 16 codewords (4 bits); the
        PALLAS_PACKED kernel additionally needs l == 16 — ScoringEngine
        enforces that pairing at construction.  Odd K gets a zero phantom
        nibble that every scoring path masks out.

        pre_packed=True declares that ``codes`` are ALREADY two-per-byte
        (e.g. a delta shard that packs row by row on append, DESIGN.md §6) —
        the packed flag is set without re-packing.  valid_mask forwards the
        (N,) live/tombstone mask; the batch build leaves it None."""
        pos = np.full(d_active + 1, 0, np.int32)
        tiles = jnp.zeros((1, 1, 1), jnp.float32)
        ptr = jnp.zeros((2,), jnp.int32)
        col = jnp.zeros((1,), jnp.int32)
        max_steps = 0
        if head is not None:
            d_head_pad = head.block.shape[1]
            pos = np.full(d_active + 1, d_head_pad, np.int32)
            hd = np.asarray(head.head_dims)
            valid = np.flatnonzero(hd >= 0)
            pos[hd[valid]] = valid.astype(np.int32)
            if with_bcsr:
                from repro.kernels.ops import bcsr_from_head
                tiles, ptr, col, max_steps = bcsr_from_head(head)
        if pack and pre_packed:
            raise ValueError("pass pack=True (pack now) or pre_packed=True "
                             "(already packed), not both")
        if pack or pre_packed:
            if codebooks.num_codes > 16:
                raise ValueError(
                    "packed codes need l <= 16 codewords (4 bits), got "
                    f"l={codebooks.num_codes}")
        if pack:
            from repro.kernels.ops import pack_codes
            codes = jnp.asarray(pack_codes(np.asarray(codes)))
        return cls(codebooks=codebooks, codes=codes, inv_index=inv_index,
                   head=head, head_pos=jnp.asarray(pos), head_tiles=tiles,
                   head_ptr=ptr, head_col=col, dense_residual=dense_residual,
                   sparse_residual=sparse_residual, num_points=num_points,
                   d_active=d_active, head_max_steps=max_steps,
                   codes_packed=pack or pre_packed, valid_mask=valid_mask)


# ---------------------------------------------------------------------------
# Jittable building blocks
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnums=(2,))
def scatter_queries_compact(q_dims: jax.Array, q_vals: jax.Array,
                            d_active: int) -> jax.Array:
    """(Q, nq) padded sparse queries -> (Q, d_active + 1) dense w/ pad slot."""
    qn = q_dims.shape[0]
    out = jnp.zeros((qn, d_active + 1), jnp.float32)
    qidx = jnp.arange(qn)[:, None]
    out = out.at[jnp.broadcast_to(qidx, q_dims.shape), q_dims].add(
        q_vals, mode="drop")
    return out.at[:, d_active].set(0.0)


def scatter_head_queries(q_dims: jax.Array, q_vals: jax.Array,
                         head_pos: jax.Array, d_head_pad: int) -> jax.Array:
    """Scatter padded sparse queries into the dense head subspace on device.

    head_pos maps compact dim ids (plus the pad sentinel d_active) to head
    slots; non-head dims map to the trailing pad slot, sliced off."""
    qn = q_dims.shape[0]
    pos = jnp.take(head_pos, q_dims, axis=0, mode="clip")       # (Q, nq)
    out = jnp.zeros((qn, d_head_pad + 1), jnp.float32)
    qidx = jnp.arange(qn)[:, None]
    out = out.at[jnp.broadcast_to(qidx, pos.shape), pos].add(
        q_vals, mode="drop")
    return out[:, :d_head_pad]


def _head_scores(arrays: IndexArrays, q_head: jax.Array,
                 backend: Backend) -> jax.Array:
    # head_max_steps == 0 marks arrays built without BCSR (with_bcsr=False);
    # fall back to the dense matmul, which is always correct
    if (backend in (Backend.PALLAS, Backend.PALLAS_PACKED)
            and arrays.head_max_steps > 0):
        from repro.kernels.ops import block_sparse_matmul_bcsr
        return block_sparse_matmul_bcsr(
            q_head, arrays.head_tiles, arrays.head_ptr, arrays.head_col,
            max_steps=arrays.head_max_steps)
    return score_head_ref(arrays.head, q_head)


def pass1_bias(arrays: IndexArrays, q_dims: jax.Array, q_vals: jax.Array,
               backend: Backend = Backend.REF) -> jax.Array:
    """The sparse half of pass 1: inverted-index tail + head block.  (Q, N).

    This is the per-(query, row) additive bias the fused scan-and-select
    kernel (DESIGN.md §2.5) folds into its select step — the dense ADC term
    and the tombstone mask are NOT included here."""
    with jax.named_scope("pass1.sparse_bias"):
        with jax.named_scope("inverted"):
            sparse = score_inverted(arrays.inv_index, q_dims, q_vals)
        if arrays.head is not None:
            with jax.named_scope("head"):
                q_head = scatter_head_queries(q_dims, q_vals, arrays.head_pos,
                                              arrays.head.block.shape[1])
                head_s = _head_scores(arrays, q_head, backend)
                sparse = sparse + head_s[:, : arrays.num_points]
    return sparse


def pass1_scores(arrays: IndexArrays, q_dims: jax.Array, q_vals: jax.Array,
                 lut: jax.Array, backend: Backend = Backend.REF) -> jax.Array:
    """Pass-1 approximate hybrid scores over the full (local) shard:
    inverted-index sparse + head-block sparse + LUT ADC dense.  (Q, N).

    When the arrays carry a ``valid_mask`` (delta shard, DESIGN.md §6) it is
    added here, so tombstoned and empty slots score -inf and can never crowd
    live rows out of ANY pass's top-k — the later passes only add finite
    residual terms to -inf.  (The mask is folded into the sparse bias BEFORE
    the dense term; adding 0.0 is exact and -inf absorbs, so the result is
    bit-identical to masking last — and matches the fused kernel's
    bias-at-select ordering.)"""
    bias = pass1_bias(arrays, q_dims, q_vals, backend)
    with jax.named_scope("pass1.scan"):
        if arrays.valid_mask is not None:
            bias = bias + arrays.valid_mask[None, :]
        dense = adc_scores(arrays.codes, lut, backend,
                           packed=arrays.codes_packed)
        return bias + dense


def tombstone_mask(capacity: int, count: int,
                   dead: np.ndarray | None = None) -> jax.Array:
    """(capacity,) additive row mask for a delta shard: 0 for live slots,
    -inf for tombstoned slots and slots at/after ``count`` (never filled).
    ``dead``: optional (capacity,) bool of tombstoned slots."""
    live = np.arange(capacity) < count
    if dead is not None:
        live &= ~np.asarray(dead, bool)
    return jnp.asarray(np.where(live, 0.0, -np.inf).astype(np.float32))


def _use_fused_pass1(arrays: IndexArrays, backend: Backend, fused: bool,
                     k: int) -> bool:
    """Static routing decision for the fused scan-and-select pass 1.

    Only the Pallas backends have the fused kernel; k must fit the VMEM
    candidate buffer (MAX_FUSED_CANDIDATES) or the op would fall back to
    materialize-then-topk anyway — routing through pass1_scores keeps the
    jaxpr honest about what actually runs."""
    from repro.kernels.ops import MAX_FUSED_CANDIDATES
    return (fused and backend in (Backend.PALLAS, Backend.PALLAS_PACKED)
            and k <= MAX_FUSED_CANDIDATES)


def _fused_pass1_topk(arrays: IndexArrays, q_dims: jax.Array,
                      q_vals: jax.Array, lut: jax.Array, k: int,
                      backend: Backend):
    """Pass-1 top-k via the fused scan-and-select kernel (DESIGN.md §2.5):
    the (Q, N) dense score matrix is never written to HBM — the sparse bias
    is folded in at the kernel's select step, bit-identical to
    pass1_scores + top_k."""
    from repro.kernels.ops import lut16_adc_topk
    bias = pass1_bias(arrays, q_dims, q_vals, backend)
    with jax.named_scope("pass1.scan"):
        return lut16_adc_topk(arrays.codes, lut, k, bias=bias,
                              row_mask=arrays.valid_mask,
                              packed=arrays.codes_packed)


@partial(jax.jit, static_argnames=("h", "c1", "c2", "backend", "fused"))
def three_pass_search(arrays: IndexArrays, q_dims: jax.Array,
                      q_vals: jax.Array, q_dense: jax.Array, *, h: int,
                      c1: int, c2: int, backend: Backend = Backend.REF,
                      fused: bool = True):
    """The paper's full search as ONE jitted function — no host sync between
    passes.  Returns (scores (Q, h), ids (Q, h), pass1 ids (Q, c1)); ids are
    positions in cache-sorted row order (callers map through pi).

    ``fused`` (default on) routes pass 1 through the fused scan-and-select
    kernel on the Pallas backends whenever c1 fits the candidate buffer —
    same (scores, ids) bit-for-bit, minus the (Q, N) HBM round-trip.

    Each layer runs under a ``jax.named_scope`` — ``pass1.lut``,
    ``pass1.sparse_bias`` (``inverted`` and ``head`` inside it),
    ``pass1.scan``, ``pass2``, ``pass3`` — which the compiled program's
    instruction metadata carries (``ScoringEngine.search_hlo``), so device
    time in a profiler trace can be attributed to a layer (DESIGN.md §9.3).
    The scopes cost nothing at run time."""
    with jax.named_scope("pass1.lut"):
        lut = adc_lut(q_dense, arrays.codebooks)

    # pass 1: approximate scores on the full shard, overfetch c1
    if _use_fused_pass1(arrays, backend, fused, c1):
        s1, ids1 = _fused_pass1_topk(arrays, q_dims, q_vals, lut, c1, backend)
    else:
        approx = pass1_scores(arrays, q_dims, q_vals, lut, backend)
        with jax.named_scope("pass1.scan"):
            s1, ids1 = res.topk_candidates(approx, c1)

    # pass 2: + dense residual, keep c2
    with jax.named_scope("pass2"):
        extra_d = res.dense_residual_scores(arrays.dense_residual, ids1,
                                            q_dense)
        s2, ids2 = res.reorder_pass(s1, ids1, extra_d, c2)

    # pass 3: + sparse residual, return h
    with jax.named_scope("pass3"):
        q_cols = scatter_queries_compact(q_dims, q_vals, arrays.d_active)
        extra_s = res.sparse_residual_scores(arrays.sparse_residual, ids2,
                                             q_cols)
        s3, ids3 = res.reorder_pass(s2, ids2, extra_s, h)
    return s3, ids3, ids1


# ---------------------------------------------------------------------------
# ScoringEngine — thin stateful façade over the jitted search
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class ScoringEngine:
    """Owns the device-resident index + backend choice.

    ``search`` resolves the per-pass candidate counts (static ints, so each
    (h, alpha, beta) pair compiles once) and dispatches the single-jit
    three-pass search.  ``fused`` (default on) lets the Pallas backends take
    the fused scan-and-select pass 1 (DESIGN.md §2.5); turn it off to force
    materialize-then-topk (the A/B baseline benchmarks use)."""
    arrays: IndexArrays
    backend: Backend = Backend.REF
    fused: bool = True

    def __post_init__(self):
        # fail at construction, not at the first search deep inside the
        # kernel wrapper: the packed Pallas kernel's LUT last dim is 16.
        if (self.backend is Backend.PALLAS_PACKED and self.arrays.codes_packed
                and self.arrays.codebooks.num_codes != 16):
            raise ValueError(
                "Backend.PALLAS_PACKED requires l == 16 codewords, got "
                f"l={self.arrays.codebooks.num_codes}; scan packed codes "
                "with smaller codebooks via the ref/onehot-mxu backends")

    @property
    def num_points(self) -> int:
        return self.arrays.num_points

    def candidate_counts(self, h: int, alpha: int, beta: int) -> tuple[int, int]:
        c1 = min(max(alpha * h, h), self.num_points)
        c2 = min(max(beta * h, h), c1)
        return c1, c2

    def search(self, q_dims: jax.Array, q_vals: jax.Array,
               q_dense: jax.Array, *, h: int, alpha: int, beta: int):
        """Three-pass device search.  Returns (scores, ids, pass1_ids) in
        cache-sorted row positions."""
        c1, c2 = self.candidate_counts(h, alpha, beta)
        return three_pass_search(self.arrays, q_dims, q_vals, q_dense,
                                 h=h, c1=c1, c2=c2, backend=self.backend,
                                 fused=self.fused)

    def search_hlo(self, q_dims, q_vals, q_dense, *, h: int, alpha: int,
                   beta: int) -> str:
        """Optimized HLO text of the program ``search`` runs for queries of
        these shapes (arrays or ``jax.ShapeDtypeStruct``) and ``(h, alpha,
        beta)``.  Each instruction's ``metadata={op_name=...}`` names the
        ``three_pass_search`` scope it came from; a profiler trace names
        device operations by the same instruction names."""
        c1, c2 = self.candidate_counts(h, alpha, beta)
        return three_pass_search.lower(
            self.arrays, q_dims, q_vals, q_dense, h=h, c1=c1, c2=c2,
            backend=self.backend, fused=self.fused).compile().as_text()

    def pass1_topk(self, q_dims: jax.Array, q_vals: jax.Array,
                   lut: jax.Array, k: int):
        """Pass-1-only local top-k (the distributed fan-out building block)."""
        if _use_fused_pass1(self.arrays, self.backend, self.fused, k):
            return _fused_pass1_topk(self.arrays, q_dims, q_vals, lut, k,
                                     self.backend)
        scores = pass1_scores(self.arrays, q_dims, q_vals, lut, self.backend)
        return res.topk_candidates(scores, k)


# ---------------------------------------------------------------------------
# Serving hooks (DESIGN.md §5): result-cache fingerprints and the donation
# hook for double-buffered IndexArrays swaps
# ---------------------------------------------------------------------------

def query_fingerprint(q_dims, q_vals, q_dense, *extra) -> str:
    """Content hash of one query (or query batch) for result caching.

    Hashes the raw bytes of the padded sparse query (dims + vals), the dense
    query, and any extra context (search params, index generation) — two
    requests collide only if every input byte agrees, so a cache keyed on
    this digest can never serve a stale or mismatched result.  Host-side
    numpy; meant to run once per request on arrays that are already on host.
    """
    h = hashlib.blake2b(digest_size=16)
    for a in (q_dims, q_vals, q_dense):
        a = np.ascontiguousarray(np.asarray(a))
        h.update(str((a.shape, a.dtype.str)).encode())
        h.update(a.tobytes())
    for e in extra:
        h.update(repr(e).encode())
    return h.hexdigest()


def release_index_arrays(arrays: IndexArrays, *, keep=()) -> int:
    """Donation hook for double-buffered index swaps (DESIGN.md §5).

    Deletes the device buffers of a RETIRED ``IndexArrays`` copy so its HBM
    is reclaimed immediately — the host-side analogue of jit buffer donation
    for a pytree that lives across dispatches rather than inside one.  Leaves
    that also appear in any pytree of ``keep`` (e.g. the replacement arrays
    sharing a codebook, or per-shard views sharing ``head_pos``) are skipped,
    as are non-jax leaves and buffers already deleted.  Returns the number of
    buffers deleted.  Callers must ensure no in-flight computation still
    reads ``arrays`` (QueryService refcounts generations for exactly this).
    """
    keep_ids = {id(leaf) for tree in keep for leaf in jax.tree.leaves(tree)}
    deleted = 0
    for leaf in jax.tree.leaves(arrays):
        if (isinstance(leaf, jax.Array) and id(leaf) not in keep_ids
                and not leaf.is_deleted()):
            leaf.delete()
            deleted += 1
    return deleted
