"""Cache-sorted block-sparse scoring as a Pallas TPU kernel (paper §3.1-3.3,
TPU-adapted — see DESIGN.md §2).

The paper's cache-sorted inverted index minimizes 64-byte accumulator
cache-lines touched.  The TPU analogue: store the (N × d_head) head-dim
matrix as **BCSR over (block_rows × block_cols) VMEM tiles**, keeping *only
nonzero tiles* in HBM.  Cache sorting (Algorithm 1) is exactly the
permutation that minimizes the number of stored/streamed tiles, so the
paper's E[C_sort] cost model (Eq. 5 with B = tile rows) directly predicts
this kernel's DMA traffic.

Scalar-prefetch drives the gather: the grid walks (query-block, row-block,
step) and the per-row-block tile list is resolved through prefetched
``tile_ptr``/``tile_col`` arrays inside the BlockSpec index_maps — i.e. the
kernel *never touches* zero tiles, matching the paper's skipped cache-lines.

Contract (matches kernels/ref.py::block_sparse_ref):
  q       (Q, D) float32          dense query head-subvectors
  tiles   (T, Br, Bc) float32     nonzero tiles, row-block-major
  tile_ptr(NB + 1,) int32         CSR offsets over row-blocks
  tile_col(T,) int32              column-block index of each tile
  out     (Q, N) float32          q @ X_head^T  (X reassembled from tiles)
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["block_sparse_matmul_pallas", "dense_to_bcsr",
           "inverted_value_forward_pallas"]


def dense_to_bcsr(x: np.ndarray, br: int, bc: int):
    """(N, D) -> (tiles (T,br,bc), tile_ptr (N/br+1,), tile_col (T,)).

    T == number of nonzero tiles == the object cache sorting minimizes."""
    n, d = x.shape
    assert n % br == 0 and d % bc == 0, (x.shape, br, bc)
    nb, db = n // br, d // bc
    view = x.reshape(nb, br, db, bc).transpose(0, 2, 1, 3)     # (nb, db, br, bc)
    nz = np.abs(view).max(axis=(2, 3)) > 0                     # (nb, db)
    tiles, cols, ptr = [], [], [0]
    for i in range(nb):
        for j in np.flatnonzero(nz[i]):
            tiles.append(view[i, j])
            cols.append(j)
        ptr.append(len(tiles))
    if not tiles:                                              # fully zero
        tiles = [np.zeros((br, bc), x.dtype)]
        cols = [0]
        ptr = [0] * (nb + 1)
    return (np.stack(tiles).astype(np.float32),
            np.asarray(ptr, np.int32), np.asarray(cols, np.int32))


def _kernel(ptr_ref, col_ref, q_ref, tiles_ref, out_ref):
    nb_idx = pl.program_id(1)
    step = pl.program_id(2)

    @pl.when(step == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    t = ptr_ref[nb_idx] + step
    valid = t < ptr_ref[nb_idx + 1]

    @pl.when(valid)
    def _acc():
        tile = tiles_ref[0]                                   # (Br, Bc)
        qv = q_ref[...]                                       # (bq, Bc)
        out_ref[...] += jax.lax.dot_general(
            qv, tile, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("bq", "max_steps", "interpret"))
def block_sparse_matmul_pallas(q: jax.Array, tiles: jax.Array,
                               tile_ptr: jax.Array, tile_col: jax.Array,
                               *, bq: int = 8, max_steps: int = 1,
                               interpret: bool) -> jax.Array:
    """q (Q, D) × BCSR head matrix -> (Q, N).  Q % bq == 0 (ops.py pads).

    ``max_steps`` bounds the per-row-block tile count (grid dim 2); pass the
    true max (host-computed from tile_ptr) for a tight grid — extra steps are
    masked out, zero tiles are never fetched either way."""
    qn, d = q.shape
    t_total, br, bc = tiles.shape
    nb = tile_ptr.shape[0] - 1
    n = nb * br
    assert d % bc == 0 and qn % bq == 0
    max_steps = max(int(max_steps), 1)

    grid = (qn // bq, nb, max_steps)

    def q_map(iq, jn, s, ptr, col):
        t = jnp.minimum(ptr[jn] + s, t_total - 1)
        return (iq, col[t])

    def tiles_map(iq, jn, s, ptr, col):
        t = jnp.minimum(ptr[jn] + s, t_total - 1)
        return (t, 0, 0)

    def out_map(iq, jn, s, ptr, col):
        return (iq, jn)

    return pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((bq, bc), q_map),
                pl.BlockSpec((1, br, bc), tiles_map),
            ],
            out_specs=pl.BlockSpec((bq, br), out_map),
        ),
        out_shape=jax.ShapeDtypeStruct((qn, n), jnp.float32),
        interpret=interpret,
    )(tile_ptr, tile_col, q, tiles)


# ---------------------------------------------------------------------------
# Value-forward inverted-index traversal (SINDI-motivated; DESIGN.md §2.5)
# ---------------------------------------------------------------------------

def _vf_kernel(ptr_ref, rows_ref, qidx_ref, contrib_ref, out_ref, *,
               bq: int, bn: int, chunk: int, nb1: int):
    """Consume one chunk of the (row, query, contribution) stream.

    The stream is row-sorted per (query-block, row-block), so each chunk
    lands entirely in the current (bq, bn) output tile: a query one-hot
    weighted by the contributions (bq, chunk) contracted against a local-row
    one-hot (chunk, bn) scatter-adds the whole chunk on the MXU — the
    value-forward replacement for the (Q, nq, L_max) gather + (Q, N)
    scatter-add of score_inverted."""
    b = pl.program_id(0)
    j = pl.program_id(1)
    s = pl.program_id(2)

    @pl.when(s == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    start = ptr_ref[b * nb1 + j]
    end = ptr_ref[b * nb1 + j + 1]

    @pl.when(start + s < end)
    def _acc():
        rows = rows_ref[0]                                     # (chunk,) local
        qi = qidx_ref[0]                                       # (chunk,)
        cv = contrib_ref[0]                                    # (chunk,)
        qsel = (qi[None, :] ==
                jax.lax.broadcasted_iota(jnp.int32, (bq, chunk), 0)
                ).astype(jnp.float32) * cv[None, :]            # (bq, chunk)
        rsel = (rows[:, None] ==
                jax.lax.broadcasted_iota(jnp.int32, (chunk, bn), 1)
                ).astype(jnp.float32)                          # (chunk, bn)
        out_ref[...] += jax.lax.dot_general(
            qsel, rsel, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)


@functools.partial(jax.jit,
                   static_argnames=("bq", "bn", "chunk", "num_row_blocks",
                                    "max_steps", "interpret"))
def inverted_value_forward_pallas(ptr: jax.Array, rows: jax.Array,
                                  qidx: jax.Array, contrib: jax.Array, *,
                                  bq: int, bn: int, chunk: int,
                                  num_row_blocks: int, max_steps: int,
                                  interpret: bool) -> jax.Array:
    """Value-forward inverted scoring over a host-planned stream.

    ptr (QB*(NB+1),) int32 chunk offsets per (query-block, row-block) —
    scalar-prefetched so the BlockSpec index maps stream exactly the chunks
    each tile owns; rows/qidx/contrib (QB, P_pad): block-LOCAL row ids
    (pad = bn, matches nothing), query index within the block (pad 0), and
    q_val*posting_val contributions (pad 0).  Returns
    (QB*bq, num_row_blocks*bn) f32 scores; callers slice to (Q, N).

    Built by ``core.sparse_index.build_value_forward_stream``; wrapped by
    ``kernels.ops.score_inverted_vf``."""
    qb, p_pad = rows.shape
    assert p_pad % chunk == 0 and p_pad > 0, (p_pad, chunk)
    total_chunks = p_pad // chunk
    nb1 = num_row_blocks + 1
    grid = (qb, num_row_blocks, max(int(max_steps), 1))

    def stream_map(b, j, s, ptr):
        c = jnp.minimum(ptr[b * nb1 + j] + s, total_chunks - 1)
        return (b, c)

    def out_map(b, j, s, ptr):
        return (b, j)

    return pl.pallas_call(
        functools.partial(_vf_kernel, bq=bq, bn=bn, chunk=chunk, nb1=nb1),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, chunk), stream_map),
                pl.BlockSpec((1, chunk), stream_map),
                pl.BlockSpec((1, chunk), stream_map),
            ],
            out_specs=pl.BlockSpec((bq, bn), out_map),
        ),
        out_shape=jax.ShapeDtypeStruct((qb * bq, num_row_blocks * bn),
                                       jnp.float32),
        interpret=interpret,
    )(ptr, rows, qidx, contrib)
