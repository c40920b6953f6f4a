"""Product quantization for the dense component (paper §2.3, §4.1, §6).

Codebooks are learned with Lloyd's k-means per subspace (paper cites [17] QUIPS;
we use the reconstruction-MSE objective with optional whitening, which §4.1.3
notes is the QUIPS special case where query distribution == datapoint
distribution).

Two indices are built (paper §6):
  * data index   — K_U = d^D/2 subspaces, l = 16 codewords (4 bits / 2 dims),
                   scanned with the LUT16 kernel (kernels/lut16.py);
  * residual idx — K_V = d^D  subspaces, l = 256 ⇒ per-dimension scalar
                   quantization of the residual at 8 bits (§6.1.1).
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.lut16 import pack_codes, unpack_codes  # noqa: F401

__all__ = [
    "PQCodebooks", "train_codebooks", "pq_encode", "pq_decode",
    "adc_lut", "adc_scores_ref", "ScalarQuant", "scalar_quantize",
    "scalar_dequantize", "scalar_quantize_rows", "encode_rows",
    "whitening_transform", "pack_codes", "unpack_codes",
]


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class PQCodebooks:
    """K subspace codebooks, all subspaces the same width p = d^D / K.

    centers: (K, l, p) float32.
    """
    centers: jax.Array

    @property
    def num_subspaces(self) -> int:
        return self.centers.shape[0]

    @property
    def num_codes(self) -> int:
        return self.centers.shape[1]

    @property
    def sub_dim(self) -> int:
        return self.centers.shape[2]


def _split_subspaces(x: jax.Array, k: int) -> jax.Array:
    """(N, d) -> (N, K, p): contiguous subvector blocks (paper Eq. 2)."""
    n, d = x.shape
    assert d % k == 0, f"d={d} not divisible by K={k}"
    return x.reshape(n, k, d // k)


@partial(jax.jit, static_argnums=(1, 2))
def _kmeans_one_subspace(x: jax.Array, l: int, iters: int,
                         seed: jax.Array) -> jax.Array:
    """Lloyd's k-means on (N, p) -> (l, p) centers.  kmeans++-lite init:
    random distinct points, deterministic under `seed` — a traced int32, so
    every subspace of one build shares one compiled program."""
    n, p = x.shape
    key = jax.random.PRNGKey(seed)
    idx = jax.random.choice(key, n, shape=(l,), replace=False)
    centers = x[idx]

    def step(centers, _):
        # (N, l) squared distances via ||x||^2 - 2 x.c + ||c||^2 ; x-term constant.
        d2 = (
            jnp.sum(centers * centers, axis=1)[None, :]
            - 2.0 * x @ centers.T
        )
        assign = jnp.argmin(d2, axis=1)
        one_hot = jax.nn.one_hot(assign, l, dtype=x.dtype)        # (N, l)
        counts = one_hot.sum(axis=0)                              # (l,)
        sums = one_hot.T @ x                                      # (l, p)
        new = sums / jnp.maximum(counts, 1.0)[:, None]
        # Keep old center for empty clusters.
        new = jnp.where((counts > 0)[:, None], new, centers)
        return new, None

    centers, _ = jax.lax.scan(step, centers, None, length=iters)
    return centers


def train_codebooks(x_dense: jax.Array, num_subspaces: int, num_codes: int = 16,
                    iters: int = 12, seed: int = 0,
                    sample: int | None = 65536) -> PQCodebooks:
    """Learn K codebooks by independent per-subspace k-means (paper §2.3)."""
    x = jnp.asarray(x_dense, jnp.float32)
    if sample is not None and x.shape[0] > sample:
        sel = jax.random.choice(jax.random.PRNGKey(seed + 101), x.shape[0],
                                shape=(sample,), replace=False)
        x = x[sel]
    d = x.shape[1]
    assert d % num_subspaces == 0, f"d={d} not divisible by K={num_subspaces}"
    p = d // num_subspaces
    centers = []
    for k in range(num_subspaces):
        # column slices of x, not an (N, K, p) split: its minor axis of p
        # would be padded to 128 lanes on the TPU
        centers.append(_kmeans_one_subspace(x[:, k * p:(k + 1) * p],
                                            num_codes, iters,
                                            jnp.int32(seed + k)))
    return PQCodebooks(centers=jnp.stack(centers))                # (K, l, p)


@jax.jit
def pq_encode(x_dense: jax.Array, codebooks: PQCodebooks) -> jax.Array:
    """phi_PQ: (N, d) -> (N, K) uint8 codes (argmin L2 per subspace).

    Rows are encoded in blocks of 16k, so the (rows, K, l) distance
    intermediate stays ~100 MiB at K=100 however large N is."""
    c = codebooks.centers                                         # (K, l, p)
    subs = _split_subspaces(jnp.asarray(x_dense, jnp.float32), c.shape[0])
    c2 = jnp.sum(c * c, axis=2)                                   # (K, l)

    def encode_row(sub):                                          # (K, p)
        # (K, l) squared distance; x-term constant wrt argmin.
        d2 = c2 - 2.0 * jnp.einsum("kp,klp->kl", sub, c)
        return jnp.argmin(d2, axis=1).astype(jnp.uint8)

    return jax.lax.map(encode_row, subs, batch_size=16384)


@jax.jit
def pq_decode(codes: jax.Array, codebooks: PQCodebooks) -> jax.Array:
    """Reconstruct (N, d) from (N, K) codes.

    One gather from the flat (K*l*p,) codebook table with (N, d) indices:
    an (N, K, p) intermediate would have p (2 at K = d/2) as its minor
    axis, which the TPU pads to 128 lanes (51 GB at 1M rows, K=100)."""
    c = codebooks.centers
    k, l, p = c.shape
    dim = jnp.arange(k * p)
    base = (dim // p) * (l * p) + dim % p                         # (d,)
    codes_d = jnp.repeat(codes.astype(jnp.int32), p, axis=1)      # (N, d)
    return c.reshape(-1)[base[None, :] + codes_d * p]


@jax.jit
def adc_lut(q_dense: jax.Array, codebooks: PQCodebooks) -> jax.Array:
    """Asymmetric LUT (paper §4.1.1): T[q][k][c] = q^(k) · U^(k)_c.

    q_dense: (Q, d) or (d,).  Returns (Q, K, l) (or (K, l)) float32.
    """
    c = codebooks.centers                                         # (K, l, p)
    single = q_dense.ndim == 1
    q = jnp.atleast_2d(jnp.asarray(q_dense, jnp.float32))
    qs = _split_subspaces(q, c.shape[0])                          # (Q, K, p)
    lut = jnp.einsum("qkp,klp->qkl", qs, c)
    return lut[0] if single else lut


@jax.jit
def adc_scores_ref(codes: jax.Array, lut: jax.Array) -> jax.Array:
    """Reference ADC scan: (N, K) codes × (Q, K, l) LUT -> (Q, N) scores.

    Pure-jnp oracle for the LUT16 Pallas kernel (kernels/ref.py re-exports)."""
    single = lut.ndim == 2
    lut3 = lut[None] if single else lut                           # (Q, K, l)
    gathered = jnp.take_along_axis(
        lut3[:, None],                                            # (Q, 1, K, l)
        codes[None, :, :, None].astype(jnp.int32),                # (1, N, K, 1)
        axis=3,
    )[..., 0]                                                     # (Q, N, K)
    out = gathered.sum(axis=-1)
    return out[0] if single else out


# ---------------------------------------------------------------------------
# Scalar quantization — the dense residual index (K_V = d^D, l = 256, §6.1.1)
# ---------------------------------------------------------------------------

@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class ScalarQuant:
    """Per-dimension affine int8 quantization: x ≈ scale * q + zero."""
    q: jax.Array          # (N, d) int8
    scale: jax.Array      # (d,) float32
    zero: jax.Array       # (d,) float32


@jax.jit
def scalar_quantize(x: jax.Array) -> ScalarQuant:
    x = jnp.asarray(x, jnp.float32)
    lo = x.min(axis=0)
    hi = x.max(axis=0)
    scale = jnp.maximum(hi - lo, 1e-12) / 255.0
    zero = lo
    q = jnp.clip(jnp.round((x - zero) / scale), 0, 255) - 128
    return ScalarQuant(q=q.astype(jnp.int8), scale=scale, zero=zero)


@jax.jit
def scalar_dequantize(sq: ScalarQuant) -> jax.Array:
    return (sq.q.astype(jnp.float32) + 128.0) * sq.scale + sq.zero


def scalar_quantize_rows(x: np.ndarray, scale: np.ndarray,
                         zero: np.ndarray) -> np.ndarray:
    """Quantize NEW rows with FROZEN affine params (delta-shard insert path,
    DESIGN.md §6): the streaming index must keep serving the main
    generation's ``scale``/``zero``, so inserted residual rows are clamped
    into the existing grid instead of re-deriving it.  Same rounding as
    ``scalar_quantize`` (half-to-even), host-side numpy.  (M, d) -> int8."""
    x = np.asarray(x, np.float32)
    scale = np.asarray(scale, np.float32)
    zero = np.asarray(zero, np.float32)
    q = np.clip(np.round((x - zero) / scale), 0, 255) - 128
    return q.astype(np.int8)


def encode_rows(x_dense: np.ndarray, codebooks: PQCodebooks, *,
                pack: bool = False) -> np.ndarray:
    """Encode-on-insert: PQ-encode NEW dense rows against the FROZEN
    codebooks of the serving index (no retraining until compaction,
    DESIGN.md §6).  pack=True returns the rows packed two codes per byte —
    the delta shard's append unit — with pack_codes' odd-K phantom nibble.
    (M, d) -> (M, K) uint8, or (M, ceil(K/2)) packed."""
    codes = np.asarray(pq_encode(jnp.asarray(x_dense, jnp.float32),
                                 codebooks))
    return pack_codes(codes) if pack else codes


def whitening_transform(x_dense: jax.Array, eps: float = 1e-4):
    """P = Cov^{-1/2}(X^D) (paper §4.1.3).  Returns (P, P^{-T}) so that data is
    multiplied by P and queries by (P^{-1})^T, preserving inner products."""
    x = np.asarray(x_dense, np.float64)
    cov = np.cov(x, rowvar=False) + eps * np.eye(x.shape[1])
    evals, evecs = np.linalg.eigh(cov)
    p = evecs @ np.diag(evals ** -0.5) @ evecs.T
    p_inv_t = evecs @ np.diag(evals ** 0.5) @ evecs.T            # symmetric
    return jnp.asarray(p, jnp.float32), jnp.asarray(p_inv_t, jnp.float32)
