"""Compile the main path's kernels for a described TPU v5e (no chip needed).

Interpret mode accepts block shapes and vector ops that the chip's compiler
(Mosaic) refuses, so these tests hand the real-size programs to the TPU
compiler for a ``v5e:2x2`` topology that is described, not attached.  This
is the only test file that describes the chip: the topology is made inside a
module fixture, never at import time, so every pytest-xdist worker collects
the same tests and only the worker that runs this file loads the TPU
library.
"""

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

N = 1_000_000          # one chip's shard (chip_smoke.py's size)
Q = 32                 # QueryService's largest batch bucket
C1 = 200               # pass-1 overfetch: alpha=20 x h=10


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # a compile for a described chip can be cached but never read back here
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.fixture
def compiled_kernels(monkeypatch):
    """The process's backend is the CPU, so the op wrappers would pick
    interpret mode; the described chip compiles the kernels themselves."""
    from repro.kernels import ops
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    return ops


@pytest.mark.parametrize("q", [1, Q])
@pytest.mark.parametrize("fused", [False, True], ids=["materialize", "fused"])
@pytest.mark.parametrize("packed", [False, True], ids=["unpacked", "packed"])
@pytest.mark.parametrize("k_sub", [100, 384])
def test_lut16_compiles_for_v5e(one_chip, compiled_kernels, k_sub, packed,
                                fused, q):
    """Pass 1 as the engine routes it — the fused kernel, or the
    materialize kernel plus ``lax.top_k`` — packed and unpacked, at K=100
    (QuerySim's 200 dense dims) and K=384 (768-d passage embeddings), 1M
    rows, for the smallest and largest serving bucket."""
    kc = k_sub // 2 if packed else k_sub
    fn = jax.jit(lambda c, t, b: compiled_kernels.lut16_adc_topk(
        c, t, C1, bias=b, packed=packed, fused=fused))
    compiled = fn.lower(_spec(one_chip, (N, kc), jnp.uint8),
                        _spec(one_chip, (q, k_sub, 16), jnp.float32),
                        _spec(one_chip, (q, N), jnp.float32)).compile()
    _assert_kernel(compiled)


def test_block_sparse_head_compiles_for_v5e(one_chip):
    """The BCSR head kernel at 1M rows, one 128x128 tile per row block."""
    from repro.kernels.block_sparse import block_sparse_matmul_pallas
    nb = -(-N // 128)
    fn = jax.jit(lambda q, t, p, c: block_sparse_matmul_pallas(
        q, t, p, c, bq=8, max_steps=1, interpret=False))
    compiled = fn.lower(_spec(one_chip, (Q, 128), jnp.float32),
                        _spec(one_chip, (nb, 128, 128), jnp.float32),
                        _spec(one_chip, (nb + 1,), jnp.int32),
                        _spec(one_chip, (nb,), jnp.int32)).compile()
    _assert_kernel(compiled)


@pytest.mark.parametrize("step", ["pq_encode", "pq_decode"])
def test_pq_build_steps_fit_v5e(one_chip, step):
    """The build's device steps over a whole 1M-row shard (K=100, p=2) fit
    one chip: an (N, K, p) intermediate would pad p to 128 lanes (51 GB)."""
    from repro.core import pq
    k_sub, d_dense = 100, 200
    cb = pq.PQCodebooks(centers=_spec(one_chip, (k_sub, 16, d_dense // k_sub),
                                      jnp.float32))
    x = (_spec(one_chip, (N, d_dense), jnp.float32) if step == "pq_encode"
         else _spec(one_chip, (N, k_sub), jnp.uint8))
    _assert_fits(getattr(pq, step).lower(x, cb).compile())


def _assert_fits(compiled):
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < 16e9 * 0.9, used


def _index_shapes(*, packed: bool, d_active: int = 9000, k_sub: int = 100,
                  d_dense: int = 200, inv_len: int = 192, res_len: int = 32):
    """Abstract IndexArrays of a 1M-row QuerySim-shaped shard (the sizes
    HybridIndex.build gives for chip_smoke.py's data), from eval_shape."""
    from repro.core.engine import IndexArrays
    from repro.core.pq import PQCodebooks, ScalarQuant
    from repro.core.sparse_index import (PaddedInvertedIndex,
                                         PaddedSparseRows, TileSparseHead)
    n_pad = -(-N // 128) * 128
    nb = n_pad // 128

    def build():
        z = jnp.zeros
        return IndexArrays(
            codebooks=PQCodebooks(centers=z((k_sub, 16, d_dense // k_sub))),
            codes=z((N, k_sub // 2 if packed else k_sub), jnp.uint8),
            inv_index=PaddedInvertedIndex(
                rows=z((d_active, inv_len), jnp.int32),
                vals=z((d_active, inv_len)), num_points=N),
            head=TileSparseHead(block=z((n_pad, 128)),
                                occupancy=z((nb, 1), bool),
                                head_dims=z((128,), jnp.int32),
                                block_rows=128, block_cols=128),
            head_pos=z((d_active + 1,), jnp.int32),
            head_tiles=z((nb, 128, 128)), head_ptr=z((nb + 1,), jnp.int32),
            head_col=z((nb,), jnp.int32),
            dense_residual=ScalarQuant(q=z((N, d_dense), jnp.int8),
                                       scale=z((d_dense,)),
                                       zero=z((d_dense,))),
            sparse_residual=PaddedSparseRows(
                cols=z((N, res_len), jnp.int32), vals=z((N, res_len))),
            num_points=N, d_active=d_active, head_max_steps=1,
            codes_packed=packed)

    return jax.eval_shape(build)


@pytest.mark.parametrize("backend", ["pallas", "pallas-packed"])
def test_three_pass_search_compiles_for_v5e(one_chip, compiled_kernels,
                                           backend):
    """The whole jitted three-pass search for one chip at 1M rows, Q=32,
    with the fused pass-1 kernel: it compiles, carries the Pallas kernels,
    and fits the chip's 16 GB of HBM."""
    from repro.core.engine import Backend, three_pass_search
    be = Backend.from_name(backend)
    arrays = jax.tree.map(
        lambda s: _spec(one_chip, s.shape, s.dtype),
        _index_shapes(packed=be is Backend.PALLAS_PACKED))
    nq, d_dense = 256, 200
    compiled = three_pass_search.lower(
        arrays, _spec(one_chip, (Q, nq), jnp.int32),
        _spec(one_chip, (Q, nq), jnp.float32),
        _spec(one_chip, (Q, d_dense), jnp.float32),
        h=10, c1=C1, c2=50, backend=be, fused=True).compile()
    _assert_kernel(compiled)
    _assert_fits(compiled)
