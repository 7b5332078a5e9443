"""Pallas matmul kernels vs pure-jnp oracles — shape/dtype sweeps in
interpret mode (the kernel body executes on CPU)."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import pack, make_mask, quantize_weight_int8
from repro.kernels import ops, ref
from repro.kernels.dense_matmul import dense_matmul_pallas
from repro.kernels.sparse_matmul import sparse_matmul_pallas
from repro.kernels.sparse_gemv import sparse_gemv_pallas
from repro.kernels.sparse_matmul_int8 import sparse_matmul_int8_pallas
from repro.core.quant import quantize_act_int8


def rand(shape, seed=0, dtype=np.float32):
    return jnp.asarray(np.random.default_rng(seed).normal(
        size=shape).astype(dtype))


def make_sparse(k, n, sparsity=0.5, block=(128, 128), dtype=jnp.float32,
                seed=0, policy="balanced"):
    w = rand((k, n), seed=seed).astype(dtype)
    mask = make_mask(w.astype(jnp.float32), sparsity, policy, block)
    return jnp.where(mask, w, 0), pack(w, mask, block)


@pytest.mark.parametrize("block", [(256, 128), (16, 32), (64, 96),
                                   (128, 96)])
@pytest.mark.parametrize("sparsity", [0.0, 0.5])
def test_decompress_block_matches_unpack(block, sparsity):
    """The in-kernel decompression rebuilds each block exactly as
    ``unpack`` does, for widths that divide 128 and for head_dim 96, whose
    bitmap rows straddle two rows of the 128-lane word view."""
    from repro.core.sparse_format import unpack
    from repro.kernels.common import decompress_block, tile_view
    bk, bn = block
    _, sw = make_sparse(bk, 2 * bn, sparsity, block, seed=3)
    words, vals = tile_view(sw.bitmap), tile_view(sw.values)
    dense = np.asarray(unpack(sw))
    for j in range(2):
        got = decompress_block(words[j], vals[j], bk, bn)
        np.testing.assert_array_equal(np.asarray(got),
                                      dense[:, j * bn:(j + 1) * bn])


@pytest.mark.parametrize("m,k,n", [(8, 128, 128), (16, 256, 384),
                                   (128, 512, 256), (5, 200, 100)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_dense_matmul(m, k, n, dtype):
    x = rand((m, k), 1).astype(dtype)
    w = rand((k, n), 2).astype(dtype)
    out = dense_matmul_pallas(x, w, block=(128, 128, 128), interpret=True)
    expect = ref.dense_matmul_ref(x, w)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32),
                               rtol=2e-2 if dtype == jnp.bfloat16 else 1e-5,
                               atol=1e-2)


@pytest.mark.parametrize("m,k,n", [(16, 128, 128), (32, 384, 256),
                                   (128, 256, 512)])
@pytest.mark.parametrize("sparsity", [0.0, 0.5, 0.9])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_sparse_matmul_sweep(m, k, n, sparsity, dtype):
    x = rand((m, k), 3).astype(dtype)
    wd, sw = make_sparse(k, n, sparsity, dtype=dtype, seed=4)
    out = sparse_matmul_pallas(x, sw, tm=16, interpret=True)
    expect = jnp.dot(x.astype(jnp.float32),
                     wd.astype(jnp.float32)).astype(dtype)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32),
                               rtol=3e-2 if dtype == jnp.bfloat16 else 1e-4,
                               atol=3e-2)


def test_sparse_matmul_global_policy():
    x = rand((8, 256), 5)
    wd, sw = make_sparse(256, 256, 0.6, seed=6, policy="global")
    out = sparse_matmul_pallas(x, sw, tm=8, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(x @ wd),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("m", [1, 2, 8])
def test_sparse_gemv(m):
    x = rand((m, 384), 7)
    wd, sw = make_sparse(384, 256, 0.5, seed=8)
    out = sparse_gemv_pallas(x, sw, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(x @ wd),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("m,k,n", [(16, 128, 128), (64, 256, 384)])
@pytest.mark.parametrize("sparsity", [0.0, 0.5])
def test_sparse_int8(m, k, n, sparsity):
    x = rand((m, k), 9)
    w = rand((k, n), 10)
    mask = make_mask(w, sparsity, "balanced", (128, 128))
    q, scale = quantize_weight_int8(jnp.where(mask, w, 0))
    sw = pack(q, mask, (128, 128), scale=scale)
    xq, sx = quantize_act_int8(x)
    out = sparse_matmul_int8_pallas(xq, sx, sw, tm=16, interpret=True)
    expect = ref.sparse_matmul_int8_ref(x, sw)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=1e-4, atol=1e-3)
    # and the whole int8 path approximates the f32 product
    dense = np.asarray(x @ jnp.where(mask, w, 0))
    rel = np.abs(np.asarray(out) - dense).mean() / np.abs(dense).mean()
    assert rel < 0.05


def test_ops_dispatch_backends():
    x = rand((4, 256), 11)
    wd, sw = make_sparse(256, 128, 0.5, seed=12)
    with ops.backend("xla"):
        a = ops.sparse_matmul(x, sw)
    with ops.backend("interpret"):
        b = ops.sparse_matmul(x, sw)   # m<=8 -> gemv kernel
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-4, atol=1e-4)


def test_linear_dispatch_types():
    x = rand((4, 256), 13)
    w = rand((256, 128), 14)
    wd, sw = make_sparse(256, 128, 0.5, seed=14)
    assert ops.linear(x, w).shape == (4, 128)
    assert ops.linear(x, sw).shape == (4, 128)


def test_leading_batch_dims():
    x = rand((2, 3, 256), 15)
    wd, sw = make_sparse(256, 128, 0.5, seed=16)
    with ops.backend("xla"):
        out = ops.sparse_matmul(x, sw)
    assert out.shape == (2, 3, 128)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(x.reshape(6, 256) @ wd).reshape(2, 3, 128),
        rtol=1e-4, atol=1e-4)
