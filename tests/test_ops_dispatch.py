"""``ops.linear`` is the run-time face of the paper's "automatically
replace all linear layers" feature: callers hand it whatever leaf the
conversion produced and must land on the right kernel.  This pins the
dispatch table — dense jax.Array, bf16 block-sparse, int8 block-sparse,
nibble-packed int4 — and the numerics of each route."""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import make_mask, pack
from repro.core.quant import quantize_weight_int4, quantize_weight_int8
from repro.core.sparse_format import BlockSparseWeight, pack_nibbles
from repro.kernels import ops

K, N = 64, 128
BLOCK = (32, 128)


@pytest.fixture
def xw():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(4, K)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(K, N)).astype(np.float32))
    return x, w


@pytest.fixture
def routes(monkeypatch):
    """Record which matmul entry point ops.linear picks per call."""
    calls = []
    for name in ("dense_matmul", "sparse_matmul", "sparse_matmul_int8"):
        orig = getattr(ops, name)

        def wrapper(*a, _name=name, _orig=orig, **kw):
            calls.append(_name)
            return _orig(*a, **kw)

        monkeypatch.setattr(ops, name, wrapper)
    return calls


def _sparse_bf16(w, sparsity=0.0):
    mask = make_mask(w, sparsity, policy="balanced", block=BLOCK)
    return mask, pack(jnp.where(mask, w, 0).astype(jnp.bfloat16), mask, BLOCK)


def _sparse_int8(w, sparsity=0.5):
    mask = make_mask(w, sparsity, policy="balanced", block=BLOCK)
    q, scale = quantize_weight_int8(jnp.where(mask, w, 0))
    return mask, pack(q, mask, BLOCK, scale=scale)


def _sparse_int4(w, sparsity=0.5):
    mask = make_mask(w, sparsity, policy="balanced", block=BLOCK)
    q, scale = quantize_weight_int4(jnp.where(mask, w, 0))
    sw = pack(q, mask, BLOCK, scale=scale)
    return mask, BlockSparseWeight(sw.bitmap, pack_nibbles(sw.values),
                                   sw.scale, sw.shape, sw.block,
                                   packed4=True)


def test_linear_dense_route(xw, routes):
    x, w = xw
    out = ops.linear(x, w)
    assert routes == ["dense_matmul"]
    np.testing.assert_allclose(np.asarray(out), np.asarray(x @ w),
                               rtol=1e-5, atol=1e-5)


def test_linear_bf16_sparse_route(xw, routes):
    x, w = xw
    mask, sw = _sparse_bf16(w, sparsity=0.0)
    assert not sw.packed4 and sw.values.dtype == jnp.bfloat16
    out = ops.linear(x, sw, out_dtype=jnp.float32)
    assert routes == ["sparse_matmul"]
    expect = x @ jnp.where(mask, w, 0).astype(jnp.bfloat16).astype(
        jnp.float32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=1e-2, atol=1e-2)


def test_linear_int8_sparse_route(xw, routes):
    x, w = xw
    mask, sw = _sparse_int8(w)
    assert sw.values.dtype == jnp.int8 and not sw.packed4
    out = ops.linear(x, sw, out_dtype=jnp.float32)
    assert routes == ["sparse_matmul_int8"]
    expect = np.asarray(x @ jnp.where(mask, w, 0))
    got = np.asarray(out)
    rel = np.abs(got - expect).mean() / (np.abs(expect).mean() + 1e-9)
    assert rel < 0.05, rel


def test_linear_packed4_route(xw, routes):
    x, w = xw
    mask, sw = _sparse_int4(w)
    assert sw.packed4 and sw.values.dtype == jnp.uint8
    out = ops.linear(x, sw, out_dtype=jnp.float32)
    assert routes == ["sparse_matmul_int8"]        # int4 rides the int8 path
    expect = np.asarray(x @ jnp.where(mask, w, 0))
    got = np.asarray(out)
    rel = np.abs(got - expect).mean() / (np.abs(expect).mean() + 1e-9)
    assert rel < 0.15, rel


def test_linear_one_route_per_leaf_type(xw, routes):
    """The dispatch is exhaustive and exclusive: every leaf type takes
    exactly one route per call."""
    x, w = xw
    leaves = [w, _sparse_bf16(w, 0.5)[1], _sparse_int8(w)[1],
              _sparse_int4(w)[1]]
    for leaf in leaves:
        ops.linear(x, leaf)
    assert routes == ["dense_matmul", "sparse_matmul",
                      "sparse_matmul_int8", "sparse_matmul_int8"]


def test_platform_backend_is_the_reference_on_cpu():
    """A CPU process starts on the jnp reference; only a TPU starts on the
    real kernels (and any other platform is refused)."""
    assert ops._platform_backend() == "xla"


def test_kernel_mesh_wraps_kernels_in_shard_map(xw):
    """Under ``kernel_mesh`` every Pallas call traces as a ``shard_map``
    (Mosaic kernels cannot be partitioned by XLA) whose rows follow the
    batch placement, and computes what the bare call computes.  The
    multi-device placement and token parity are checked by
    ``test_serving_sharded.py``."""
    import jax
    from repro.distributed import serving_sharding
    from repro.launch.mesh import make_test_mesh
    x, w = xw
    _, sw = _sparse_bf16(w, sparsity=0.5)
    ctx = serving_sharding.serving_ctx(make_test_mesh(1, 1))
    with ops.backend("interpret"):
        bare = ops.linear(x, sw, out_dtype=jnp.float32)
        with ops.kernel_mesh(ctx):
            jaxpr = str(jax.make_jaxpr(
                lambda x: ops.linear(x, sw, out_dtype=jnp.float32))(x))
            wrapped = ops.linear(x, sw, out_dtype=jnp.float32)
    assert "shard_map" in jaxpr and "'data'" in jaxpr
    assert ops._SHARD is None
    np.testing.assert_array_equal(np.asarray(wrapped), np.asarray(bare))
