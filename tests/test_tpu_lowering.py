"""Compile the main path's Pallas kernels for a TPU v5e at qwen3-0.6b widths.

Interpret mode (the parity tests) cannot vouch for what only the Mosaic
compiler checks: block shapes against the (8, 128) tiling, the primitives
it lowers, scalar-prefetch operands, the VMEM limit.  These tests compile
each kernel for a *described* v5e chip — the TPU compiler is installed, no
chip is attached — and require the compiled program to call the kernel
(``tpu_custom_call``).  Nothing runs, so they say nothing about results.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and pytest-xdist imports every test
file in every worker.  The TPU library must be installed: a test fails,
never skips, when it is not.  The persistent compilation cache is off here, since
a compile for a described chip is written to it but cannot be read back.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.sparse_format import (BlockSparseWeight, balanced_capacity,
                                      packed_spec)
from repro.kernels.dense_matmul import dense_matmul_pallas
from repro.kernels.sparse_attention import (
    sparse_decode_attention_fused_pallas, sparse_decode_attention_pallas)
from repro.kernels.sparse_gemv import sparse_gemv_pallas
from repro.kernels.sparse_matmul import sparse_matmul_pallas
from repro.kernels.sparse_matmul_int4 import sparse_matmul_int4_pallas
from repro.kernels.sparse_matmul_int8 import sparse_matmul_int8_pallas

# qwen3-0.6b: d_model 1024, d_ff 3072, 8 KV heads x GQA group 2, head_dim
# 128, vocab 151936; weights in (256, 128) blocks at 50%, KV in (128, 128)
# blocks at 30% (K) / 50% (V) sparsity
LINEAR_SHAPES = [(1024, 3072), (3072, 1024)]
HKV, G, D, BS = 8, 2, 128, 128
SLOTS, PREFIX_BLOCKS, TAIL, N_PHYS = 8, 4, 128, 40


@pytest.fixture(scope="module")
def topo():
    # needs libtpu (the TPU compiler): without it these tests fail, they
    # never skip
    from jax.experimental import topologies
    return topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _compile_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def _weight(sharding, k, n, mode):
    int_mode = mode in ("int8", "int4")
    ps = packed_spec(k, n, 0.5, dtype=jnp.int8 if int_mode else jnp.bfloat16,
                     with_scale=int_mode)
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,  # noqa: E731
                                                    sharding=sharding)
    values = sds(ps.values.shape, ps.values.dtype)
    if mode == "int4":
        values = sds(ps.values.shape[:-1] + (ps.values.shape[-1] // 2,),
                     jnp.uint8)
    return BlockSparseWeight(
        sds(ps.bitmap.shape, jnp.uint32), values,
        sds(ps.scale.shape, jnp.float32) if int_mode else None,
        ps.shape, ps.block, packed4=mode == "int4")


@pytest.mark.parametrize("k,n", LINEAR_SHAPES)
def test_sparse_gemv_compiles(one_chip, k, n):
    x = jax.ShapeDtypeStruct((8, k), jnp.bfloat16, sharding=one_chip)
    txt = _compile_text(
        lambda x, w: sparse_gemv_pallas(x, w, interpret=False),
        x, _weight(one_chip, k, n, "bf16"))
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("k,n", LINEAR_SHAPES)
@pytest.mark.parametrize("mode", ["bf16", "int8", "int4"])
def test_sparse_matmul_lowers_for_tpu(one_chip, mode, k, n):
    w = _weight(one_chip, k, n, mode)
    if mode == "bf16":
        x = jax.ShapeDtypeStruct((256, k), jnp.bfloat16, sharding=one_chip)
        txt = _compile_text(
            lambda x, w: sparse_matmul_pallas(x, w, interpret=False), x, w)
    else:
        kernel = (sparse_matmul_int8_pallas if mode == "int8"
                  else sparse_matmul_int4_pallas)
        xq = jax.ShapeDtypeStruct((256, k), jnp.int8, sharding=one_chip)
        sx = jax.ShapeDtypeStruct((256,), jnp.float32, sharding=one_chip)
        txt = _compile_text(
            lambda xq, sx, w: kernel(xq, sx, w, interpret=False), xq, sx, w)
    assert "tpu_custom_call" in txt


def test_dense_unembed_compiles(one_chip):
    """The tied-embedding unembed: [8, 1024] @ [1024, 151936] to f32."""
    x = jax.ShapeDtypeStruct((8, 1024), jnp.bfloat16, sharding=one_chip)
    w = jax.ShapeDtypeStruct((1024, 151936), jnp.bfloat16, sharding=one_chip)
    txt = _compile_text(
        lambda x, w: dense_matmul_pallas(x, w, out_dtype=jnp.float32,
                                         interpret=False), x, w)
    assert "tpu_custom_call" in txt


def _kv_operands(sharding, paged, d=D):
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,  # noqa: E731
                                                    sharding=sharding)
    ck = balanced_capacity(0.7, (BS, d))
    cv = balanced_capacity(0.5, (BS, d))
    lead = (N_PHYS, HKV) if paged else (SLOTS, HKV, PREFIX_BLOCKS)
    words = BS * d // 32
    return (sds(lead + (words,), jnp.uint32), sds(lead + (ck,), jnp.bfloat16),
            sds(lead + (words,), jnp.uint32), sds(lead + (cv,), jnp.bfloat16))


@pytest.mark.parametrize("paged", [False, True], ids=["flat", "paged"])
@pytest.mark.parametrize("qn", [1, 5])
def test_fused_decode_attention_compiles(one_chip, paged, qn):
    """Fused prefix+tail flash-decode at a decode tick (Q=1) and a
    speculative verify panel (Q=5), with per-slot lengths and (paged) the
    block table as scalar-prefetch operands."""
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,  # noqa: E731
                                                    sharding=one_chip)
    q = sds((SLOTS, HKV, qn * G, D), jnp.bfloat16)
    tail = sds((SLOTS, HKV, TAIL, D), jnp.bfloat16)
    lens = sds((SLOTS,), jnp.int32)
    table = sds((SLOTS, PREFIX_BLOCKS), jnp.int32) if paged else None

    def fn(q, kb, kv, vb, vv, kt, vt, nb, tl, tbl):
        return sparse_decode_attention_fused_pallas(
            q, kb, kv, vb, vv, kt, vt, nb, tl, tbl, bs=BS, sm_scale=D ** -0.5,
            interpret=False, group=G)
    txt = _compile_text(fn, q, *_kv_operands(one_chip, paged), tail, tail,
                        lens, lens, table)
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("d", [64, 96])
def test_fused_decode_attention_narrow_head_compiles(one_chip, d):
    """KV blocks narrower than the 128 lanes: head_dim 64 (internvl2-1b)
    and 96 (phi3-mini, whose bitmap rows straddle rows of the word view)."""
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,  # noqa: E731
                                                    sharding=one_chip)
    q = sds((SLOTS, HKV, G, d), jnp.bfloat16)
    tail = sds((SLOTS, HKV, TAIL, d), jnp.bfloat16)
    lens = sds((SLOTS,), jnp.int32)

    def fn(q, kb, kv, vb, vv, kt, vt, nb, tl):
        return sparse_decode_attention_fused_pallas(
            q, kb, kv, vb, vv, kt, vt, nb, tl, None, bs=BS,
            sm_scale=d ** -0.5, interpret=False, group=G)
    txt = _compile_text(fn, q, *_kv_operands(one_chip, False, d), tail, tail,
                        lens, lens)
    assert "tpu_custom_call" in txt


def test_prefix_partial_attention_compiles(one_chip):
    """The prefix-only (out, lse) entry the context-parallel path uses."""
    q = jax.ShapeDtypeStruct((SLOTS, HKV, G, D), jnp.bfloat16,
                             sharding=one_chip)
    nb = jax.ShapeDtypeStruct((SLOTS,), jnp.int32, sharding=one_chip)
    txt = _compile_text(
        lambda q, kb, kv, vb, vv, nb: sparse_decode_attention_pallas(
            q, kb, kv, vb, vv, nb, bs=BS, sm_scale=D ** -0.5,
            interpret=False),
        q, *_kv_operands(one_chip, False), nb)
    assert "tpu_custom_call" in txt


# phi3-mini-3.8b: d_model 3072, d_ff 8192, 32 heads = 32 KV heads (group
# 1), head_dim 96; decode at 8 slots goes through sparse_gemv
PHI3_LINEAR_SHAPES = [(3072, 3072), (3072, 8192), (8192, 3072)]
PHI3_HKV, PHI3_D = 32, 96


@pytest.mark.parametrize("k,n", PHI3_LINEAR_SHAPES)
def test_sparse_gemv_compiles_at_phi3_widths(one_chip, k, n):
    x = jax.ShapeDtypeStruct((8, k), jnp.bfloat16, sharding=one_chip)
    txt = _compile_text(
        lambda x, w: sparse_gemv_pallas(x, w, interpret=False),
        x, _weight(one_chip, k, n, "bf16"))
    assert "tpu_custom_call" in txt


def test_fused_paged_decode_attention_compiles_mha_head_dim_96(one_chip):
    """The paged fused decode kernel at Phi-3's geometry: query-per-KV
    group 1 (MHA) and head_dim 96, whose bitmap rows straddle rows of the
    word view, with the block table as a scalar-prefetch operand."""
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,  # noqa: E731
                                                    sharding=one_chip)
    ck = balanced_capacity(0.7, (BS, PHI3_D))
    cv = balanced_capacity(0.5, (BS, PHI3_D))
    words = BS * PHI3_D // 32
    lead = (N_PHYS, PHI3_HKV)
    kv = (sds(lead + (words,), jnp.uint32), sds(lead + (ck,), jnp.bfloat16),
          sds(lead + (words,), jnp.uint32), sds(lead + (cv,), jnp.bfloat16))
    q = sds((SLOTS, PHI3_HKV, 1, PHI3_D), jnp.bfloat16)
    tail = sds((SLOTS, PHI3_HKV, TAIL, PHI3_D), jnp.bfloat16)
    lens = sds((SLOTS,), jnp.int32)
    table = sds((SLOTS, PREFIX_BLOCKS), jnp.int32)

    def fn(q, kb, kv, vb, vv, kt, vt, nb, tl, tbl):
        return sparse_decode_attention_fused_pallas(
            q, kb, kv, vb, vv, kt, vt, nb, tl, tbl, bs=BS,
            sm_scale=PHI3_D ** -0.5, interpret=False, group=1)
    txt = _compile_text(fn, q, *kv, tail, tail, lens, lens, table)
    assert "tpu_custom_call" in txt
