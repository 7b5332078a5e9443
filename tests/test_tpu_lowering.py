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
SLOTS, PREFIX_BLOCKS, TAIL, N_PHYS, N_PERIODS = 8, 4, 128, 40, 3


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


def _kv_operands(sharding, paged, d=D, hkv=HKV):
    """The compressed prefix: a flat pool's per-slot block grid, or the
    paged pool's arena of every layer as it stores it, one
    ``[rows, 128]`` tile per block."""
    from repro.core.sparse_kv import arena_rows
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,  # noqa: E731
                                                    sharding=sharding)
    ck = balanced_capacity(0.7, (BS, d))
    cv = balanced_capacity(0.5, (BS, d))
    words = BS * d // 32
    if paged:
        leaf = lambda x: (N_PERIODS, N_PHYS, hkv, arena_rows(x), 128)  # noqa: E731
    else:
        leaf = lambda x: (SLOTS, hkv, PREFIX_BLOCKS, x)  # noqa: E731
    return (sds(leaf(words), jnp.uint32), sds(leaf(ck), jnp.bfloat16),
            sds(leaf(words), jnp.uint32), sds(leaf(cv), jnp.bfloat16))


@pytest.mark.parametrize("paged", [False, True], ids=["flat", "paged"])
@pytest.mark.parametrize("qn", [1, 5])
def test_fused_decode_attention_compiles(one_chip, paged, qn):
    """Fused prefix+tail flash-decode at a decode tick (Q=1) and a
    speculative verify panel (Q=5), with per-slot lengths and (paged) the
    layer index and the block table as scalar-prefetch operands."""
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,  # noqa: E731
                                                    sharding=one_chip)
    q = sds((SLOTS, HKV, qn * G, D), jnp.bfloat16)
    tail = sds((SLOTS, HKV, TAIL, D), jnp.bfloat16)
    lens = sds((SLOTS,), jnp.int32)
    table = sds((SLOTS, PREFIX_BLOCKS), jnp.int32) if paged else None
    layer = sds((), jnp.int32) if paged else None

    def fn(q, kb, kv, vb, vv, kt, vt, nb, tl, tbl, lyr):
        return sparse_decode_attention_fused_pallas(
            q, kb, kv, vb, vv, kt, vt, nb, tl, tbl, lyr, bs=BS,
            sm_scale=D ** -0.5, interpret=False, group=G)
    txt = _compile_text(fn, q, *_kv_operands(one_chip, paged), tail, tail,
                        lens, lens, table, layer)
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
    word view, with the layer index and the block table as scalar-prefetch
    operands."""
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,  # noqa: E731
                                                    sharding=one_chip)
    kv = _kv_operands(one_chip, True, d=PHI3_D, hkv=PHI3_HKV)
    q = sds((SLOTS, PHI3_HKV, 1, PHI3_D), jnp.bfloat16)
    tail = sds((SLOTS, PHI3_HKV, TAIL, PHI3_D), jnp.bfloat16)
    lens = sds((SLOTS,), jnp.int32)
    table = sds((SLOTS, PREFIX_BLOCKS), jnp.int32)
    layer = sds((), jnp.int32)

    def fn(q, kb, kv, vb, vv, kt, vt, nb, tl, tbl, lyr):
        return sparse_decode_attention_fused_pallas(
            q, kb, kv, vb, vv, kt, vt, nb, tl, tbl, lyr, bs=BS,
            sm_scale=PHI3_D ** -0.5, interpret=False, group=1)
    txt = _compile_text(fn, q, *kv, tail, tail, lens, lens, table, layer)
    assert "tpu_custom_call" in txt


# the two cells' KV geometries (KV heads, head_dim), with the lane rows a
# 128-token block's K values, V values and bitmap fill at 30% / 50% KV
# sparsity and capacity slack 1.25: 14336 / 10240 values and 512 words
# (qwen3-0.6b), 10752 / 7680 values and 384 words (phi3-mini)
ARENA_GEOMETRIES = [
    pytest.param(8, 128, (112, 80, 4), id="qwen3-0.6b"),
    pytest.param(32, 96, (84, 60, 3), id="phi3-mini"),
]
# HLO ops that move data: one of these with an output as large as a layer's
# slice of an arena leaf is that slice sliced out or relaid out
MOVERS = ("copy", "reshape", "dynamic-slice", "transpose", "fusion")
DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s16": 2,
               "u16": 2, "f32": 4, "s32": 4, "u32": 4, "f64": 8, "s64": 8,
               "u64": 8}


def _moves_of_at_least(hlo: str, limit: int):
    """``(op, name, shape)`` of every data-movement op in the compiled
    text whose output (or one tuple element of it) has ``limit`` bytes or
    more."""
    import re
    inst = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*?)\s+"
                      r"([a-z][a-z0-9\-]*)\(")
    shape = re.compile(r"\b([a-z]+\d*)\[([\d,]*)\]")
    out = []
    for line in hlo.splitlines():
        m = inst.match(line)
        if not m or m.group(3) not in MOVERS:
            continue
        for dt, dims in shape.findall(m.group(2)):
            n = DTYPE_BYTES.get(dt, 0)
            for d in filter(None, dims.split(",")):
                n *= int(d)
            if n >= limit:
                out.append((m.group(3), m.group(1), f"{dt}[{dims}]"))
    return out


@pytest.mark.parametrize("hkv,hd,rows", ARENA_GEOMETRIES)
def test_paged_decode_step_reads_the_arena_in_place(one_chip, hkv, hd, rows):
    """The overlapped engine's decode step, as the engine builds it with
    the state donated, over a paged pool at a cell's KV geometry (2
    layers, 32 physical blocks, one slot so that no per-slot leaf is as
    large as a layer's arena): the compiled step calls the fused paged
    kernel, and no copy, reshape, dynamic slice, transpose or fusion puts
    out as many bytes as the smallest leaf of one layer's arena -- the
    kernel reads the stacked arena where it lies."""
    import dataclasses

    from repro.configs import get_config
    from repro.core.sparse_kv import arena_rows
    from repro.distributed import NULL_CTX
    from repro.distributed.convert_plan import convert_abstract
    from repro.kernels import ops
    from repro.models import lm
    from repro.serving import ARENA_KEYS, ContinuousEngine

    cfg = dataclasses.replace(
        get_config("qwen3-0.6b").reduced(), n_layers=2, d_model=256,
        n_heads=hkv, n_kv=hkv, head_dim=hd, d_ff=512, kv_tail=128,
        kv_k_sparsity=0.3, kv_v_sparsity=0.5)
    params = convert_abstract(
        jax.eval_shape(lambda: lm.init_params(cfg, jax.random.PRNGKey(0))),
        lm.model_specs(cfg), cfg, NULL_CTX)
    eng = ContinuousEngine(params, cfg, slots=1, max_tokens=512,
                           prefill_chunk=128, paged=True, phys_blocks=32,
                           overlap=True, donate=True)
    kv = eng.state["layers"]["l0"]["kv"]
    assert eng.pool.bs == BS
    assert [-(-x // 128) for x in (eng.pool.cap_k, eng.pool.cap_v,
                                   BS * hd // 32)] == list(rows)
    # stored as whole (8, 128) tiles: 112, 80, 8 rows; 88, 64, 8 rows
    for key, r in zip(("k_values", "v_values", "k_bitmap"), rows):
        assert kv[key].shape == (2, 32, hkv, arena_rows(r * 128), 128)
    layer_leaf = min(kv[k][0].nbytes for k in ARENA_KEYS)

    fn, args = eng.entry_points()["decode_chain"]
    placed = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        args)
    with ops.backend("tpu"):
        hlo = fn.lower(*placed).compile().as_text()
    assert "sparse_decode_attention_fused_paged" in hlo
    assert _moves_of_at_least(hlo, layer_leaf) == []
