"""Sparse GEMV Pallas kernel — the paper's §4.4 AVX (vector) kernel on TPU.

At decode batch 1, a 128-row MXU macro-tile wastes 127/128 of its input rows
— the same observation that motivates the paper's AVX kernel (their 16-row
AMX input tile is 15/16 wasted).  This kernel is the VPU-path analogue:

* the input stays as a single ``(tm<=8, bk)`` sliver (8 sublanes = the f32
  native tile, the VPU's natural granule),
* the grid iterates output-block-major ``(Nb, Kb)`` so each output sliver is
  produced by a running vector FMA against decompressed weight rows rather
  than an MXU macro-tile pass,
* decompression is identical to the matmul kernel (bitmap -> prefix-sum ->
  gather), matching the paper's shared format between its AVX and AMX paths.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import jax.experimental.pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.core.sparse_format import BlockSparseWeight
from .common import decompress_block, tile_view


def _kernel(x_ref, bm_ref, val_ref, o_ref, acc_ref, *, bk, bn):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    w_tile = decompress_block(bm_ref[0], val_ref[0], bk, bn,
                              dtype=jnp.float32)
    x = x_ref[...].astype(jnp.float32)                # (tm, bk)
    acc_ref[...] += jax.lax.dot_general(
        x, w_tile, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(1) == pl.num_programs(1) - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@partial(jax.jit, static_argnames=("out_dtype", "interpret"))
def sparse_gemv_pallas(x: jax.Array, sw: BlockSparseWeight, *,
                       interpret: bool, out_dtype=None) -> jax.Array:
    """``x [M<=8, K] @ unpack(sw)`` — batch-1..8 decode path."""
    bk, bn = sw.block
    kb, nb, _ = sw.bitmap.shape
    m, k = x.shape
    tm = 8
    if m > tm:
        raise ValueError(f"gemv path is for m<={tm}, got {m}")
    kp = kb * bk
    x = jnp.pad(x, ((0, tm - m), (0, kp - k)))
    out_dtype = out_dtype or x.dtype
    bm, vals = tile_view(sw.bitmap), tile_view(sw.values)
    blk = lambda j, kk: (kk * nb + j, 0, 0)

    out = pl.pallas_call(
        partial(_kernel, bk=bk, bn=bn),
        grid=(nb, kb),
        in_specs=[
            pl.BlockSpec((tm, bk), lambda j, kk: (0, kk)),
            pl.BlockSpec((1,) + bm.shape[1:], blk),
            pl.BlockSpec((1,) + vals.shape[1:], blk),
        ],
        out_specs=pl.BlockSpec((tm, bn), lambda j, kk: (0, j)),
        out_shape=jax.ShapeDtypeStruct((tm, nb * bn), out_dtype),
        scratch_shapes=[pltpu.VMEM((tm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="sparse_gemv",
    )(x, bm, vals)
    return out[:m, : sw.shape[1]]
