"""INT8 sparse GEMM Pallas kernel — paper §4.5 INT8 kernels on the MXU.

Same decompress-then-dense-dot structure as :mod:`sparse_matmul`, with:
  * int8 packed values (each block holds 2x the weights of a bf16 block per
    byte, mirroring the paper's 16x64 int8 AMX tiles vs 16x32 bf16),
  * int32 MXU accumulation,
  * per-row dynamic activation scale + per-output-channel weight scale
    applied at the epilogue.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import jax.experimental.pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.core.sparse_format import BlockSparseWeight
from .common import decompress_block, tile_view


def _kernel(x_ref, sx_ref, bm_ref, val_ref, sw_ref, o_ref, acc_ref, *, bk, bn):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    w_tile = decompress_block(bm_ref[0], val_ref[0], bk, bn, dtype=jnp.int8)
    acc_ref[...] += jnp.dot(x_ref[...], w_tile,
                            preferred_element_type=jnp.int32)

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _done():
        scaled = (acc_ref[...].astype(jnp.float32)
                  * sx_ref[...]                      # (tm, 1) per-row act scale
                  * sw_ref[...])                     # (1, bn) per-channel w scale
        o_ref[...] = scaled.astype(o_ref.dtype)


def int8_matmul_call(kernel, xq, sx, sw: BlockSparseWeight, vals, tm,
                     out_dtype, interpret, name):
    """The int8/int4 GEMM grid: ``vals`` is the kernel's view of the packed
    values (int8 for int8, nibble bytes for int4)."""
    bk, bn = sw.block
    kb, nb, _ = sw.bitmap.shape
    m, k = xq.shape
    kp, mp = kb * bk, -(-m // tm) * tm
    xq = jnp.pad(xq, ((0, mp - m), (0, kp - k)))
    sx2 = jnp.pad(sx.astype(jnp.float32), (0, mp - m))[:, None]
    w_scale = sw.scale.reshape(1, nb * bn)
    bm = tile_view(sw.bitmap)
    blk = lambda i, j, kk: (kk * nb + j, 0, 0)

    out = pl.pallas_call(
        partial(kernel, bk=bk, bn=bn),
        grid=(mp // tm, nb, kb),
        in_specs=[
            pl.BlockSpec((tm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((tm, 1), lambda i, j, kk: (i, 0)),
            pl.BlockSpec((1,) + bm.shape[1:], blk),
            pl.BlockSpec((1,) + vals.shape[1:], blk),
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((tm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, nb * bn), out_dtype),
        scratch_shapes=[pltpu.VMEM((tm, bn), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name=name,
    )(xq, sx2, bm, vals, w_scale)
    return out[:m, : sw.shape[1]]


@partial(jax.jit, static_argnames=("tm", "out_dtype", "interpret"))
def sparse_matmul_int8_pallas(xq: jax.Array, sx: jax.Array,
                              sw: BlockSparseWeight, *, interpret: bool,
                              tm: int = 128,
                              out_dtype=jnp.float32) -> jax.Array:
    """``dequant(xq, sx) @ dequant(sw)``; xq int8 [M, K], sx f32 [M]."""
    if not (sw.values.dtype == jnp.int8 and sw.scale is not None):
        raise ValueError("int8 path needs int8 values and a scale")
    return int8_matmul_call(_kernel, xq, sx, sw, tile_view(sw.values), tm,
                            out_dtype, interpret, "sparse_matmul_int8")
