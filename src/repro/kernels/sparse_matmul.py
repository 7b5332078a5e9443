"""Sparse (bitmap + packed values) GEMM Pallas kernel — paper §4.3 on TPU.

Load-as-sparse, compute-as-dense: each grid cell streams one *compressed*
weight block (bitmap words + up-to-capacity packed values) HBM->VMEM,
expands it to a dense ``(bk, bn)`` tile with
:func:`repro.kernels.common.decompress_block`, and feeds the MXU.  HBM
traffic for weights is ``C/(bk*bn) + 1/16`` of the dense bf16 bytes —
exactly the paper's bandwidth-saving mechanism, minus the AVX->memory->AMX
round-trip which has no TPU analogue (DESIGN.md §2).

Layout (produced by ``repro.core.sparse_format.pack``):
  bitmap  uint32 ``[Kb, Nb, bk*bn//32]``
  values         ``[Kb, Nb, C]``
Both are fetched per block as ``(X/128, 128)`` tiles of the
:func:`repro.kernels.common.tile_view` of the leaf (block index
``kk * Nb + j``).

Grid ``(M/tm, Nb, Kb)``; K innermost/sequential, f32 VMEM accumulator.
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
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    w_tile = decompress_block(bm_ref[0], val_ref[0], bk, bn,
                              dtype=val_ref.dtype)
    acc_ref[...] += jnp.dot(x_ref[...].astype(w_tile.dtype), w_tile,
                            preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@partial(jax.jit, static_argnames=("tm", "out_dtype", "interpret"))
def sparse_matmul_pallas(x: jax.Array, sw: BlockSparseWeight, *,
                         interpret: bool, tm: int = 128,
                         out_dtype=None) -> jax.Array:
    """``x [M, K] @ unpack(sw) [K, N]`` without materializing the dense W in HBM."""
    bk, bn = sw.block
    kb, nb, _ = sw.bitmap.shape
    m, k = x.shape
    kp = kb * bk
    mp = -(-m // tm) * tm
    x = jnp.pad(x, ((0, mp - m), (0, kp - k)))
    out_dtype = out_dtype or x.dtype
    bm, vals = tile_view(sw.bitmap), tile_view(sw.values)
    blk = lambda i, j, kk: (kk * nb + j, 0, 0)

    out = pl.pallas_call(
        partial(_kernel, bk=bk, bn=bn),
        grid=(mp // tm, nb, kb),
        in_specs=[
            pl.BlockSpec((tm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((1,) + bm.shape[1:], blk),
            pl.BlockSpec((1,) + vals.shape[1:], blk),
        ],
        out_specs=pl.BlockSpec((tm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, nb * bn), out_dtype),
        scratch_shapes=[pltpu.VMEM((tm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="sparse_matmul",
    )(x, bm, vals)
    return out[:m, : sw.shape[1]]
