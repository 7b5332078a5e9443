"""INT4 sparse GEMM Pallas kernel — the paper's §8 extension, implemented as
prescribed: "dequantizing INT4 values into INT8 before computation".

Identical structure to :mod:`sparse_matmul_int8`, with one extra VMEM stage:
the packed nibble stream (two weights/byte — HBM traffic halves again vs
int8) is expanded in registers *before* the bitmap decompression, then the
int8 MXU path runs unchanged.  Byte ``i`` of a block holds value ``i`` in
its low nibble and value ``i + C/2`` in its high nibble
(:func:`repro.core.sparse_format.pack_nibbles`), so the two nibble planes
of the ``(C/256, 128)`` byte tile stack into the ``(C/128, 128)`` value
tile with one row concatenation.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import jax.experimental.pallas as pl

from repro.core.sparse_format import BlockSparseWeight
from .common import decompress_block, tile_view
from .sparse_matmul_int8 import int8_matmul_call


def _unpack_nibbles(b):
    """uint8 ``(C/256, 128)`` -> sign-extended int32 ``(C/128, 128)``."""
    b = b.astype(jnp.int32)
    sext = lambda x: (x ^ 8) - 8
    # in-VMEM tile assembly, not an HBM concat
    return jnp.concatenate([sext(b & 0xF), sext((b >> 4) & 0xF)], axis=0)  # jitlint: disable=hot-path-op


def _kernel(x_ref, sx_ref, bm_ref, val_ref, sw_ref, o_ref, acc_ref, *,
            bk, bn):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    vals = _unpack_nibbles(val_ref[0]).astype(jnp.bfloat16)  # exact in bf16
    w_tile = decompress_block(bm_ref[0], vals, bk, bn, dtype=jnp.int8)
    acc_ref[...] += jnp.dot(x_ref[...], w_tile,
                            preferred_element_type=jnp.int32)

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _done():
        scaled = (acc_ref[...].astype(jnp.float32)
                  * sx_ref[...] * sw_ref[...])
        o_ref[...] = scaled.astype(o_ref.dtype)


@partial(jax.jit, static_argnames=("tm", "out_dtype", "interpret"))
def sparse_matmul_int4_pallas(xq: jax.Array, sx: jax.Array,
                              sw: BlockSparseWeight, *, interpret: bool,
                              tm: int = 128,
                              out_dtype=jnp.float32) -> jax.Array:
    """``dequant(xq, sx) @ dequant4(sw)``; xq int8 [M, K], sx f32 [M]."""
    if not (sw.packed4 and sw.scale is not None):
        raise ValueError("int4 path needs nibble-packed values and a scale")
    if sw.values.shape[-1] % 128:
        raise ValueError(f"int4 kernel needs a capacity multiple of 256, got "
                         f"{sw.capacity}")
    return int8_matmul_call(_kernel, xq, sx, sw, tile_view(sw.values), tm,
                            out_dtype, interpret, "sparse_matmul_int4")
