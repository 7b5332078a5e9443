"""Dense GEMM Pallas kernel — the paper's §4.1 dense AMX kernel on the MXU.

The paper tiles 2x2 output macro-tiles across 4 AMX accumulator tiles to get
a 1:1 compute:load ratio.  The MXU analogue: each grid cell owns a
``(tm, bn)`` output macro-block accumulated in an f32 VMEM scratch across the
``K`` loop, with (tm, bk) input and (bk, bn) weight blocks streamed through
VMEM — the same "keep accumulators resident, stream operands" structure,
sized for 128x128 systolic tiles instead of 16x32 AMX tiles.

Grid: ``(M/tm, N/bn, K/bk)``; the K dimension is innermost/sequential
("arbitrary"), M and N are parallel.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import jax.experimental.pallas as pl
import jax.experimental.pallas.tpu as pltpu


def _kernel(x_ref, w_ref, o_ref, acc_ref):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    pet = jnp.int32 if x_ref.dtype == jnp.int8 else jnp.float32
    acc_ref[...] += jnp.dot(x_ref[...], w_ref[...],
                            preferred_element_type=pet)

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@partial(jax.jit, static_argnames=("block", "out_dtype", "interpret"))
def dense_matmul_pallas(x: jax.Array, w: jax.Array, *, interpret: bool,
                        block=(128, 256, 128),
                        out_dtype=None) -> jax.Array:
    """``x [M, K] @ w [K, N]`` with padding to block multiples."""
    tm, bk, bn = block
    m, k = x.shape
    k2, n = w.shape
    if k != k2:
        raise ValueError(f"inner dims disagree: x has K={k}, w has K={k2}")
    mp, kp, np_ = -(-m // tm) * tm, -(-k // bk) * bk, -(-n // bn) * bn
    x = jnp.pad(x, ((0, mp - m), (0, kp - k)))
    w = jnp.pad(w, ((0, kp - k), (0, np_ - n)))
    out_dtype = out_dtype or (jnp.int32 if x.dtype == jnp.int8 else x.dtype)
    acc_dtype = jnp.int32 if x.dtype == jnp.int8 else jnp.float32

    out = pl.pallas_call(
        _kernel,
        grid=(mp // tm, np_ // bn, kp // bk),
        in_specs=[
            pl.BlockSpec((tm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((tm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), out_dtype),
        scratch_shapes=[pltpu.VMEM((tm, bn), acc_dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="dense_matmul",
    )(x, w)
    return out[:m, :n]
