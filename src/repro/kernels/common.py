"""Shared in-kernel helpers for the SparAMX-style Pallas kernels.

``decompress_block`` is the TPU re-think of the paper's Algorithm 2
(`vpexpandw` + `vpopcntd` + AVX prefix sum), written only in operations the
Mosaic TPU lowering accepts (2-D tiles, matmuls, lane gathers on 32-bit
tiles):

* AVX bitmap fetch            -> uint32 words staged in VMEM as a
                                 ``(words/128, 128)`` tile; one lane gather
                                 per 32-row chunk spreads each row's words
                                 over its lanes, then an iota shift unpacks
* vpopcntd + Alg. 1 prefix sum -> two 0/1 triangular matmuls on the MXU
                                 (within-row ranks and row offsets; exact
                                 integer counts in f32 accumulation)
* vpexpandw expand            -> the packed values viewed as a
                                 ``(C/128, 128)`` tile: the values of dense
                                 row ``r`` lie in at most two consecutive
                                 rows of that view, selected by two one-hot
                                 matmuls (exact: one non-zero per output),
                                 then two lane gathers and a ``where``

There is no AVX->memory->AMX round-trip (the paper's stated architectural
bottleneck, §7): the expanded tile is produced in VMEM and fed straight to
the MXU.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

LANES = 128


def tile_view(a: jax.Array) -> jax.Array:
    """Packed per-block leaf ``[..., X]`` -> ``[N, ceil(X/128), 128]``.

    The kernels fetch one block's bitmap words or packed values as a
    ``(X/128, 128)`` tile: the block's last two dims then equal the array's,
    which the TPU block-shape rule accepts.  Capacities are whole lane
    multiples (``balanced_capacity``), and so is the bitmap of a block
    whose size is a multiple of 4096 (every weight block, and KV blocks of
    32 to 128 tokens at head_dim 128).  Other KV blocks (prefill chunks of
    16 tokens, head_dim 96, the CPU tests) have their bitmap padded here,
    which copies the leaf per call.
    """
    x = a.shape[-1]
    pad = -x % LANES
    if pad:
        a = jnp.pad(a, [(0, 0)] * (a.ndim - 1) + [(0, pad)])
    return a.reshape(-1, (x + pad) // LANES, LANES)


def unpack_mask(words: jax.Array, bk: int, bn: int) -> jax.Array:
    """uint32 words ``(W/128, 128)`` -> int32 0/1 mask ``(bk, 128)`` whose
    first ``bn <= 128`` columns are the block's and the rest zero.

    Bit ``b`` of word ``j`` is flat row-major position ``32*j + b`` of the
    block.  Dense rows are unpacked in chunks of at most 4096 bits (128
    words), each a broadcast view row, a lane gather and a shift; the
    lowering gathers whole 128-lane rows only, hence the zero columns.
    When ``bn`` divides 128 (every weight block, and KV blocks of head_dim
    64 or 128) a chunk's words are exactly one row of the view; otherwise
    (head_dim 96) a chunk may straddle two rows, gathered from both and
    joined with a ``where``.
    """
    if bn > LANES:
        raise ValueError(f"block width {bn} exceeds {LANES}")
    bits = LANES * 32                                 # bits per view row
    rows = min(bk, bits // bn)
    if rows < bk:
        rows -= rows % 8                              # whole sublane tiles
    r = jax.lax.broadcasted_iota(jnp.int32, (rows, LANES), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (rows, LANES), 1)
    flat = r * bn + c
    lane = flat >> 5
    shift = (flat & 31).astype(jnp.uint32)
    chunks = []
    for r0 in range(0, bk, rows):
        q, off = divmod(r0 * bn, bits)        # first view row, bit offset
        n = min(rows, bk - r0)
        ln, sh = lane, shift
        if off or n < rows:
            f = flat[:n] + off
            ln, sh = f >> 5, (f & 31).astype(jnp.uint32)

        def gather(q, idx):
            row = jnp.broadcast_to(words[q:q + 1, :], (n, LANES))
            return jnp.take_along_axis(row, idx, axis=1)
        if off + n * bn <= bits:
            w = gather(q, ln & (LANES - 1) if bn < LANES else ln)
        else:                                 # lanes [0, 256): two rows
            idx = ln & (LANES - 1)
            w = jnp.where(ln < LANES, gather(q, idx), gather(q + 1, idx))
        bit = (w >> sh) & jnp.uint32(1)
        if bn < LANES:                        # zero the pad columns
            bit = jnp.where(c[:n] < bn, bit, jnp.uint32(0))
        chunks.append(bit.astype(jnp.int32))
    # in-VMEM tile assembly, not an HBM concat
    return chunks[0] if len(chunks) == 1 else jnp.concatenate(chunks, axis=0)  # jitlint: disable=hot-path-op


def decompress_block(words: jax.Array, values: jax.Array,
                     bk: int, bn: int, dtype=None) -> jax.Array:
    """Expand one compressed block to a dense ``(bk, bn)`` tile in VMEM.

    words: uint32 ``(W/128, 128)`` bitmap tile; values: ``(C/128, 128)``
    packed non-zeros in row-major order (bf16, int8 or f32).
    """
    out_dtype = dtype or values.dtype
    mask = unpack_mask(words, bk, bn)
    mb = mask.astype(jnp.bfloat16)
    f32 = jnp.float32
    # exclusive prefix within each row: mask @ strict-upper(128, 128)
    upper = (jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 0)
             < jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 1))
    within = jnp.dot(mb, upper.astype(jnp.bfloat16), preferred_element_type=f32)
    # set bits in all earlier rows: strict-lower(bk, bk) @ mask, lane-summed
    lower = (jax.lax.broadcasted_iota(jnp.int32, (bk, bk), 1)
             < jax.lax.broadcasted_iota(jnp.int32, (bk, bk), 0))
    before = jnp.dot(lower.astype(jnp.bfloat16), mb, preferred_element_type=f32)
    row_off = jnp.sum(before, axis=1, keepdims=True).astype(jnp.int32)
    q0 = row_off >> 7                                        # view row (bk, 1)
    lane = (row_off & (LANES - 1)) + within.astype(jnp.int32)  # [0, 256)
    # rows q0 and q0+1 of the value view, selected by one-hot matmuls
    if values.dtype == f32:
        vmat, prec = values, jax.lax.Precision.HIGHEST
    else:                      # bf16, and int8 (exact in bf16)
        vmat, prec = values.astype(jnp.bfloat16), None
    nq = values.shape[0]
    qi = jax.lax.broadcasted_iota(jnp.int32, (bk, nq), 1)
    pick = lambda q: jnp.dot((qi == q).astype(vmat.dtype), vmat,
                             precision=prec, preferred_element_type=f32)
    lo, hi = pick(q0), pick(q0 + 1)                          # (bk, 128) f32
    idx = lane & (LANES - 1)
    dense = jnp.where(lane < LANES,
                      jnp.take_along_axis(lo, idx, axis=1),
                      jnp.take_along_axis(hi, idx, axis=1))
    dense = jnp.where(mask > 0, dense, 0.0)
    if bn < LANES:
        dense = dense[:, :bn]
    return dense.astype(out_dtype)
