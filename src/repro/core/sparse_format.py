"""Blocked bitmap+packed-values sparse format (SparAMX -> TPU adaptation).

The paper stores weights as ``weight_metadata`` (a bitmap, 1 bit/weight) plus
``weight_values`` (packed non-zeros), and decompresses 16x32 AMX tiles with
``vpexpandw`` right before a dense AMX matmul ("load-as-sparse,
compute-as-dense").

On TPU the analogue is a *blocked* layout so Pallas BlockSpecs stay static:

* the dense weight ``W[K, N]`` is cut into ``(bk, bn)`` blocks,
* each block's mask is packed into uint32 bitmap words (bit order: row-major
  over the flattened ``bk*bn`` block, 32 bits per word),
* each block's non-zero values are packed — in the same row-major order —
  into a fixed per-tensor **capacity** ``C`` (max block nnz, rounded up to a
  lane multiple).  The fixed capacity replaces the paper's per-thread
  ``weight_value_index``: every grid cell's value slice is statically
  addressable.

Decompression (kernel + reference) mirrors the paper's Algorithm 2:
popcount/prefix-sum to turn the bitmap into gather indices, then an expand —
``vpexpandw`` on AMX, a vector gather on the TPU VPU.

All functions are pure jnp and traceable, so ``jax.eval_shape`` gives
abstract packed layouts for the dry-run without allocating.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

DEFAULT_BLOCK = (256, 128)
LANE = 128  # value capacity is rounded up to this


def _ceil_to(x: int, m: int) -> int:
    return int(-(-x // m) * m)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class BlockSparseWeight:
    """A ``[K, N]`` weight stored as bitmap + packed values.

    Attributes:
      bitmap:  uint32 ``[Kb, Nb, bk*bn // 32]`` — per-block metadata words.
      values:  ``[Kb, Nb, C]`` packed non-zeros (row-major within block).
      scale:   optional fp32 ``[N_pad]`` per-output-channel scale (int8 mode).
      shape:   logical (un-padded) ``(K, N)``.
      block:   ``(bk, bn)`` block shape.
      packed4: values hold two int4 nibbles per uint8 byte (paper §8's INT4
               extension — dequantized to int8 before the MXU pass).
    """

    bitmap: jax.Array
    values: jax.Array
    scale: Optional[jax.Array]
    shape: Tuple[int, int]
    block: Tuple[int, int]
    packed4: bool = False

    # -- pytree protocol ----------------------------------------------------
    def tree_flatten(self):
        children = (self.bitmap, self.values, self.scale)
        aux = (self.shape, self.block, self.packed4)
        return children, aux

    @classmethod
    def tree_unflatten(cls, aux, children):
        bitmap, values, scale = children
        return cls(bitmap, values, scale, *aux)

    # -- conveniences --------------------------------------------------------
    @property
    def capacity(self) -> int:
        c = self.values.shape[-1]
        return c * 2 if self.packed4 else c

    @property
    def padded_shape(self) -> Tuple[int, int]:
        bk, bn = self.block
        return self.bitmap.shape[-3] * bk, self.bitmap.shape[-2] * bn

    @property
    def lead_shape(self) -> Tuple[int, ...]:
        return tuple(self.bitmap.shape[:-3])

    @property
    def dtype(self):
        return self.values.dtype

    def nbytes_compressed(self) -> int:
        n = self.bitmap.size * 4 + self.values.size * self.values.dtype.itemsize
        if self.scale is not None:
            n += self.scale.size * self.scale.dtype.itemsize
        return n

    def nbytes_dense(self) -> int:
        k, n = self.shape
        return (math.prod(self.lead_shape) * k * n
                * self.values.dtype.itemsize)

    def compression_ratio(self) -> float:
        """compressed bytes / dense bytes (lower is better)."""
        return self.nbytes_compressed() / self.nbytes_dense()


# ---------------------------------------------------------------------------
# int4 nibble packing (paper §8: "extending support to INT4 is feasible by
# dequantizing INT4 values into INT8 before computation")
# ---------------------------------------------------------------------------

def pack_nibbles(v: jax.Array) -> jax.Array:
    """int8 ``[..., C]`` in [-8, 7] -> uint8 ``[..., C//2]``.

    Byte ``i`` holds value ``i`` in its low nibble and value ``i + C/2`` in
    its high nibble: the two nibble planes are the two halves of the value
    stream, which the int4 kernel stacks back with one row concatenation.
    """
    c = v.shape[-1]
    if c % 2 != 0:
        raise ValueError(f"nibble packing needs an even channel dim, got {c}")
    u = v.astype(jnp.uint8) & jnp.uint8(0xF)
    lo, hi = u[..., : c // 2], u[..., c // 2:]
    return lo | (hi << jnp.uint8(4))


def unpack_nibbles(b: jax.Array) -> jax.Array:
    """Inverse of :func:`pack_nibbles` -> int8 ``[..., 2C]`` (sign-extended).

    This is the dequant-to-int8 step the paper prescribes; in the Pallas
    kernel it runs in VMEM right before the bitmap expansion.
    """
    lo = (b & jnp.uint8(0xF)).astype(jnp.int8)
    hi = (b >> jnp.uint8(4)).astype(jnp.int8)
    sext = lambda x: ((x ^ jnp.int8(8)) - jnp.int8(8)).astype(jnp.int8)
    return jnp.concatenate([sext(lo), sext(hi)], axis=-1)


# ---------------------------------------------------------------------------
# bit packing helpers
# ---------------------------------------------------------------------------

def pack_bits(mask: jax.Array) -> jax.Array:
    """Pack a ``[..., L]`` 0/1 mask into ``[..., L//32]`` uint32 words.

    Bit ``b`` of word ``j`` corresponds to flat position ``32*j + b``.
    """
    l = mask.shape[-1]
    if l % 32 != 0:
        raise ValueError(f"mask length {l} not a multiple of 32")
    m = mask.astype(jnp.uint32).reshape(*mask.shape[:-1], l // 32, 32)
    shifts = jnp.arange(32, dtype=jnp.uint32)
    return jnp.sum(m << shifts, axis=-1, dtype=jnp.uint32)


def unpack_bits(words: jax.Array, length: int) -> jax.Array:
    """Inverse of :func:`pack_bits` -> int32 0/1 mask ``[..., length]``."""
    shifts = jnp.arange(32, dtype=jnp.uint32)
    bits = (words[..., None] >> shifts) & jnp.uint32(1)
    out = bits.reshape(*words.shape[:-1], words.shape[-1] * 32)
    return out[..., :length].astype(jnp.int32)


def exclusive_cumsum(x: jax.Array, axis: int = -1) -> jax.Array:
    """The paper's Alg. 1 parallel prefix sum, exclusive variant."""
    inc = jnp.cumsum(x, axis=axis)
    return inc - x


# ---------------------------------------------------------------------------
# packing
# ---------------------------------------------------------------------------

def _to_blocks(w: jax.Array, block: Tuple[int, int],
               pad_to_blocks: Tuple[int, int] = (1, 1)) -> jax.Array:
    """``[K, N]`` -> ``[Kb, Nb, bk*bn]`` (row-major within block), padding K/N."""
    bk, bn = block
    k, n = w.shape
    kp = _ceil_to(_ceil_to(k, bk) // bk, pad_to_blocks[0]) * bk
    np_ = _ceil_to(_ceil_to(n, bn) // bn, pad_to_blocks[1]) * bn
    w = jnp.pad(w, ((0, kp - k), (0, np_ - n)))
    kb, nb = kp // bk, np_ // bn
    w = w.reshape(kb, bk, nb, bn).transpose(0, 2, 1, 3)  # [Kb, Nb, bk, bn]
    return w.reshape(kb, nb, bk * bn)


def _from_blocks(blocks: jax.Array, block: Tuple[int, int],
                 shape: Tuple[int, int]) -> jax.Array:
    """``[..., Kb, Nb, bk*bn]`` -> ``[..., K, N]`` (strips padding)."""
    bk, bn = block
    *lead, kb, nb, _ = blocks.shape
    w = blocks.reshape(*lead, kb, nb, bk, bn)
    w = jnp.moveaxis(w, -2, -3)                       # [..., Kb, bk, Nb, bn]
    w = w.reshape(*lead, kb * bk, nb * bn)
    return w[..., : shape[0], : shape[1]]


def _cap_mask(wb: jax.Array, mb: jax.Array, cap: int) -> jax.Array:
    """Drop the smallest-|.| overflow entries of any block whose nnz exceeds
    ``cap`` — from the *mask* (and therefore the bitmap), so bitmap and packed
    values never disagree.  Blocks with nnz <= cap come back unchanged."""
    score = jnp.where(mb, jnp.abs(wb.astype(jnp.float32)), -jnp.inf)
    idx = jax.lax.top_k(score, cap)[1]                    # [..., cap]
    l = mb.shape[-1]
    flat_i = idx.reshape(-1, cap)
    sel = jax.vmap(lambda i: jnp.zeros((l,), jnp.bool_).at[i].set(True))(
        flat_i)
    return jnp.logical_and(mb, sel.reshape(mb.shape))


def pack_blocks(wb: jax.Array, mb: jax.Array, cap: int,
                cap_may_truncate: bool = True
                ) -> Tuple[jax.Array, jax.Array]:
    """Pack pre-blocked values ``wb [..., L]`` under mask ``mb`` at a *static*
    per-block capacity ``cap`` -> (bitmap uint32 ``[..., L//32]``, values
    ``[..., cap]``).

    This is the jit-stable packing primitive the serving cache pool builds
    on: ``cap`` never depends on the data, and when a block holds more than
    ``cap`` kept entries the overflow is dropped consistently from bitmap
    *and* values (magnitude order), so ``unpack`` always round-trips what the
    bitmap claims.

    ``cap_may_truncate=False`` skips the overflow re-rank when the caller
    can prove ``cap >= max block nnz`` (e.g. it derived ``cap`` from the
    data) — the top-k scan is pure waste there.
    """
    l = wb.shape[-1]
    cap = min(int(cap), l)
    if cap < l and cap_may_truncate:
        mb = _cap_mask(wb, mb, cap)
    mb_i = mb.astype(jnp.int32)
    nnz = mb_i.sum(-1)
    # Stable partition: indices of kept entries first, in row-major order.
    order = jnp.argsort(jnp.logical_not(mb), axis=-1, stable=True)
    vals = jnp.take_along_axis(wb * mb.astype(wb.dtype),
                               order[..., :cap], axis=-1)
    valid = jnp.arange(cap) < nnz[..., None]
    vals = jnp.where(valid, vals, 0).astype(wb.dtype)
    return pack_bits(mb_i), vals


def pack(w: jax.Array,
         mask: jax.Array,
         block: Tuple[int, int] = DEFAULT_BLOCK,
         capacity: Optional[int] = None,
         pad_to_blocks: Tuple[int, int] = (1, 1),
         scale: Optional[jax.Array] = None) -> BlockSparseWeight:
    """Pack ``w`` (zeroed outside ``mask``) into the blocked sparse format.

    Args:
      w: dense ``[K, N]`` weight.
      mask: boolean/0-1 ``[K, N]`` keep-mask.
      block: ``(bk, bn)`` block shape.
      capacity: per-block packed-value capacity; default = max block nnz
        rounded up to ``LANE``.  Must be a static int under tracing
        (pass it explicitly when ``jax.eval_shape``-ing).  If a block holds
        more kept entries than the capacity, the smallest-magnitude overflow
        is dropped from bitmap *and* values together (see ``pack_blocks``).
      pad_to_blocks: pad block-counts ``(Kb, Nb)`` to these multiples so the
        block axes shard evenly over a mesh axis.
      scale: optional per-output-channel scale to carry (int8 mode).
    """
    bk, bn = block
    if (bk * bn) % 32 != 0:
        raise ValueError(f"block {block} must cover a multiple of 32 entries")
    wb = _to_blocks(w, block, pad_to_blocks)              # [Kb, Nb, L]
    mb = _to_blocks(mask.astype(w.dtype), block, pad_to_blocks) > 0

    if capacity is None:
        nnz = mb.astype(jnp.int32).sum(-1)                 # [Kb, Nb]
        cap = _ceil_to(max(int(jnp.max(nnz)), 1), LANE)
    else:
        cap = int(capacity)
    cap = min(cap, bk * bn)

    # capacity derived from the data can never truncate; skip the re-rank
    bitmap, vals = pack_blocks(wb, mb, cap,
                               cap_may_truncate=capacity is not None)
    if scale is not None:
        n_pad = wb.shape[1] * bn
        scale = jnp.pad(scale.astype(jnp.float32), (0, n_pad - scale.shape[0]))
    return BlockSparseWeight(bitmap=bitmap, values=vals, scale=scale,
                             shape=(int(w.shape[0]), int(w.shape[1])),
                             block=block)


def repack_capacity(sw: BlockSparseWeight, capacity: int) -> BlockSparseWeight:
    """Re-store ``sw`` at exactly ``capacity`` packed slots per block.

    Growing pads the value arrays (bit-exact round trip).  Shrinking
    re-ranks each block's kept entries by magnitude and drops the overflow
    from the bitmap *and* the values together, so ``unpack`` of the result
    always equals the dense weight its own bitmap describes.  (The old
    engine repack padded values only, which could leave a bitmap claiming
    entries whose values had been truncated away.)
    """
    if sw.packed4:
        raise ValueError("repack of nibble-packed int4 not supported")
    cap = int(capacity)
    if cap == sw.capacity:
        return sw
    if cap > sw.capacity:
        pad = cap - sw.values.shape[-1]
        vals = jnp.pad(sw.values,
                       [(0, 0)] * (sw.values.ndim - 1) + [(0, pad)])
        return BlockSparseWeight(sw.bitmap, vals, sw.scale, sw.shape,
                                 sw.block, sw.packed4)
    # shrink: decompress block-locally, re-pack at the smaller capacity
    bk, bn = sw.block
    mask, idx = block_gather_indices(sw.bitmap, sw.block)
    idx = jnp.minimum(idx, sw.capacity - 1)
    dense_flat = jnp.take_along_axis(sw.values, idx, axis=-1)
    dense_flat = jnp.where(mask > 0, dense_flat, 0)
    bitmap, vals = pack_blocks(dense_flat, mask > 0, cap)
    return BlockSparseWeight(bitmap, vals, sw.scale, sw.shape,
                             sw.block, sw.packed4)


def block_gather_indices(bitmap: jax.Array, block: Tuple[int, int]):
    """Bitmap -> (mask, gather index) per block — the decompression front half.

    Returns ``mask`` int32 ``[..., L]`` and ``idx`` int32 ``[..., L]`` where
    ``dense_flat = where(mask, values[idx], 0)``.  This is the TPU analogue of
    the paper's popcount + prefix-sum offset computation (Alg. 1 / Alg. 2).
    """
    bk, bn = block
    mask = unpack_bits(bitmap, bk * bn)
    idx = exclusive_cumsum(mask, axis=-1)
    return mask, idx


def unpack(sw: BlockSparseWeight, trim: bool = True) -> jax.Array:
    """Decompress to a dense ``[..., K, N]`` weight — pure-jnp oracle.

    Supports leading stacked dims (layer-stacked / expert-stacked weights):
    all decompression math is block-local, so extra leading dims broadcast.
    """
    mask, idx = block_gather_indices(sw.bitmap, sw.block)
    idx = jnp.minimum(idx, sw.capacity - 1)
    values = unpack_nibbles(sw.values) if sw.packed4 else sw.values
    dense_flat = jnp.take_along_axis(values, idx, axis=-1)
    dense_flat = jnp.where(mask > 0, dense_flat, 0).astype(values.dtype)
    shape = sw.shape if trim else sw.padded_shape
    return _from_blocks(dense_flat, sw.block, shape)


# ---------------------------------------------------------------------------
# abstract packing (for the dry-run: no allocation, shapes only)
# ---------------------------------------------------------------------------

def packed_spec(k: int, n: int, density: float,
                block: Tuple[int, int] = DEFAULT_BLOCK,
                dtype: Any = jnp.bfloat16,
                pad_to_blocks: Tuple[int, int] = (1, 1),
                with_scale: bool = False,
                lead: Tuple[int, ...] = ()) -> BlockSparseWeight:
    """Build a ShapeDtypeStruct-leaved BlockSparseWeight for abstract lowering.

    Capacity is the *balanced* capacity ``ceil(density * bk * bn / LANE) * LANE``
    — the storage the paper's sparsity level implies.  ``lead`` adds stacked
    leading dims (layer/expert stacks).
    """
    bk, bn = block
    kb = _ceil_to(_ceil_to(k, bk) // bk, pad_to_blocks[0])
    nb = _ceil_to(_ceil_to(n, bn) // bn, pad_to_blocks[1])
    cap = min(_ceil_to(max(int(round(density * bk * bn)), 1), LANE), bk * bn)
    sds = jax.ShapeDtypeStruct
    return BlockSparseWeight(
        bitmap=sds(lead + (kb, nb, bk * bn // 32), jnp.uint32),
        values=sds(lead + (kb, nb, cap), dtype),
        scale=sds(lead + (nb * bn,), jnp.float32) if with_scale else None,
        shape=(k, n), block=block)


def balanced_capacity(density: float, block: Tuple[int, int] = DEFAULT_BLOCK) -> int:
    bk, bn = block
    return min(_ceil_to(max(int(round(density * bk * bn)), 1), LANE), bk * bn)
