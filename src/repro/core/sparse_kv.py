"""Sparse KV cache: compressed frozen prefix + dense dynamic tail (paper §6.2).

The paper observes PyTorch's cache-update path (realloc + ``repeat_kv`` per
token) is >6x slower than freezing the prefill cache in model state and
appending new tokens to a small separate buffer.  We reproduce that design:

* after prefill, K and V are magnitude-pruned (paper: 30% K / 50% V keeps
  downstream accuracy within 1%) and packed with the standard blocked format
  — one (bs=128 tokens, D) block per bitmap row, viewed as [B*Hkv*S, D];
* newly decoded tokens land in a fixed-size dense ring ``tail`` with a
  monotone ``tail_len`` (no realloc, no concatenation on the hot path);
* when the tail fills, ``refreeze`` compresses it into the prefix (off the
  per-token hot path, amortized).

Two cache families build on these primitives:

* :class:`SparseKVCache` — the legacy one-shot layout (data-dependent
  capacity; refreeze grows shapes, so jitted consumers re-trace);
* the **pooled** layout (``freeze_chunk_blocks`` / ``pooled_view``) used by
  ``repro.serving.CachePool`` — per-block storage at a *static* capacity so
  refreeze is an in-place scatter and the serving decode step never
  re-traces.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from .sparse_format import (BlockSparseWeight, pack, pack_blocks,
                            packed_spec, balanced_capacity, unpack)
from .pruning import prune_kv

KV_BLOCK_TOKENS = 128


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class SparseKVCache:
    """Per-layer compressed KV state.

    k_sp/v_sp: packed from the [B*Hkv*S, D] cache view, block (bs, D).
    k_tail/v_tail: dense [B, Hkv, T, D] ring for fresh tokens.
    tail_len: int32 scalar — valid tail entries.
    """
    k_sp: BlockSparseWeight
    v_sp: BlockSparseWeight
    k_tail: jax.Array
    v_tail: jax.Array
    tail_len: jax.Array

    def tree_flatten(self):
        return (self.k_sp, self.v_sp, self.k_tail, self.v_tail,
                self.tail_len), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @property
    def prefix_len(self) -> int:
        b, hkv, _, d = self.k_tail.shape
        return self.k_sp.shape[0] // (b * hkv)


def freeze_prefix(k: jax.Array, v: jax.Array,
                  k_sparsity: float = 0.3, v_sparsity: float = 0.5,
                  tail_size: int = 128,
                  bs: int = KV_BLOCK_TOKENS,
                  capacity_k: Optional[int] = None,
                  capacity_v: Optional[int] = None,
                  structured: bool = True) -> SparseKVCache:
    """Prune + pack a dense prefill cache ``k/v [B, Hkv, S, D]``.

    structured=True stores block arrays as [B, Hkv, Sb, 1, ...] so the
    batch / head / sequence-block dims shard independently (context-parallel
    decode); False keeps the flat [(B*Hkv*Sb), 1, ...] layout.
    """
    b, hkv, s, d = k.shape
    if s % bs != 0:
        raise ValueError(f"prefix length {s} must be a multiple of {bs}")
    kf = k.reshape(b * hkv * s, d)
    vf = v.reshape(b * hkv * s, d)
    k_sp = pack(kf, prune_kv(kf, k_sparsity), block=(bs, d),
                capacity=capacity_k)
    v_sp = pack(vf, prune_kv(vf, v_sparsity), block=(bs, d),
                capacity=capacity_v)
    if structured:
        k_sp = structure_kv(k_sp, b, hkv)
        v_sp = structure_kv(v_sp, b, hkv)
    zeros = jnp.zeros((b, hkv, tail_size, d), k.dtype)
    return SparseKVCache(k_sp, v_sp, zeros, zeros,
                         jnp.zeros((), jnp.int32))


def structure_kv(sw: BlockSparseWeight, b: int, hkv: int
                 ) -> BlockSparseWeight:
    """Flat [(B*Hkv*Sb), 1, X] block arrays -> [B, Hkv, Sb, 1, X].

    aux ``shape`` becomes the per-(b,h) logical (S, D) so ``unpack`` yields
    [B, Hkv, S, D] directly (leading dims broadcast through decompression).
    """
    rows_total, nb, _ = sw.bitmap.shape
    sb = rows_total // (b * hkv)
    bs, d = sw.block
    re = lambda a: a.reshape(b, hkv, sb, nb, a.shape[-1])
    return BlockSparseWeight(
        bitmap=re(sw.bitmap), values=re(sw.values),
        scale=sw.scale, shape=(sb * bs, d), block=sw.block,
        packed4=sw.packed4)


def append_token(cache: SparseKVCache, k_new: jax.Array,
                 v_new: jax.Array) -> SparseKVCache:
    """O(1) per-token append into the dense tail (no realloc, paper §6.2)."""
    idx = cache.tail_len
    k_tail = jax.lax.dynamic_update_slice_in_dim(
        cache.k_tail, k_new[:, :, None, :], idx, axis=2)
    v_tail = jax.lax.dynamic_update_slice_in_dim(
        cache.v_tail, v_new[:, :, None, :], idx, axis=2)
    return SparseKVCache(cache.k_sp, cache.v_sp, k_tail, v_tail, idx + 1)


def refreeze(cache: SparseKVCache,
             k_sparsity: float = 0.3, v_sparsity: float = 0.5
             ) -> SparseKVCache:
    """Fold a full tail back into the compressed prefix (paper §6.2's
    amortized off-hot-path step: "when the tail fills").

    The tail must be block-aligned (tail_size % bs == 0) and full; the
    result has a longer prefix, an empty tail, and (possibly) a larger
    capacity — callers decode against it with the same kernels.
    """
    b, hkv, t, d = cache.k_tail.shape
    bs = cache.k_sp.block[0]
    if t % bs != 0:
        raise ValueError(f"tail {t} not a multiple of block {bs}")
    structured = cache.k_sp.bitmap.ndim == 5
    k_pref = unpack(cache.k_sp)
    v_pref = unpack(cache.v_sp)
    if not structured:
        s = cache.k_sp.shape[0] // (b * hkv)
        k_pref = k_pref.reshape(b, hkv, s, d)
        v_pref = v_pref.reshape(b, hkv, s, d)
    k = jnp.concatenate([k_pref, cache.k_tail.astype(k_pref.dtype)], axis=2)
    v = jnp.concatenate([v_pref, cache.v_tail.astype(v_pref.dtype)], axis=2)
    # note: the old prefix is already pruned; re-pruning is a no-op on it
    # beyond threshold drift, matching the paper's layer-wide magnitude rule
    return freeze_prefix(k, v, k_sparsity, v_sparsity, tail_size=t, bs=bs,
                         structured=structured)


def maybe_refreeze(cache: SparseKVCache, k_sparsity: float,
                   v_sparsity: float) -> SparseKVCache:
    """Host-side helper: refreeze when the tail is full (static check via
    concrete tail_len; used by the serving engine between jitted steps)."""
    t = cache.k_tail.shape[2]
    # documented sync: this helper is the host boundary by design
    if int(cache.tail_len) >= t:  # jitlint: disable=host-sync
        return refreeze(cache, k_sparsity, v_sparsity)
    return cache


# ---------------------------------------------------------------------------
# pooled-cache primitives (serving CachePool — jit-stable, static shapes)
# ---------------------------------------------------------------------------

def freeze_chunk_blocks(k: jax.Array, v: jax.Array,
                        k_sparsity: float, v_sparsity: float,
                        bs: int, cap_k: int, cap_v: int):
    """Compress a block-aligned K/V chunk at *static* per-block capacities.

    ``k/v [B, Hkv, C, D]`` with ``C % bs == 0`` -> ``(k_bitmap [B, Hkv, Cb,
    bs*D//32], k_values [B, Hkv, Cb, cap_k], v_bitmap, v_values)``.

    The magnitude threshold is computed per ``(batch entry, token block)``
    — the paper's layer-wide rule applied per request slot at block
    granularity — then each ``(bs, D)`` token block is packed at the
    pool's fixed capacity via :func:`pack_blocks` — if pruning leaves a
    block denser than the capacity, the overflow is dropped consistently
    from bitmap and values.  Per-*block* (not per-chunk) thresholds are a
    sharing invariant, not a tuning choice: they make a frozen block's
    compressed bytes a pure function of the tokens up to the block's end,
    independent of how prefill happened to be chunked — the property the
    paged cache's content-addressed block index relies on.  Everything
    here is traceable with static shapes, so the serving refreeze can run
    inside a once-compiled ``jax.jit``.
    """
    b, hkv, c, d = k.shape
    if c % bs != 0:
        raise ValueError(f"context {c} not a multiple of block {bs}")
    nb = c // bs

    def block_masks(a, sparsity):
        # [B, Hkv, C, D] -> per-(slot, block) thresholds over (Hkv, bs, D)
        ab = a.reshape(b, hkv, nb, bs, d).transpose(0, 2, 1, 3, 4)
        m = jax.vmap(jax.vmap(lambda x: prune_kv(x, sparsity)))(ab)
        return m.transpose(0, 2, 1, 3, 4).reshape(b, hkv, c, d)

    mask_k = block_masks(k, k_sparsity)
    mask_v = block_masks(v, v_sparsity)

    def blocks(a):
        return a.reshape(b, hkv, c // bs, bs * d)
    k_bm, k_vals = pack_blocks(blocks(k), blocks(mask_k), cap_k)
    v_bm, v_vals = pack_blocks(blocks(v), blocks(mask_v), cap_v)
    return k_bm, k_vals, v_bm, v_vals


def append_tail_panel(tail: jax.Array, new: jax.Array, tail_len: jax.Array,
                      n_valid: jax.Array) -> jax.Array:
    """Masked multi-token append into the dense tail ring.

    ``tail [B, Hkv, T, D]``; ``new [B, Hkv, m, D]`` — up to ``m`` fresh
    K/V tokens per slot, written at each slot's own ``tail_len`` offset;
    ``n_valid int32 [B]`` (or scalar) — how many of the ``m`` panel tokens
    slot ``b`` actually writes (0 = pure passthrough).  Writes that would
    land past the ring end are dropped (the caller's rollback/refreeze
    bookkeeping guarantees the *kept* tokens always fit; only never-kept
    panel padding can overflow).  One batched scatter at static shapes —
    invalid panel tokens route to an out-of-bounds row and fall to
    ``mode="drop"``, so the ring is written in a single pass and the
    speculative verify step jits once per panel width.
    """
    b, _, t, _ = tail.shape
    m = new.shape[2]
    tail_len = jnp.broadcast_to(jnp.asarray(tail_len, jnp.int32), (b,))
    n_valid = jnp.broadcast_to(jnp.asarray(n_valid, jnp.int32), (b,))
    j = jnp.arange(m)
    off = tail_len[:, None] + j[None, :]                       # [B, m]
    ok = (j[None, :] < n_valid[:, None]) & (off < t)
    idx = jnp.where(ok, off, t)                                # t => dropped
    return jax.vmap(lambda tl, nw, ix: tl.at[:, ix].set(
        nw.astype(tl.dtype), mode="drop"))(tail, new, idx)


# the compressed-block storage leaves of one layer's kv tree: the paged
# arena's persistent content (tails are private in-flight state)
ARENA_KEYS = ("k_bitmap", "k_values", "v_bitmap", "v_values")
ARENA_LANES = 128
# a tile's rows are stored as a multiple of this: the chip lays a
# [..., Hkv, rows, 128] arena out in (8, 128) tiles only when the rows fill
# them, and otherwise puts Hkv minor to the rows, which the fused kernel's
# block fetch would have relaid out (a copy of the whole arena) every step
ARENA_SUBLANES = 8


def arena_rows(x: int) -> int:
    """Stored rows of one paged-arena block whose packed leaf holds ``x``
    words or values: ``ceil(x / 128)`` rounded up to a multiple of 8."""
    rows = -(-x // ARENA_LANES)
    return -(-rows // ARENA_SUBLANES) * ARENA_SUBLANES


def to_arena_tiles(a: jax.Array, rows: int) -> jax.Array:
    """Packed blocks ``[..., X]`` -> arena tiles ``[..., rows, 128]``, the
    padding zero (never selected: a block's bitmap words and values occupy
    its first ``X`` entries)."""
    pad = rows * ARENA_LANES - a.shape[-1]
    a = jnp.pad(a, [(0, 0)] * (a.ndim - 1) + [(0, pad)])
    return a.reshape(*a.shape[:-1], rows, ARENA_LANES)


def from_arena_tiles(a: jax.Array, x: Optional[int] = None) -> jax.Array:
    """Arena tiles ``[..., rows, 128]`` -> packed blocks ``[..., X]`` (the
    stored width when ``x`` is None: the padding values are zeros that no
    bitmap bit selects)."""
    flat = a.reshape(*a.shape[:-2], a.shape[-2] * a.shape[-1])
    return flat if x is None else flat[..., :x]


def pooled_view(bitmap: jax.Array, values: jax.Array, bs: int, d: int
                ) -> BlockSparseWeight:
    """Pooled block arrays ``[B, Hkv, Sb, X]`` -> the structured
    ``BlockSparseWeight`` view (``[B, Hkv, Sb, 1, X]``) the decode-attention
    kernels consume.  Zero-copy (reshape only)."""
    sb = bitmap.shape[2]
    return BlockSparseWeight(
        bitmap=bitmap[:, :, :, None, :], values=values[:, :, :, None, :],
        scale=None, shape=(sb * bs, d), block=(bs, d))


def abstract_cache(batch: int, hkv: int, prefix: int, d: int,
                   k_density: float = 0.7, v_density: float = 0.5,
                   tail_size: int = 128, bs: int = KV_BLOCK_TOKENS,
                   dtype=jnp.bfloat16,
                   structured: bool = True) -> SparseKVCache:
    """ShapeDtypeStruct cache for the dry-run (no allocation)."""
    sds = jax.ShapeDtypeStruct
    if structured:
        k_sp = packed_spec(prefix, d, k_density, block=(bs, d), dtype=dtype,
                           lead=(batch, hkv))
        v_sp = packed_spec(prefix, d, v_density, block=(bs, d), dtype=dtype,
                           lead=(batch, hkv))
    else:
        rows = batch * hkv * prefix
        k_sp = packed_spec(rows, d, k_density, block=(bs, d), dtype=dtype)
        v_sp = packed_spec(rows, d, v_density, block=(bs, d), dtype=dtype)
    tail = sds((batch, hkv, tail_size, d), dtype)
    return SparseKVCache(k_sp, v_sp, tail, tail, sds((), jnp.int32))
