"""Sparse-KV flash-decode Pallas kernels — paper §6 on TPU.

The paper prunes the cached K/V values with unstructured magnitude pruning
(30%/50% with <1% accuracy loss) and adapts its sparse kernel to the QK^T and
RV batched matmuls.  Here the compressed **frozen prefix** of the KV cache
(bitmap + packed values per 128-token block, packed once after prefill —
paper §6.2's constant-size cache-in-model-state design) plus the dense
**dynamic tail** ring are consumed by flash-decoding kernels.

Two entry points share one online-softmax core:

* :func:`sparse_decode_attention_fused_pallas` — the serving hot path.
  Grid ``(B, Hkv, Sb + Tb)`` with the sequence axis innermost/sequential:
  the first ``Sb`` steps decompress one (bs, D) compressed prefix block
  each (skipping past each slot's valid-block count), the remaining ``Tb``
  steps load dense (bs, D) panels straight from the ``[B, Hkv, T, D]``
  tail ring under a per-slot ``tail_len`` validity mask held in SMEM.  The
  same VMEM online-softmax scratch runs across both phases, so ONE
  ``pallas_call`` produces the final attention output — no ``lse`` output,
  no XLA-side tail attention, no lse merge, and no ``jnp.repeat`` GQA head
  materialization anywhere on the per-token path.

  The query operand is a *panel*: ``[B, Hkv, Q*G, D]`` rows ordered
  query-major within the GQA group (``row // G`` is the query's panel
  index).  ``Q == 1`` is the plain decode tick; ``Q == K+1`` is the
  speculative-decoding verify step, where panel query ``j`` additionally
  sees the ``j`` tail tokens its panel predecessors appended — the
  intra-window causal mask is ``token < tail_len + j``, applied per row
  against the same SMEM ``tail_len`` scalar.  The compressed prefix is
  fully visible to every panel query, so the prefix phase is untouched.

* :func:`sparse_decode_attention_pallas` — the prefix-*partial* entry:
  returns ``(out, lse)`` over the compressed prefix only.  Kept for the
  context-parallel decode path (``repro.distributed.cp_attention``), where
  per-shard partials must cross chips before the merge, so fusing the tail
  into the kernel is structurally impossible.

Each sequence step does the (G, bs) score panel for the GQA head group on
the MXU and maintains online softmax statistics in VMEM scratch.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import jax.experimental.pallas as pl
import jax.experimental.pallas.tpu as pltpu

from .common import decompress_block, tile_view

NEG_INF = -1e30


def _online_update(q, k_blk, v_blk, acc_ref, m_ref, l_ref, *, sm_scale,
                   valid=None):
    """One flash step: score a (bs, D) panel against the (G, D) query group
    and fold it into the online-softmax scratch state (all 2-D: the row
    statistics live broadcast over 128 lanes)."""
    s = jax.lax.dot_general(q, k_blk, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * sm_scale
    if valid is not None:                                    # (rows, bs) mask
        s = jnp.where(valid, s, NEG_INF)
    m_prev = m_ref[:, :1]                                    # (G, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)                                   # (G, bs)
    if valid is not None:
        p = jnp.where(valid, p, 0.0)
    l_new = l_ref[:, :1] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = (acc_ref[...] * alpha
                    + jnp.dot(p, v_blk,
                              preferred_element_type=jnp.float32))
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)


def _init_state(acc_ref, m_ref, l_ref):
    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)


def _prefix_step(q_ref, kbm_ref, kval_ref, vbm_ref, vval_ref, acc_ref,
                 m_ref, l_ref, *, bs, d, sm_scale):
    """Decompress one (bs, D) K and V prefix block and fold it in."""
    k_blk = decompress_block(kbm_ref[0], kval_ref[0], bs, d,
                             dtype=jnp.float32)                  # (bs, D)
    v_blk = decompress_block(vbm_ref[0], vval_ref[0], bs, d,
                             dtype=jnp.float32)
    q = q_ref[0, 0].astype(jnp.float32)                          # (rows, D)
    _online_update(q, k_blk, v_blk, acc_ref, m_ref, l_ref, sm_scale=sm_scale)


def _kernel(nb_ref, q_ref, kbm_ref, kval_ref, vbm_ref, vval_ref,
            o_ref, lse_ref, acc_ref, m_ref, l_ref, *, bs, d, sm_scale):
    s_idx = pl.program_id(2)

    @pl.when(s_idx == 0)
    def _init():
        _init_state(acc_ref, m_ref, l_ref)

    # Per-slot valid-block count (pooled cache: each request fills only a
    # prefix of the fixed-capacity block storage).  Blocks past it are
    # skipped entirely — zero compute, zero softmax contribution.
    @pl.when(s_idx < nb_ref[pl.program_id(0)])
    def _block():
        _prefix_step(q_ref, kbm_ref, kval_ref, vbm_ref, vval_ref, acc_ref,
                     m_ref, l_ref, bs=bs, d=d, sm_scale=sm_scale)

    @pl.when(s_idx == pl.num_programs(2) - 1)
    def _done():
        l_safe = jnp.maximum(l_ref[...], 1e-30)                  # (G, 128)
        o_ref[0, 0] = (acc_ref[...] / l_safe[:, :1]).astype(o_ref.dtype)
        lse_ref[0, 0] = (m_ref[...] + jnp.log(l_safe)).astype(lse_ref.dtype)


def _flat_views(b, hkv, sb, *leaves):
    """``[B, Hkv, Sb, X]`` prefix leaves -> kernel tile views, plus the
    index map that fetches block ``(slot, head, min(s, sb-1))``."""
    views = [tile_view(a) for a in leaves]
    blk = lambda bb, h, s, *_: ((bb * hkv + h) * sb + jnp.minimum(s, sb - 1),
                                0, 0)
    return views, blk


@partial(jax.jit, static_argnames=("bs", "sm_scale", "interpret"))
def sparse_decode_attention_pallas(
        q: jax.Array,
        k_bitmap: jax.Array, k_values: jax.Array,
        v_bitmap: jax.Array, v_values: jax.Array,
        n_blocks: jax.Array | None = None, *,
        bs: int, sm_scale: float, interpret: bool):
    """Prefix-partial attention over the compressed cache.

    Kept for the context-parallel path (per-shard partials merge across
    chips); single-chip decode uses the fused entry below.

    q:         [B, Hkv, G, D]
    k_bitmap:  uint32 [B, Hkv, Sb, bs*D//32]   (same for v_bitmap)
    k_values:  [B, Hkv, Sb, Ck]                (v_values: [.., Cv])
    n_blocks:  optional int32 [B] — per-slot count of *valid* sequence
               blocks (pooled serving cache); blocks past it are skipped.
               None means every block is valid.
    Returns (out [B, Hkv, G, D] f32, lse [B, Hkv, G] f32).
    """
    b, hkv, g, d = q.shape
    sb = k_bitmap.shape[2]
    if n_blocks is None:
        n_blocks = jnp.full((b,), sb, jnp.int32)
    views, blk = _flat_views(b, hkv, sb, k_bitmap, k_values,
                             v_bitmap, v_values)
    bcast = lambda bb, h, s, *_: (bb, h, 0, 0)

    out, lse = pl.pallas_call(
        partial(_kernel, bs=bs, d=d, sm_scale=sm_scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, hkv, sb),
            in_specs=[pl.BlockSpec((1, 1, g, d), bcast)]
            + [pl.BlockSpec((1,) + a.shape[1:], blk) for a in views],
            out_specs=[
                pl.BlockSpec((1, 1, g, d), bcast),
                pl.BlockSpec((1, 1, g, 128), bcast),
            ],
            scratch_shapes=[
                pltpu.VMEM((g, d), jnp.float32),
                pltpu.VMEM((g, 128), jnp.float32),
                pltpu.VMEM((g, 128), jnp.float32),
            ]),
        out_shape=[
            jax.ShapeDtypeStruct((b, hkv, g, d), jnp.float32),
            jax.ShapeDtypeStruct((b, hkv, g, 128), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="sparse_decode_attention",
    )(n_blocks.astype(jnp.int32), q, *views)
    return out, lse[..., 0]


def _fused_kernel(nb_ref, tl_ref, q_ref, kbm_ref, kval_ref, vbm_ref,
                  vval_ref, kt_ref, vt_ref, o_ref, acc_ref, m_ref, l_ref,
                  *, bs, d, sm_scale, sb, g):
    """Prefix + tail in one sequential sweep.

    Steps ``[0, sb)`` walk the compressed prefix blocks (gated by the
    per-slot valid-block count, a scalar-prefetch operand in SMEM); steps
    ``[sb, sb+tb)`` walk the dense tail ring (gated per token by the
    per-slot ``tail_len``, likewise in SMEM).  One online-softmax scratch
    state spans both phases, so the final step writes the fully-normalized
    attention output — no lse ever leaves the kernel.

    The query block is ``(Q*g, D)`` rows ordered query-major within the
    GQA group; tail validity is per row — panel query ``row // g`` sees
    ``tail_len + row // g`` tail tokens (the extra ones are the K/V its
    panel predecessors appended).  ``Q == 1`` reduces to the plain
    single-query mask ``token < tail_len``.
    """
    s_idx = pl.program_id(2)
    slot = pl.program_id(0)
    n_valid, t_len = nb_ref[slot], tl_ref[slot]

    @pl.when(s_idx == 0)
    def _init():
        _init_state(acc_ref, m_ref, l_ref)

    @pl.when(jnp.logical_and(s_idx < sb, s_idx < n_valid))
    def _prefix_block():
        _prefix_step(q_ref, kbm_ref, kval_ref, vbm_ref, vval_ref, acc_ref,
                     m_ref, l_ref, bs=bs, d=d, sm_scale=sm_scale)

    tail_base = (s_idx - sb) * bs
    qg = q_ref.shape[2]

    @pl.when(jnp.logical_and(s_idx >= sb,
                             tail_base < t_len + (qg // g - 1)))
    def _tail_block():
        k_blk = kt_ref[0, 0].astype(jnp.float32)                 # (bs, D)
        v_blk = vt_ref[0, 0].astype(jnp.float32)
        q = q_ref[0, 0].astype(jnp.float32)
        # per-row visibility limit: query j (= row // g) sees tail_len + j
        row_q = jax.lax.broadcasted_iota(jnp.int32, (qg, bs), 0) // g
        tok = tail_base + jax.lax.broadcasted_iota(jnp.int32, (qg, bs), 1)
        _online_update(q, k_blk, v_blk, acc_ref, m_ref, l_ref,
                       sm_scale=sm_scale, valid=tok < t_len + row_q)

    @pl.when(s_idx == pl.num_programs(2) - 1)
    def _done():
        l_safe = jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l_safe).astype(o_ref.dtype)


def _fused_kernel_paged(layer_ref, tbl_ref, *refs, **kw):
    """Paged wrapper: the layer index and the block table ride in as
    scalar-prefetch operands consumed ONLY by the index maps (which tile of
    the stacked arena the prefix phase fetches); the kernel body is
    byte-for-byte the flat ``_fused_kernel``."""
    del layer_ref, tbl_ref
    _fused_kernel(*refs, **kw)


@partial(jax.jit, static_argnames=("bs", "sm_scale", "interpret", "group"))
def sparse_decode_attention_fused_pallas(
        q: jax.Array,
        k_bitmap: jax.Array, k_values: jax.Array,
        v_bitmap: jax.Array, v_values: jax.Array,
        k_tail: jax.Array, v_tail: jax.Array,
        n_blocks: jax.Array | None = None,
        tail_len: jax.Array | None = None,
        block_table: jax.Array | None = None,
        layer: jax.Array | None = None, *,
        bs: int, sm_scale: float, interpret: bool,
        group: int | None = None) -> jax.Array:
    """Fused prefix+tail flash-decode: final attention in ONE pallas_call.

    q:             [B, Hkv, Q*G, D] query panel, rows ordered query-major
                   within the GQA group (``row // G`` = panel index).
                   ``group=G`` declares the group size; None means the
                   whole row axis is one query (``Q == 1`` — the plain
                   decode tick).
    k_bitmap:      uint32 [B, Hkv, Sb, bs*D//32]   (same for v_bitmap)
    k_values:      [B, Hkv, Sb, Ck]                (v_values: [.., Cv])
    k_tail/v_tail: dense tail ring [B, Hkv, Tp, D] with ``Tp % bs == 0``
                   (the dispatcher zero-pads the ring to a whole number of
                   (bs,)-token panels; padding is masked by ``tail_len``).
    n_blocks:      optional int32 [B] — per-slot valid prefix blocks;
                   None means all ``Sb`` are valid.
    tail_len:      optional int32 [B] — tail tokens visible to panel query
                   0; query ``j`` sees ``tail_len + j`` (intra-window
                   causal — the verify step appends one K/V per panel
                   query).  None means the whole ring is valid to query 0.
    block_table:   optional int32 [B, Sb] — PAGED prefix: the bitmap/values
                   operands are then the pool-global arena of every layer,
                   ``[P, n_phys, Hkv, rows, 128]`` tiles as the pool
                   stores them, and the grid's prefix phase loads the tile
                   ``(layer, block_table[slot, i], head)`` in place instead
                   of slot block ``(slot, i)``.  The table rides in as a
                   scalar-prefetch operand so the index maps (which run
                   ahead of the kernel body to schedule the block DMAs)
                   can read it; every entry must address real storage
                   (``< n_phys``) even past ``n_blocks`` — dead fetches
                   are gated off the softmax by the same ``n_blocks``
                   check as the flat path, so they are never *read*.
    layer:         int32 scalar, paged only — the arena's period index,
                   likewise a scalar-prefetch operand.
    Per-slot ``n_blocks``/``tail_len`` (and the table) are scalar-prefetch
    operands; a flat block is fetched as a tile whose last two dims are the
    array's own (:func:`repro.kernels.common.tile_view`).  The paged arena
    is already stored as tiles, so no view of it is taken: the kernel
    reads the pool's own buffers.
    Returns out [B, Hkv, Q*G, D] f32 — softmax-normalized over the union
    of valid prefix and tail positions (all-empty slots return zeros).
    """
    b, hkv, qg, d = q.shape
    g = group or qg
    if qg % g != 0:
        raise ValueError(f"query panel {qg} not a multiple of group {g}")
    paged = block_table is not None
    leaves = (k_bitmap, k_values, v_bitmap, v_values)
    if paged:
        if k_bitmap.ndim != 5 or layer is None:
            raise ValueError("the paged arena is [P, n_phys, Hkv, rows, 128] "
                             f"tiles with a layer index, got {k_bitmap.shape}")
        sb = block_table.shape[1]
        views = list(leaves)
        # THE paged change: the prefix phase's tile index goes through the
        # table, in the layer's slice of the stacked arena
        pre = lambda bb, h, s, lyr, tbl, nb, tl: (
            lyr[0], tbl[bb, jnp.minimum(s, sb - 1)], h, 0, 0)
    else:
        sb = k_bitmap.shape[2]
        views, pre = _flat_views(b, hkv, sb, *leaves)
    tp = k_tail.shape[2]
    if not (sb >= 1 and tp >= bs and tp % bs == 0):
        raise ValueError(f"bad geometry: sb={sb}, tail={tp}, block={bs}")
    tb = tp // bs
    if n_blocks is None:
        n_blocks = jnp.full((b,), sb, jnp.int32)
    if tail_len is None:
        tail_len = jnp.full((b,), tp, jnp.int32)
    scalars = [n_blocks.astype(jnp.int32), tail_len.astype(jnp.int32)]
    if paged:
        scalars[:0] = [jnp.reshape(layer, (1,)).astype(jnp.int32),
                       block_table.astype(jnp.int32)]

    # index maps clamp into range on the other phase's steps (the fetched
    # block is ignored there — the pl.when gates never fire)
    tail = lambda bb, h, s, *_: (bb, h, jnp.maximum(s - sb, 0), 0)
    bcast = lambda bb, h, s, *_: (bb, h, 0, 0)
    kernel = _fused_kernel_paged if paged else _fused_kernel
    # a prefix block is one (rows, 128) tile, under a unit block axis
    block = ((lambda a: (None, None, 1) + a.shape[3:]) if paged
             else (lambda a: (1,) + a.shape[1:]))
    return pl.pallas_call(
        partial(kernel, bs=bs, d=d, sm_scale=sm_scale, sb=sb, g=g),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=(b, hkv, sb + tb),
            in_specs=[pl.BlockSpec((1, 1, qg, d), bcast)]
            + [pl.BlockSpec(block(a), pre) for a in views]
            + [pl.BlockSpec((1, 1, bs, d), tail)] * 2,
            out_specs=pl.BlockSpec((1, 1, qg, d), bcast),
            scratch_shapes=[
                pltpu.VMEM((qg, d), jnp.float32),
                pltpu.VMEM((qg, 128), jnp.float32),
                pltpu.VMEM((qg, 128), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((b, hkv, qg, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name=("sparse_decode_attention_fused_paged" if paged
              else "sparse_decode_attention_fused"),
    )(*scalars, q, *views, k_tail, v_tail)
