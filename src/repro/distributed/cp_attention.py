"""Context-parallel sparse-KV flash-decode (§Perf iteration 1).

Baseline problem (measured in EXPERIMENTS.md §Perf): letting the XLA
partitioner handle the decode-attention einsums over a (data x model)-
sharded compressed cache replicates the per-(b,h) score computation across
the model axis and all-gathers cache shards — ~2 orders of magnitude of
extra HBM+ICI traffic per token.

Fix: shard_map the whole prefix attention so every chip touches ONLY its
local cache blocks (batch over dp, sequence-blocks over the remaining
axes), computes a local flash partial (o_i, lse_i), and merges partials
with one tiny pair of collectives per layer:

    m*  = pmax(lse_i)
    w_i = exp(lse_i - m*)                 # = l_i * exp(m_i - m*)
    o   = psum(o_i * w_i) / psum(w_i)     # [B, Hq, D] + [B, Hq] psum only

The dense dynamic tail is computed redundantly per shard (it's ~128 tokens)
and merged locally after the combine, so it never enters the psum.

NOTE — this module is deliberately **pinned to the partial+merge entry
points** (``ref.gqa_partial_ref`` / ``ref._merge_attn`` /
``ref.sparse_decode_attention_ref`` and, on TPU, the prefix-partial
``sparse_decode_attention_pallas``): the per-shard (o_i, lse_i) partials
must cross chips before they can be normalized, so the single-chip fused
prefix+tail kernel (``sparse_decode_attention_fused_pallas``, used by
``ops.sparse_decode_attention`` everywhere else) structurally cannot apply
here.  Everything outside this module goes through the fused path.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.sparse_format import BlockSparseWeight, unpack
from repro.core.sparse_kv import SparseKVCache
from repro.kernels import ref


def _local_partial(q, k_sp_leaves, v_sp_leaves, sw_meta, hkv, sm_scale):
    """Flash partial over the local cache blocks (grouped GQA — no
    repeat_kv materialization, bf16 cache operands). Returns (o, lse) with
    o/lse shaped [B_loc, Hkv, G, ...]."""
    (kbm, kvv), (vbm, vvv) = k_sp_leaves, v_sp_leaves
    shape, block = sw_meta
    k_sp = BlockSparseWeight(kbm, kvv, None, shape, block)
    v_sp = BlockSparseWeight(vbm, vvv, None, shape, block)
    k = unpack(k_sp)            # [B_loc, Hkv, S_loc, D] (bf16)
    v = unpack(v_sp)
    b, hq, d = q.shape
    qg = q.reshape(b, hkv, hq // hkv, d)
    return ref.gqa_partial_ref(qg, k, v, sm_scale)


def sparse_decode_attention_cp(q: jax.Array, cache: SparseKVCache,
                               hkv: int, sm_scale: float, ctx
                               ) -> jax.Array:
    """q [B, Hq, D]; cache structured (bitmap [B, Hkv, Sb, 1, W])."""
    mesh = ctx.mesh
    b, hq, d = q.shape
    kb = cache.k_sp.bitmap
    if kb.ndim != 5:
        raise ValueError("context-parallel path needs the structured layout")
    sb = kb.shape[2]

    dp = ctx.rules.get("batch")
    dp = tuple(a for a in (dp if isinstance(dp, (tuple, list)) else (dp,))
               if a is not None)
    dp_size = 1
    for a in dp:
        dp_size *= mesh.shape[a]
    b_axes = dp if (dp_size > 1 and b % dp_size == 0) else ()
    tp = ctx.rules.get("ffn")
    seq_axes = tuple(a for a in ((tp,) if b_axes else dp + (tp,))
                     if a is not None)
    seq_size = 1
    for a in seq_axes:
        seq_size *= mesh.shape[a]
    if seq_size <= 1 or sb % seq_size != 0:
        # cannot context-shard: fall back to the replicated two-pass
        # reference (this path stays partial+merge by design — see the
        # module docstring)
        return ref.sparse_decode_attention_ref(
            q, cache.k_sp, cache.v_sp, sm_scale, cache.k_tail,
            cache.v_tail, cache.tail_len)

    bspec = b_axes if b_axes else None
    blk5 = P(bspec, None, seq_axes, None, None)
    tail_spec = P(bspec, None, None, None)
    q_spec = P(bspec, None, None)
    meta = (cache.k_sp.shape, cache.k_sp.block)

    def body(qL, kbm, kvv, vbm, vvv, ktL, vtL, tlen):
        o, lse = _local_partial(qL, (kbm, kvv), (vbm, vvv), meta, hkv,
                                sm_scale)                # [B,Hkv,G,...]
        m_star = jax.lax.pmax(lse, seq_axes)
        w = jnp.exp(lse - m_star)
        num = jax.lax.psum(o * w[..., None], seq_axes)
        den = jax.lax.psum(w, seq_axes)
        o_pref = num / jnp.maximum(den, 1e-30)[..., None]
        lse_pref = m_star + jnp.log(jnp.maximum(den, 1e-30))
        # dense tail: tiny, computed redundantly per shard, merged locally
        t = ktL.shape[2]
        bl, hq_l, d_l = qL.shape
        if t > 0:
            valid = jnp.broadcast_to(jnp.arange(t)[None, :] < tlen, (bl, t))
            qg = qL.reshape(bl, hkv, hq_l // hkv, d_l)
            o_t, lse_t = ref.gqa_partial_ref(qg, ktL, vtL, sm_scale, valid)
            empty = ~jnp.any(valid, axis=-1)
            lse_t = jnp.where(empty[:, None, None], lse_pref - 60.0, lse_t)
            lse_t = jnp.where(jnp.isfinite(lse_t), lse_t, lse_pref - 60.0)
            o_pref, _ = ref._merge_attn(o_pref, lse_pref, o_t, lse_t)
        return o_pref.reshape(bl, hq_l, d_l).astype(qL.dtype)

    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(q_spec, blk5, blk5, blk5, blk5, tail_spec, tail_spec,
                  P()),
        out_specs=q_spec, check_vma=False)
    return fn(q, cache.k_sp.bitmap, cache.k_sp.values, cache.v_sp.bitmap,
              cache.v_sp.values, cache.k_tail, cache.v_tail,
              cache.tail_len)
