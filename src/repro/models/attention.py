"""GQA attention (train full-sequence, decode with dense or sparse-KV cache).

Head counts are padded to ``cfg.tp_pad`` (extra heads have zero-init wq/wo
rows so they are mathematically inert) so the head axis always shards over
the model axis; kv heads replicate when ``n_kv`` doesn't divide TP
(DESIGN.md §6).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ops
from repro.core.sparse_format import unpack
from repro.core.sparse_kv import (SparseKVCache, append_tail_panel,
                                  append_token, from_arena_tiles,
                                  pooled_view)
from .module import ParamSpec
from .layers import rms_norm, rope_angles, apply_rope
from .flash import blocked_attention, full_attention


# ---------------------------------------------------------------------------
# caches
# ---------------------------------------------------------------------------

@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class DenseKVCache:
    """Baseline decode cache: preallocated [B, Hkv, S_max, D] + length."""
    k: jax.Array
    v: jax.Array
    length: jax.Array           # int32 scalar

    def tree_flatten(self):
        return (self.k, self.v, self.length), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


def init_dense_cache(batch, hkv, s_max, d, dtype=jnp.bfloat16):
    z = jax.ShapeDtypeStruct if dtype is None else None
    k = jnp.zeros((batch, hkv, s_max, d), dtype)
    return DenseKVCache(k, k, jnp.zeros((), jnp.int32))


# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------

def attn_specs(cfg, cross: bool = False) -> Dict[str, ParamSpec]:
    hq, hkv, hd, d = cfg.padded_heads, cfg.n_kv, cfg.hd, cfg.d_model
    dt = cfg.pdtype
    specs = {
        "wq": ParamSpec((d, hq * hd), dt, ("embed", "heads")),
        "wk": ParamSpec((d, hkv * hd), dt, ("embed", "kv_heads")),
        "wv": ParamSpec((d, hkv * hd), dt, ("embed", "kv_heads")),
        "wo": ParamSpec((hq * hd, d), dt, ("heads", "embed")),
    }
    if cfg.qk_norm:
        specs["q_norm"] = ParamSpec((hd,), jnp.float32, (None,), init="ones")
        specs["k_norm"] = ParamSpec((hd,), jnp.float32, (None,), init="ones")
    return specs


def _project_q(p, x, cfg):
    b = x.shape[:-1]
    q = ops.linear(x, p["wq"]).reshape(*b, cfg.padded_heads, cfg.hd)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.rms_eps)
    return q


def _project_kv(p, x, cfg):
    b = x.shape[:-1]
    k = ops.linear(x, p["wk"]).reshape(*b, cfg.n_kv, cfg.hd)
    v = ops.linear(x, p["wv"]).reshape(*b, cfg.n_kv, cfg.hd)
    if cfg.qk_norm:
        k = rms_norm(k, p["k_norm"], cfg.rms_eps)
    return k, v


def _repeat_kv(k: jax.Array, g: int) -> jax.Array:
    # train/prefill only; the decode kernels expand groups in-register
    return jnp.repeat(k, g, axis=1)  # jitlint: disable=hot-path-op


# ---------------------------------------------------------------------------
# full-sequence (train / prefill)
# ---------------------------------------------------------------------------

def attn_apply(p, x: jax.Array, cfg, ctx, positions: jax.Array,
               memory: Optional[jax.Array] = None,
               causal: Optional[bool] = None,
               attn_impl: str = "masked",
               return_kv: bool = False):
    """x [B, S, d]; memory (enc-dec cross attention source) [B, Sm, d]."""
    b, s, _ = x.shape
    hq, hkv, hd = cfg.padded_heads, cfg.n_kv, cfg.hd
    q = _project_q(p, x, cfg)                                # [B,S,Hq,hd]
    src = memory if memory is not None else x
    k, v = _project_kv(p, src, cfg)                          # [B,Sm,Hkv,hd]

    if causal is None:
        causal = memory is None
    if memory is None:
        cos, sin = rope_angles(positions, hd, cfg.rope_theta)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)

    q = q.transpose(0, 2, 1, 3)                              # [B,Hq,S,hd]
    k = _repeat_kv(k.transpose(0, 2, 1, 3), hq // hkv)
    v = _repeat_kv(v.transpose(0, 2, 1, 3), hq // hkv)
    sm = 1.0 / hd ** 0.5
    # short seqs: one einsum (scores fit per-device; also keeps the HLO flat
    # so compiled-probe cost analysis is exact).  Longer: blocked flash.
    thr = getattr(cfg, "full_attn_max", 4096)
    if s <= thr and k.shape[2] <= thr:
        o = full_attention(q, k, v, sm, causal=causal)
    else:
        o = blocked_attention(q, k, v, sm, causal=causal, impl=attn_impl)
    o = o.transpose(0, 2, 1, 3).reshape(b, s, hq * hd)
    out = ops.linear(o, p["wo"])
    if return_kv:
        g = hq // hkv
        kv = (k[:, ::g], v[:, ::g])          # un-repeated [B, Hkv, S, hd]
        return out, kv
    return out


# ---------------------------------------------------------------------------
# decode (one token, cached KV)
# ---------------------------------------------------------------------------

def attn_decode(p, x_t: jax.Array, cache, cfg, ctx,
                position: jax.Array) -> Tuple[jax.Array, Any]:
    """x_t [B, d] (single new token). cache: DenseKVCache | SparseKVCache."""
    b, _ = x_t.shape
    hq, hkv, hd = cfg.padded_heads, cfg.n_kv, cfg.hd
    g = hq // hkv
    q = _project_q(p, x_t, cfg)                              # [B,Hq,hd]
    k_new, v_new = _project_kv(p, x_t, cfg)                  # [B,Hkv,hd]
    cos, sin = rope_angles(position, hd, cfg.rope_theta)     # scalar pos
    q = apply_rope(q[:, None], cos[None, None], sin[None, None])[:, 0]
    k_new = apply_rope(k_new[:, None], cos[None, None], sin[None, None])[:, 0]
    sm = 1.0 / hd ** 0.5

    if isinstance(cache, SparseKVCache):
        cache = append_token(cache, k_new, v_new)
        if (getattr(cfg, "cp_decode", False) and ctx is not None
                and ctx.mesh is not None and cache.k_sp.bitmap.ndim == 5):
            # context-parallel: the only surviving partial+merge consumer
            from repro.distributed.cp_attention import \
                sparse_decode_attention_cp
            o = sparse_decode_attention_cp(q, cache, hkv, sm, ctx)
        else:
            # fused prefix+tail flash-decode: one kernel yields the final
            # attention output (no XLA-side tail merge)
            o = ops.sparse_decode_attention(
                q, cache.k_sp, cache.v_sp, hkv, sm,
                cache.k_tail, cache.v_tail, cache.tail_len)
    else:
        idx = cache.length
        k = jax.lax.dynamic_update_slice_in_dim(
            cache.k, k_new[:, :, None, :].astype(cache.k.dtype), idx, axis=2)
        v = jax.lax.dynamic_update_slice_in_dim(
            cache.v, v_new[:, :, None, :].astype(cache.v.dtype), idx, axis=2)
        cache = DenseKVCache(k, v, idx + 1)
        valid = jnp.arange(k.shape[2])[None, :] < (idx + 1)
        valid = jnp.broadcast_to(valid, (b, k.shape[2]))
        if ctx is not None:
            k = ctx.constrain(k, ("batch", "kv_heads", "ctx", None))
            v = ctx.constrain(v, ("batch", "kv_heads", "ctx", None))
        o = full_attention(q[:, :, None, :], _repeat_kv(k, g),
                           _repeat_kv(v, g), sm, causal=False,
                           kv_valid=valid)[:, :, 0, :]

    out = ops.linear(o.reshape(b, hq * hd).astype(x_t.dtype), p["wo"])
    return out, cache


def pooled_attn_panel(p, x: jax.Array, kv: Dict[str, jax.Array], cfg,
                      ctx, positions: jax.Array, prefix_blocks: jax.Array,
                      tail_len: jax.Array, slot_mask: jax.Array, bs: int,
                      table: Optional[jax.Array] = None,
                      layer: Optional[jax.Array] = None
                      ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """THE pooled serving attention: one ``[B, Qn]`` query panel per layer.

    One function serves every per-token serving step — plain decode is the
    ``Qn == 1`` panel, speculative verify the ``Qn == K+1`` panel; the old
    ``pooled_attn_decode`` / ``pooled_attn_verify`` pair collapsed into
    this single body.  ``x [B, Qn, d]`` is each slot's panel (last
    committed token + up to ``Qn-1`` drafts), ``positions [B, Qn]`` its
    absolute positions; every slot carries its own position, prefix length
    and tail fill (``prefix_blocks``/``tail_len`` int32 ``[B]``) — the
    per-slot variable-length semantics continuous batching needs.  All
    shapes are static, so each panel width traces exactly once.

    All ``Qn`` fresh K/V land in the slot's dense tail at
    ``tail_len..tail_len+Qn-1`` (a rollback is a pure length decrement),
    and the panel is scored by the fused prefix+tail flash-decode kernel
    with a ``Qn*G``-row query block: panel query ``j`` sees the full
    frozen prefix, the pre-existing tail, and panel tokens ``<= j`` —
    intra-window causal.  At ``Qn == 1`` the ops layer squeezes the panel
    onto the exact single-query dispatch, so a decode tick is
    bit-identical to the pre-unification ``pooled_attn_decode`` path.
    Inactive slots (``slot_mask`` False) write nothing and pass their
    cache through bit-identical.

    ``table`` (int32 ``[B, Sb]``, paged pool only) switches the frozen
    prefix to the pool-global arena layout: ``kv``'s compressed leaves are
    then the shared storage of EVERY layer, ``[P, n_phys, Hkv, rows,
    128]``, ``layer`` (int32 scalar) is this layer's period, and each
    slot's blocks are reached through its table row — same math, one
    indirection on the fetch, and the arena is read where it lies.  The
    dense tail stays per-slot either way.
    """
    b, qn, _ = x.shape
    hq, hkv, hd = cfg.padded_heads, cfg.n_kv, cfg.hd
    q = _project_q(p, x, cfg)                                 # [B,Qn,Hq,hd]
    k_new, v_new = _project_kv(p, x, cfg)                     # [B,Qn,Hkv,hd]
    cos, sin = rope_angles(positions, hd, cfg.rope_theta)     # [B,Qn,hd//2]
    q = apply_rope(q, cos, sin)
    k_new = apply_rope(k_new, cos, sin)
    sm = 1.0 / hd ** 0.5

    n_valid = jnp.where(slot_mask, qn, 0)
    k_tail = append_tail_panel(kv["k_tail"], k_new.transpose(0, 2, 1, 3),
                               tail_len, n_valid)
    v_tail = append_tail_panel(kv["v_tail"], v_new.transpose(0, 2, 1, 3),
                               tail_len, n_valid)
    # panel query 0 sees its own token; each later query j sees j more
    t_att = tail_len + slot_mask.astype(jnp.int32)
    if table is not None:
        o = ops.sparse_decode_attention_paged(
            q, kv["k_bitmap"], kv["k_values"], kv["v_bitmap"],
            kv["v_values"], table, layer, hkv, sm, bs, k_tail, v_tail,
            t_att, prefix_len=prefix_blocks * bs)
    else:
        k_sp = pooled_view(kv["k_bitmap"], kv["k_values"], bs, hd)
        v_sp = pooled_view(kv["v_bitmap"], kv["v_values"], bs, hd)
        o = ops.sparse_decode_attention(q, k_sp, v_sp, hkv, sm,
                                        k_tail, v_tail, t_att,
                                        prefix_len=prefix_blocks * bs)
    out = ops.linear(o.reshape(b, qn, hq * hd).astype(x.dtype), p["wo"])
    return out, {**kv, "k_tail": k_tail, "v_tail": v_tail}


def pooled_attn_prefill_chunk(p, x: jax.Array, kv: Dict[str, jax.Array],
                              cfg, ctx, positions: jax.Array,
                              ctx_len: jax.Array, bs: int,
                              table_row: Optional[jax.Array] = None,
                              layer: Optional[jax.Array] = None
                              ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Chunked-prefill attention for ONE slot of the pooled cache.

    Queries attend causally within the chunk plus fully over the slot's
    already-frozen compressed prefix (decompressed here; the chunk path is
    off the per-token hot loop).  ``x [1, C, d]``; ``kv``: slot-sliced
    pooled leaves (``[1, Hkv, Sb, X]``); ``positions [C]`` absolute;
    ``ctx_len`` scalar int32 — valid prefix tokens.  Returns
    ``(out [1, C, d], k_chunk, v_chunk [1, Hkv, C, hd] post-RoPE)`` so the
    caller can freeze the chunk into the pool.

    ``table_row`` (int32 ``[Sb]``, paged pool only): ``kv``'s compressed
    leaves are the shared arena of every layer (``[P, n_phys, Hkv, rows,
    128]`` tiles), ``layer`` this layer's period, and the slot's frozen
    prefix is gathered through its block-table row before decompression —
    only the slot's pages move, never the arena; a prefix-cache hit means
    these are blocks some OTHER request froze.
    """
    b, c, _ = x.shape
    hq, hkv, hd = cfg.padded_heads, cfg.n_kv, cfg.hd
    g = hq // hkv
    q = _project_q(p, x, cfg)                                # [1,C,Hq,hd]
    k, v = _project_kv(p, x, cfg)                            # [1,C,Hkv,hd]
    cos, sin = rope_angles(positions, hd, cfg.rope_theta)    # [C, hd//2]
    q = apply_rope(q, cos[None], sin[None])
    k = apply_rope(k, cos[None], sin[None])
    q = q.transpose(0, 2, 1, 3)                              # [1,Hq,C,hd]
    k = k.transpose(0, 2, 1, 3)                              # [1,Hkv,C,hd]
    v = v.transpose(0, 2, 1, 3)

    if table_row is not None:
        # gather the slot's logical blocks out of the layer's arena, then
        # decompress exactly as the flat path would.  Explicit clip mode:
        # table entries are clipped in range at write time, and the jaxpr
        # audit (repro.analysis) rejects PROMISE_IN_BOUNDS arena access
        arena = lambda a, x=None: from_arena_tiles(  # noqa: E731
            a.at[layer, table_row].get(mode="clip").transpose(1, 0, 2, 3),
            x)[None]                                 # [1, Hkv, Sb, X]
        view = lambda bm, vl: pooled_view(  # noqa: E731
            arena(bm, bs * hd // 32), arena(vl), bs, hd)
        k_ctx = unpack(view(kv["k_bitmap"], kv["k_values"]))
        v_ctx = unpack(view(kv["v_bitmap"], kv["v_values"]))
    else:
        k_ctx = unpack(pooled_view(kv["k_bitmap"], kv["k_values"], bs, hd))
        v_ctx = unpack(pooled_view(kv["v_bitmap"], kv["v_values"], bs, hd))
    s_ctx = k_ctx.shape[2]
    # prefill-chunk path: concat over the static chunk width, not the
    # per-token decode loop  # jitlint: disable=hot-path-op
    kv_valid = jnp.concatenate(  # jitlint: disable=hot-path-op
        [jnp.arange(s_ctx) < ctx_len, jnp.ones((c,), bool)])[None, :]
    kk = _repeat_kv(jnp.concatenate([k_ctx.astype(k.dtype), k], axis=2), g)  # jitlint: disable=hot-path-op
    vv = _repeat_kv(jnp.concatenate([v_ctx.astype(v.dtype), v], axis=2), g)  # jitlint: disable=hot-path-op
    sm = 1.0 / hd ** 0.5
    o = full_attention(q, kk, vv, sm, causal=True, kv_valid=kv_valid)
    o = o.transpose(0, 2, 1, 3).reshape(b, c, hq * hd)
    return ops.linear(o, p["wo"]), k, v


def cross_attn_decode(p, x_t: jax.Array, k: jax.Array, v: jax.Array,
                      cfg) -> jax.Array:
    """Decode-time cross attention against precomputed (possibly sparse)
    encoder K/V [B, Hkv, Sm, hd] — no mask, no cache update."""
    b, _ = x_t.shape
    hq, hkv, hd = cfg.padded_heads, cfg.n_kv, cfg.hd
    q = _project_q(p, x_t, cfg)
    sm = 1.0 / hd ** 0.5
    g = hq // hkv
    o = full_attention(q[:, :, None, :], _repeat_kv(k, g), _repeat_kv(v, g),
                       sm, causal=False)[:, :, 0, :]
    return ops.linear(o.reshape(b, hq * hd).astype(x_t.dtype), p["wo"])
