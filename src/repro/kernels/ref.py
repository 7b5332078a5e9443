"""Pure-jnp oracles for every Pallas kernel in this package.

These define the *semantics* each kernel must match (asserted to by the
per-kernel shape/dtype sweep tests), and double as the XLA fallback used on
non-TPU backends and in the multi-pod dry-run.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.sparse_format import BlockSparseWeight, unpack
from repro.core.sparse_kv import from_arena_tiles
from repro.core.quant import quantize_act_int8


def dense_matmul_ref(x: jax.Array, w: jax.Array,
                     out_dtype=None) -> jax.Array:
    """``x [M, K] @ w [K, N]`` with f32 accumulation (paper §4.1 baseline)."""
    out = jnp.dot(x, w, preferred_element_type=jnp.float32)
    return out.astype(out_dtype or x.dtype)


def sparse_matmul_ref(x: jax.Array, sw: BlockSparseWeight,
                      out_dtype=None) -> jax.Array:
    """Load-as-sparse, compute-as-dense (paper §4.3): decompress then GEMM.

    Works on shard_map-sliced weights too (the aux logical shape may exceed
    the local padded arrays; trim only when padding is real)."""
    w = unpack(sw, trim=False)
    kp = w.shape[0]
    xp = jnp.pad(x, ((0, 0), (0, max(kp - x.shape[1], 0))))[:, :kp]
    out = jnp.dot(xp, w, preferred_element_type=jnp.float32)
    n = min(sw.shape[1], w.shape[1])
    return out[:, :n].astype(out_dtype or x.dtype)


def sparse_gemv_ref(x: jax.Array, sw: BlockSparseWeight,
                    out_dtype=None) -> jax.Array:
    """Semantics identical to sparse_matmul; kept separate as the oracle for
    the vector-path kernel (paper §4.4 AVX kernel)."""
    return sparse_matmul_ref(x, sw, out_dtype)


def sparse_matmul_int8_ref(x: jax.Array, sw: BlockSparseWeight,
                           out_dtype=jnp.float32) -> jax.Array:
    """INT8/INT4 path (paper §4.5/§8): dynamic per-row activation quant,
    int32 accumulation, per-channel rescale.  ``sw.values`` is int8 (or
    nibble-packed int4 — ``unpack`` dequantizes to int8 first, exactly the
    paper's prescription)."""
    if not ((sw.values.dtype == jnp.int8 or sw.packed4)
            and sw.scale is not None):
        raise ValueError("int path needs int8/int4 values and a scale")
    xq, sx = quantize_act_int8(x)
    w = unpack(sw, trim=False)                       # int8, padded
    kp = w.shape[0]
    xq = jnp.pad(xq, ((0, 0), (0, max(kp - xq.shape[1], 0))))[:, :kp]
    acc = jnp.dot(xq.astype(jnp.int32), w.astype(jnp.int32),
                  preferred_element_type=jnp.int32)
    out = acc.astype(jnp.float32) * sx[:, None] \
        * sw.scale[None, : w.shape[1]]
    n = min(sw.shape[1], w.shape[1])
    return out[:, :n].astype(out_dtype)


def gather_paged_prefix(table: jax.Array, layer: jax.Array,
                        bitmap: jax.Array, values: jax.Array, bs: int, d: int
                        ) -> BlockSparseWeight:
    """Paged arena + per-slot block table -> the flat pooled-prefix view.

    ``bitmap`` / ``values`` ``[P, n_phys, Hkv, rows, 128]`` hold every
    compressed block of every layer ONCE, as the pool's tiles; ``layer``
    int32 picks the period and ``table [B, Sb]`` int32 maps each slot's
    logical block ``i`` to its physical id.  One gather of both indices
    (clipped in range, like every arena access) materializes each slot's
    logical prefix (``[B, Hkv, Sb, X]``; the values keep the tiles'
    zero padding, which no bitmap bit selects) and wraps it in the
    structured :class:`BlockSparseWeight` view the flat reference
    semantics consume — this IS the oracle for the paged kernel's index
    indirection: paged attention == gather-then-flat-attention.  Table
    entries past a slot's valid count select arbitrary (live or dead)
    blocks; callers mask them with ``prefix_len`` exactly as on the flat
    path.
    """
    take = lambda a: a.at[layer, table].get(  # noqa: E731
        mode="clip").transpose(0, 2, 1, 3, 4)          # [B, Hkv, Sb, r, 128]
    bm = from_arena_tiles(take(bitmap), bs * d // 32)
    vl = from_arena_tiles(take(values))
    sb = table.shape[1]
    return BlockSparseWeight(
        bitmap=bm[:, :, :, None, :], values=vl[:, :, :, None, :],
        scale=None, shape=(sb * bs, d), block=(bs, d))


def _merge_attn(o1, lse1, o2, lse2):
    """Combine two attention partials via their log-sum-exps."""
    m = jnp.maximum(lse1, lse2)
    w1 = jnp.exp(lse1 - m)[..., None]
    w2 = jnp.exp(lse2 - m)[..., None]
    den = w1 + w2
    return (o1 * w1 + o2 * w2) / den, m + jnp.log(den[..., 0])


def gqa_partial_ref(q: jax.Array, k: jax.Array, v: jax.Array,
                    sm_scale: float,
                    valid: Optional[jax.Array] = None
                    ) -> Tuple[jax.Array, jax.Array]:
    """Grouped-GQA single-query partial — NO repeat_kv materialization.

    q: [B, Hkv, G, D]; k, v: [B, Hkv, S, D] (bf16 ok — contraction
    accumulates in f32 via preferred_element_type, no f32 copies of the
    cache).  Returns (out [B,Hkv,G,D] f32, lse [B,Hkv,G]).

    This is §Perf iteration 3: the paper flags PyTorch's ``repeat_kv`` as a
    decode bottleneck; the XLA analogue (jnp.repeat + .astype(f32)) was
    ~20x the ideal cache bytes.
    """
    s = jnp.einsum("bhgd,bhsd->bhgs", q, k,
                   preferred_element_type=jnp.float32) * sm_scale
    if valid is not None:
        s = jnp.where(valid[:, None, None, :], s, -jnp.inf)
    m = jnp.max(s, axis=-1)
    m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
    p = jnp.exp(s - m_safe[..., None])
    if valid is not None:
        p = jnp.where(valid[:, None, None, :], p, 0.0)
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("bhgs,bhsd->bhgd", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    l_safe = jnp.maximum(l, 1e-30)
    return o / l_safe[..., None], m_safe + jnp.log(l_safe)


def attn_partial_ref(q: jax.Array, k: jax.Array, v: jax.Array,
                     sm_scale: float,
                     valid: Optional[jax.Array] = None
                     ) -> Tuple[jax.Array, jax.Array]:
    """Single-query attention partial: returns (out, lse).

    q: [B, H, D]; k, v: [B, H, S, D] (H = kv heads already matched to q heads);
    valid: optional [B, S] bool mask of real (non-pad) positions.
    """
    s = jnp.einsum("bhd,bhsd->bhs", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * sm_scale
    if valid is not None:
        s = jnp.where(valid[:, None, :], s, -jnp.inf)
    m = jnp.max(s, axis=-1)
    # all-masked rows: avoid nan
    m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
    p = jnp.exp(s - m_safe[..., None])
    if valid is not None:
        p = jnp.where(valid[:, None, :], p, 0.0)
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("bhs,bhsd->bhd", p, v.astype(jnp.float32))
    l_safe = jnp.maximum(l, 1e-30)
    return o / l_safe[..., None], m_safe + jnp.log(l_safe)


def _len_valid(n: int, length, b: int) -> jax.Array:
    """[B, n] validity mask from a scalar or per-slot [B] length."""
    length = jnp.asarray(length)
    if length.ndim == 1:
        length = length[:, None]
    return jnp.broadcast_to(jnp.arange(n)[None, :] < length, (b, n))


def _unpack_prefix(q, k_sp, v_sp, hkv):
    """Decompress the frozen prefix to dense [B, Hkv, S, D] (both the
    structured [B, Hkv, Sb, 1, ...] and the flat [(B*Hkv*Sb), 1, ...]
    block layouts)."""
    b, hq, d = q.shape
    if k_sp.bitmap.ndim == 5:       # structured [B, Hkv, Sb, 1, ...]
        return unpack(k_sp), unpack(v_sp)
    kd = unpack(k_sp)                                 # [(B Hkv S), D]
    vd = unpack(v_sp)
    s_len = kd.shape[0] // (b * hkv)
    return (kd.reshape(b, hkv, s_len, d),
            vd.reshape(b, hkv, s_len, d))


def sparse_decode_attention_fused_ref(
        q: jax.Array,
        k_sp: BlockSparseWeight, v_sp: BlockSparseWeight,
        sm_scale: float,
        k_tail: jax.Array, v_tail: jax.Array,
        tail_len: Optional[jax.Array] = None,
        prefix_len: Optional[jax.Array] = None) -> jax.Array:
    """Oracle for the FUSED prefix+tail flash-decode kernel.

    Fused semantics: ONE softmax over the union of valid prefix and tail
    positions — no partials, no lse merge, no special-casing of empty
    prefixes (an all-invalid prefix simply contributes nothing).  Grouped
    GQA throughout: the tail is consumed at [B, Hkv, T, D], never
    materialized to Hq heads.

    q [B, Hq, D]; k_sp/v_sp the compressed frozen prefix (structured or
    flat layout); k_tail/v_tail [B, Hkv, T, D].  ``tail_len`` /
    ``prefix_len`` may be scalar or per-slot [B] int32; slots where both
    are empty return zeros.

    Concat-free: prefix and tail are scored by two grouped einsums (bf16
    cache operands stay bf16 — no f32 copies, no [S+T] concatenation)
    that share ONE softmax normalizer — the fused kernel's online softmax
    unrolled to two panels, each panel exponentiated against its own
    local max (the flash recurrence's rescaling trick, which also keeps
    the bf16-cast ``p`` numerics identical to the two-pass partials').
    """
    b, hq, d = q.shape
    hkv = k_tail.shape[1]
    k, v = _unpack_prefix(q, k_sp, v_sp, hkv)
    s_len, t = k.shape[2], k_tail.shape[2]
    valid_p = _len_valid(
        s_len, prefix_len if prefix_len is not None else s_len, b)
    valid_t = _len_valid(t, tail_len if tail_len is not None else t, b)
    g = hq // hkv
    qg = q.reshape(b, hkv, g, d)

    def panel(kx, vx, valid):
        """Unnormalized panel statistics (o, l, m) at the panel's own
        max — empty panels return (0, 0, -inf)."""
        s = jnp.einsum("bhgd,bhsd->bhgs", qg, kx,
                       preferred_element_type=jnp.float32) * sm_scale
        s = jnp.where(valid[:, None, None, :], s, -jnp.inf)
        m = jnp.max(s, axis=-1)                          # [B,Hkv,G]
        p = jnp.exp(s - jnp.where(jnp.isfinite(m), m, 0.0)[..., None])
        p = jnp.where(valid[:, None, None, :], p, 0.0)
        o = jnp.einsum("bhgs,bhsd->bhgd", p.astype(vx.dtype), vx,
                       preferred_element_type=jnp.float32)
        return o, jnp.sum(p, axis=-1), m

    o1, l1, m1 = panel(k, v, valid_p)
    o2, l2, m2 = panel(k_tail, v_tail, valid_t)
    m = jnp.maximum(m1, m2)                              # joint max
    m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
    # empty panels have m == -inf, so their weight is exactly exp(-inf)=0
    w1 = jnp.exp(m1 - m_safe)
    w2 = jnp.exp(m2 - m_safe)
    l_safe = jnp.maximum(l1 * w1 + l2 * w2, 1e-30)
    o = (o1 * w1[..., None] + o2 * w2[..., None]) / l_safe[..., None]
    return o.reshape(b, hq, d).astype(q.dtype)


def sparse_decode_attention_panel_ref(
        q: jax.Array,
        k_sp: BlockSparseWeight, v_sp: BlockSparseWeight,
        sm_scale: float,
        k_tail: jax.Array, v_tail: jax.Array,
        tail_len: Optional[jax.Array] = None,
        prefix_len: Optional[jax.Array] = None) -> jax.Array:
    """Query-panel oracle for the fused kernel's speculative verify step.

    Same concat-free two-panel softmax as
    :func:`sparse_decode_attention_fused_ref`, generalized from one query
    to a ``[B, Q, Hq, D]`` panel: every panel query sees the full valid
    prefix, while tail visibility is *intra-window causal* — panel query
    ``j`` sees ``tail_len + j`` tail tokens (``tail_len`` counts the
    tokens visible to query 0, its own appended K/V included; each later
    query additionally sees the K/V its panel predecessors appended).
    ``Q == 1`` reduces exactly to the fused single-query semantics.

    Returns out [B, Q, Hq, D]; slots with nothing valid return zeros.
    """
    b, qn, hq, d = q.shape
    hkv = k_tail.shape[1]
    k, v = _unpack_prefix(q[:, 0], k_sp, v_sp, hkv)
    s_len, t = k.shape[2], k_tail.shape[2]
    valid_p = _len_valid(
        s_len, prefix_len if prefix_len is not None else s_len, b)
    tl = jnp.asarray(tail_len if tail_len is not None else t)
    if tl.ndim == 0:
        tl = jnp.broadcast_to(tl, (b,))
    # [B, Q, T]: query j sees tail tokens < tl + j
    valid_t = (jnp.arange(t)[None, None, :]
               < tl[:, None, None] + jnp.arange(qn)[None, :, None])
    g = hq // hkv
    qg = q.reshape(b, qn, hkv, g, d).transpose(0, 2, 1, 3, 4)

    def panel(kx, vx, valid):
        """valid [B, Qv, S] with Qv in {1, Q} (broadcast over heads)."""
        s = jnp.einsum("bhqgd,bhsd->bhqgs", qg, kx,
                       preferred_element_type=jnp.float32) * sm_scale
        vm = valid[:, None, :, None, :]
        s = jnp.where(vm, s, -jnp.inf)
        m = jnp.max(s, axis=-1)                          # [B,Hkv,Q,G]
        p = jnp.exp(s - jnp.where(jnp.isfinite(m), m, 0.0)[..., None])
        p = jnp.where(vm, p, 0.0)
        o = jnp.einsum("bhqgs,bhsd->bhqgd", p.astype(vx.dtype), vx,
                       preferred_element_type=jnp.float32)
        return o, jnp.sum(p, axis=-1), m

    o1, l1, m1 = panel(k, v, valid_p[:, None, :])
    o2, l2, m2 = panel(k_tail, v_tail, valid_t)
    m = jnp.maximum(m1, m2)
    m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
    w1 = jnp.exp(m1 - m_safe)
    w2 = jnp.exp(m2 - m_safe)
    l_safe = jnp.maximum(l1 * w1 + l2 * w2, 1e-30)
    o = (o1 * w1[..., None] + o2 * w2[..., None]) / l_safe[..., None]
    return (o.transpose(0, 2, 1, 3, 4)
            .reshape(b, qn, hq, d).astype(q.dtype))


def sparse_decode_attention_ref(
        q: jax.Array,
        k_sp: BlockSparseWeight, v_sp: BlockSparseWeight,
        sm_scale: float,
        k_tail: Optional[jax.Array] = None,
        v_tail: Optional[jax.Array] = None,
        tail_len: Optional[jax.Array] = None,
        prefix_len: Optional[jax.Array] = None) -> jax.Array:
    """Two-pass (partial + lse merge) oracle for the sparse-KV flash-decode
    kernel (paper §6.2).

    Mathematically identical to :func:`sparse_decode_attention_fused_ref`;
    kept as the partial+merge reference because the context-parallel path
    (``repro.distributed.cp_attention``) is pinned to these semantics —
    per-shard partials must cross chips before the merge.

    q: [B, Hq, D].  k_sp/v_sp hold the *compressed frozen prefix*: their
    logical shape is [(B*Hkv*S), D] blocked row-major, i.e. they were packed
    from the [B*Hkv*S, D] view of the cache.  k_tail/v_tail: dense dynamic
    tail [B, Hkv, T, D] with `tail_len` valid positions.

    ``tail_len`` and ``prefix_len`` may be scalars (uniform batch — the
    legacy one-shot engine) or per-slot int32 ``[B]`` (the pooled
    continuous-batching cache, where every slot has its own lengths).
    ``prefix_len`` masks prefix positions ``>= prefix_len[b]`` — slots whose
    compressed prefix only partially fills the pool's fixed-capacity storage.
    """
    b, hq, d = q.shape
    if k_tail is not None:
        hkv = k_tail.shape[1]
    elif k_sp.bitmap.ndim == 5:     # structured layout carries Hkv
        hkv = k_sp.bitmap.shape[1]
    else:
        hkv = hq
    k, v = _unpack_prefix(q, k_sp, v_sp, hkv)
    g = hq // hkv
    qg = q.reshape(b, hkv, g, d)
    valid_p = None
    if prefix_len is not None:
        valid_p = _len_valid(k.shape[2], prefix_len, b)
    o, lse = gqa_partial_ref(qg, k, v, sm_scale, valid_p)
    if valid_p is not None:
        # an empty prefix must not win the lse merge against a real tail
        empty_p = ~jnp.any(valid_p, axis=-1)
        lse = jnp.where(empty_p[:, None, None], -1e30, lse)
    if k_tail is not None and k_tail.shape[2] > 0:
        t = k_tail.shape[2]
        valid = _len_valid(t, tail_len if tail_len is not None else t, b)
        o2, lse2 = gqa_partial_ref(qg, k_tail, v_tail, sm_scale, valid)
        # a fully-empty tail contributes nothing
        empty = ~jnp.any(valid, axis=-1)
        lse2 = jnp.where(empty[:, None, None], -jnp.inf, lse2)
        lse2_safe = jnp.where(jnp.isfinite(lse2), lse2, lse.min() - 60.0)
        o, _ = _merge_attn(o, lse, o2, lse2_safe)
    return o.reshape(b, hq, d).astype(q.dtype)
