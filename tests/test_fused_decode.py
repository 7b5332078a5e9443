"""Fused prefix+tail flash-decode: parity and serving acceptance.

The acceptance bar for the one-kernel decode redesign:

* the fused kernel (interpret mode) matches BOTH the fused-semantics
  oracle and the legacy two-pass partial+merge oracle across the pooled
  edge grid — ``tail_len in {0, 1, T}``, ``prefix_len = 0`` (an empty
  prefix must simply contribute nothing), all-inactive ``slot_mask``, and
  mixed per-slot lengths — with poisoned storage beyond each slot's valid
  lengths (masking bugs show up as parity breaks, not luck);
* the continuous engine riding the fused kernel still jits its decode
  exactly once across refreezes and admissions/evictions, and its greedy
  tokens match the XLA-backend engine token for token.
"""
import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.core.sparse_kv import freeze_chunk_blocks, pooled_view
from repro.distributed import NULL_CTX
from repro.kernels import ops, ref
from repro.models import lm
from repro.serving import (CachePool, ContinuousEngine, SamplingParams,
                           stable_trace_counts)


def _rand(shape, seed):
    return jnp.asarray(np.random.default_rng(seed).normal(
        size=shape).astype(np.float32))


def _pooled_case(b=4, hkv=2, g=2, d=32, sb=4, bs=16, t=16,
                 ks=0.3, vs=0.5, seed=0):
    """Pool-layout compressed prefix + dense tail ring + query."""
    k = _rand((b, hkv, sb * bs, d), seed)
    v = _rand((b, hkv, sb * bs, d), seed + 1)
    cap = bs * d                                  # full capacity: no drops
    k_bm, k_vl, v_bm, v_vl = freeze_chunk_blocks(k, v, ks, vs, bs, cap, cap)
    k_sp = pooled_view(k_bm, k_vl, bs, d)
    v_sp = pooled_view(v_bm, v_vl, bs, d)
    k_tail = _rand((b, hkv, t, d), seed + 2)
    v_tail = _rand((b, hkv, t, d), seed + 3)
    q = _rand((b, hkv * g, d), seed + 4)
    return q, k_sp, v_sp, k_tail, v_tail


def _poison_tail(tail, tail_len):
    """Blow up every out-of-range tail token: if validity masking leaks,
    parity against the oracles breaks loudly instead of passing by luck."""
    t = tail.shape[2]
    dead = jnp.arange(t)[None, None, :, None] >= \
        jnp.asarray(tail_len)[:, None, None, None]
    return jnp.where(dead, 50.0, tail)


EDGE_GRID = [
    # (prefix_blocks per slot, tail_len per slot)   sb=4, t=16, b=4
    pytest.param([4, 4, 4, 4], [0, 0, 0, 0], id="empty_tail"),
    pytest.param([4, 4, 4, 4], [1, 1, 1, 1], id="one_token_tail"),
    pytest.param([4, 4, 4, 4], [16, 16, 16, 16], id="full_tail"),
    pytest.param([0, 0, 0, 0], [7, 16, 1, 9], id="empty_prefix"),
    pytest.param([0, 0, 0, 0], [0, 0, 0, 0], id="all_empty"),
    pytest.param([0, 4, 2, 1], [0, 1, 16, 9], id="mixed_lengths"),
]


@pytest.mark.parametrize("ks,vs", [(0.0, 0.0), (0.3, 0.5)])
@pytest.mark.parametrize("prefix_blocks,tail_len", EDGE_GRID)
def test_fused_kernel_edge_grid(prefix_blocks, tail_len, ks, vs):
    bs, d, hkv, g = 16, 32, 2, 2
    q, k_sp, v_sp, k_tail, v_tail = _pooled_case(bs=bs, d=d, hkv=hkv, g=g,
                                                 ks=ks, vs=vs)
    tl = jnp.asarray(tail_len, jnp.int32)
    pl_ = jnp.asarray(prefix_blocks, jnp.int32) * bs
    k_tail = _poison_tail(k_tail, tl)
    v_tail = _poison_tail(v_tail, tl)
    sm = 1.0 / d ** 0.5

    with ops.backend("interpret"):
        o_kernel = ops.sparse_decode_attention(
            q, k_sp, v_sp, hkv, sm, k_tail, v_tail, tl, prefix_len=pl_)
    o_fused = ref.sparse_decode_attention_fused_ref(
        q, k_sp, v_sp, sm, k_tail, v_tail, tl, prefix_len=pl_)
    o_merge = ref.sparse_decode_attention_ref(
        q, k_sp, v_sp, sm, k_tail, v_tail, tl, prefix_len=pl_)

    np.testing.assert_allclose(np.asarray(o_kernel), np.asarray(o_fused),
                               rtol=1e-4, atol=1e-4)
    if not np.asarray(tl).any() and not np.asarray(pl_).any():
        # fully-empty slots: the two-pass oracle's merge floor leaves ~0,
        # the fused semantics are exactly 0 — both ignorable
        np.testing.assert_allclose(np.asarray(o_merge), 0.0, atol=1e-4)
        np.testing.assert_array_equal(np.asarray(o_kernel), 0.0)
    else:
        np.testing.assert_allclose(np.asarray(o_kernel), np.asarray(o_merge),
                                   rtol=1e-4, atol=1e-4)


def test_fused_kernel_head_dim_96():
    """KV blocks whose width does not divide 128 (phi3-mini's head_dim 96):
    a (64, 96) block's bitmap rows straddle two rows of the word view."""
    bs, d, hkv, g = 64, 96, 2, 2
    q, k_sp, v_sp, k_tail, v_tail = _pooled_case(bs=bs, d=d, hkv=hkv, g=g,
                                                 sb=2, t=64)
    tl = jnp.asarray([0, 5, 64, 17], jnp.int32)
    pl_ = jnp.asarray([2, 1, 0, 2], jnp.int32) * bs
    sm = 1.0 / d ** 0.5
    with ops.backend("interpret"):
        o_kernel = ops.sparse_decode_attention(
            q, k_sp, v_sp, hkv, sm, k_tail, v_tail, tl, prefix_len=pl_)
    o_fused = ref.sparse_decode_attention_fused_ref(
        q, k_sp, v_sp, sm, k_tail, v_tail, tl, prefix_len=pl_)
    np.testing.assert_allclose(np.asarray(o_kernel), np.asarray(o_fused),
                               rtol=1e-4, atol=1e-4)


def test_fused_kernel_unaligned_tail_ring_is_padded():
    """A tail ring that is not a whole number of (bs,)-panels (legacy
    one-shot cache geometry) is zero-padded by the dispatcher; the padding
    must stay masked."""
    bs, d, hkv, g = 16, 32, 2, 2
    q, k_sp, v_sp, _, _ = _pooled_case(bs=bs, d=d, hkv=hkv, g=g)
    t = 11                                        # < bs and not a multiple
    k_tail = _rand((4, hkv, t, d), 50) + 10.0     # large: leaks are loud
    v_tail = _rand((4, hkv, t, d), 51)
    tl = jnp.asarray([0, 1, 11, 5], jnp.int32)
    sm = 1.0 / d ** 0.5
    with ops.backend("interpret"):
        o_kernel = ops.sparse_decode_attention(
            q, k_sp, v_sp, hkv, sm, k_tail, v_tail, tl)
    o_ref = ref.sparse_decode_attention_ref(q, k_sp, v_sp, sm,
                                            k_tail, v_tail, tl)
    np.testing.assert_allclose(np.asarray(o_kernel), np.asarray(o_ref),
                               rtol=1e-4, atol=1e-4)


def test_no_tail_uses_prefix_partial_and_matches():
    """Without a tail the dispatcher keeps the prefix-partial kernel; a
    fully-skipped prefix must return zeros (nothing to attend to)."""
    bs, d, hkv, g = 16, 32, 2, 2
    q, k_sp, v_sp, _, _ = _pooled_case(bs=bs, d=d, hkv=hkv, g=g)
    pl_ = jnp.asarray([0, 64, 32, 16], jnp.int32)
    sm = 1.0 / d ** 0.5
    with ops.backend("interpret"):
        o_kernel = ops.sparse_decode_attention(q, k_sp, v_sp, hkv, sm,
                                               prefix_len=pl_)
    o_ref = ref.sparse_decode_attention_ref(q, k_sp, v_sp, sm,
                                            prefix_len=pl_)
    np.testing.assert_allclose(np.asarray(o_kernel), np.asarray(o_ref),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_array_equal(np.asarray(o_kernel)[0], 0.0)


# ---------------------------------------------------------------------------
# serving-level: the fused kernel under the continuous engine
# ---------------------------------------------------------------------------

def _setup(seed=0, b=2, s=16, kv_tail=16, dtype=None):
    cfg = get_config("qwen3-0.6b").reduced()
    kw = dict(kv_k_sparsity=0.0, kv_v_sparsity=0.0, kv_tail=kv_tail)
    if dtype is not None:
        kw.update(compute_dtype=dtype, param_dtype=dtype)
    cfg = dataclasses.replace(cfg, **kw)
    params = lm.init_params(cfg, jax.random.PRNGKey(seed))
    toks = jnp.asarray(np.random.default_rng(seed).integers(
        0, cfg.vocab, (b, s)), jnp.int32)
    return cfg, params, toks


def _two_pass_sparse_decode_attention(q, k_sp, v_sp, hkv, sm_scale,
                                      k_tail=None, v_tail=None,
                                      tail_len=None, prefix_len=None):
    """The PRE-FUSION dispatch, reconstructed: prefix-partial Pallas kernel
    + XLA-side grouped tail attention + lse merge.  The fused engine must
    be token-identical to an engine decoding through this."""
    from repro.kernels.sparse_attention import sparse_decode_attention_pallas
    if q.ndim == 4:          # unified panel forward at Q == 1: a decode tick
        assert q.shape[1] == 1, q.shape
        return _two_pass_sparse_decode_attention(
            q[:, 0], k_sp, v_sp, hkv, sm_scale, k_tail, v_tail,
            tail_len, prefix_len)[:, None]
    interp = ops._pallas()
    assert interp is not None
    b, hq, d = q.shape
    g = hq // hkv
    bs = k_sp.block[0]
    words = k_sp.bitmap.shape[-1]
    sb = k_sp.bitmap.shape[2]
    qg = q.reshape(b, hkv, g, d)
    kbm = k_sp.bitmap.reshape(b, hkv, sb, words)
    kvv = k_sp.values.reshape(b, hkv, sb, k_sp.capacity)
    vbm = v_sp.bitmap.reshape(b, hkv, sb, words)
    vvv = v_sp.values.reshape(b, hkv, sb, v_sp.capacity)
    n_blocks = None
    if prefix_len is not None:
        n_blocks = jnp.broadcast_to(
            jnp.asarray(prefix_len, jnp.int32) // bs, (b,))
    o, lse = sparse_decode_attention_pallas(
        qg, kbm, kvv, vbm, vvv, bs=bs, sm_scale=sm_scale, interpret=interp,
        n_blocks=n_blocks)
    o = o.reshape(b, hq, d)
    lse = lse.reshape(b, hq)
    if prefix_len is not None:
        empty_p = jnp.broadcast_to(jnp.atleast_1d(
            jnp.asarray(prefix_len)) <= 0, (b,))
        lse = jnp.where(empty_p[:, None], -1e30, lse)
    if k_tail is not None and k_tail.shape[2] > 0:
        t = k_tail.shape[2]
        valid = ref._len_valid(t, tail_len if tail_len is not None else t, b)
        o2, lse2 = ref.gqa_partial_ref(qg, k_tail, v_tail, sm_scale, valid)
        o2 = o2.reshape(b, hq, d)
        lse2 = lse2.reshape(b, hq)
        empty = ~jnp.any(valid, axis=-1)
        lse2 = jnp.where(empty[:, None], -jnp.inf, lse2)
        lse2 = jnp.where(jnp.isfinite(lse2), lse2, lse.min() - 60.0)
        o, _ = ref._merge_attn(o, lse, o2, lse2)
    return o.astype(q.dtype)


def test_all_inactive_slot_mask_is_passthrough():
    """A decode tick (the panel forward at Q == 1) with every slot masked
    off must leave the pooled state bit-identical (lengths and cache
    leaves) through the fused kernel path."""
    cfg, params, toks = _setup()
    pool = CachePool.build(cfg, slots=2, max_tokens=64, bs=16)
    state = pool.init_state()
    rng = np.random.default_rng(3)
    for leaf in state["layers"].values():
        kv = leaf["kv"]
        kv["k_tail"] = jnp.asarray(rng.normal(
            size=kv["k_tail"].shape)).astype(kv["k_tail"].dtype)
        kv["v_tail"] = kv["k_tail"] * 0.25
    state["tail_len"] = jnp.asarray([3, 0], jnp.int32)
    state["pos"] = jnp.asarray([3, 0], jnp.int32)
    mask = jnp.zeros((2,), bool)
    with ops.backend("interpret"):
        logits, out = jax.jit(
            lambda p, st, t, m: lm.forward_panel_pooled(
                p, st, t, m, cfg, NULL_CTX, pool.bs))(
                    params, state, toks[:, :1], mask)
    logits = logits[:, 0]
    assert logits.shape == (2, cfg.vocab)
    for a, b_ in zip(jax.tree_util.tree_leaves(state),
                     jax.tree_util.tree_leaves(out)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b_))


def test_fused_engine_zero_retrace_and_token_parity():
    """The engine on the fused Pallas path (interpret mode): decode jits
    exactly once across refreeze + admission/eviction waves, and greedy
    tokens are IDENTICAL to an engine decoding through the reconstructed
    two-pass partial+merge dispatch on the same backend (f32 compute so
    the comparison is numerics-matched, not near-tie roulette)."""
    cfg, params, toks = _setup(dtype="float32")
    sp = SamplingParams(max_new_tokens=24)

    def _wave2(eng):
        # staggered second wave: 3 requests through 2 slots (admission
        # from the queue + evictions), unaligned prompts -> tail remainder
        rids = [eng.submit(toks[i % 2][:9 + 4 * i],
                           SamplingParams(max_new_tokens=20 - 2 * i))
                for i in range(3)]
        res = eng.run()
        return [res[r].token_ids for r in rids]

    def _run_waves(eng):
        out = eng.generate_batch(toks, sp)        # 24 > kv_tail: refreezes
        return out, _wave2(eng)

    with ops.backend("interpret"):
        eng = ContinuousEngine(params, cfg, slots=2, max_tokens=96, bs=16)
        out_fused = eng.generate_batch(toks, sp)
        warm = eng.trace_counts()
        assert warm["decode"] == 1
        wave2_fused = _wave2(eng)
        after = eng.trace_counts()
        # new prompt lengths legitimately add prefill-chunk traces (one
        # per distinct length); everything else must stay flat
        assert (stable_trace_counts(after) == stable_trace_counts(warm)
                and after["decode"] == 1), \
            f"fused decode retraced: {warm} -> {after}"

        orig = ops.sparse_decode_attention
        ops.sparse_decode_attention = _two_pass_sparse_decode_attention
        try:
            eng2 = ContinuousEngine(params, cfg, slots=2, max_tokens=96,
                                    bs=16)
            out_two, wave2_two = _run_waves(eng2)
        finally:
            ops.sparse_decode_attention = orig

    np.testing.assert_array_equal(np.asarray(out_fused), np.asarray(out_two))
    assert wave2_fused == wave2_two
