"""Paged shared-prefix cache: block tables + copy-on-write over the
compressed pool.

The acceptance bar for the subsystem:

* the paged fused kernel (interpret mode) — block table as a
  scalar-prefetch operand, prefix phase loading ``table[slot, i]`` —
  matches the gather-then-flat XLA oracle across the pooled edge grid,
  including tables that SHARE physical blocks across slots;
* dead arena blocks are never *read*: poisoning every physical block not
  referenced by a live table entry (and pointing dead table entries at
  poisoned pages) leaves the kernel output bit-identical on both
  backends;
* refcounts are conserved: across any admit / refreeze / CoW / release
  sequence, ``sum(refcount) == live table entries`` and the device vector
  mirrors the host :class:`BlockAllocator` exactly (property tests,
  hypothesis-gated like tests/test_sparse_format.py); the allocator never
  evicts a referenced block and catches double-frees;
* greedy ``ContinuousEngine(paged=True)`` output is token-identical to
  the flat pre-PR pool on mixed shared/unshared request waves — including
  refreeze, copy-on-write divergence, prefix-cache hits, LRU eviction,
  and speculative-decoding rollback — with ZERO decode retraces across
  admissions/evictions (``trace_counts()``), and a cache hit admits with
  the shared prefill already done.
"""
import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:                      # container ships without hypothesis
    class _St:
        def integers(self, *a, **k): return None
        def lists(self, *a, **k): return None
    st = _St()

    def settings(**_kw):
        return lambda fn: fn

    def given(**_kw):
        def deco(fn):
            @pytest.mark.skip(reason="hypothesis not installed")
            def wrapper():
                pass
            wrapper.__name__ = fn.__name__
            return wrapper
        return deco

from repro.configs import get_config
from repro.core.sparse_kv import (arena_rows, freeze_chunk_blocks,
                                  to_arena_tiles)
from repro.kernels import ops
from repro.models import lm
from repro.serving import (BlockAllocator, CachePool, ContinuousEngine,
                           PrefixTrie, SamplingParams, SpecConfig,
                           block_hashes, stable_trace_counts)


def _rand(shape, seed):
    return jnp.asarray(np.random.default_rng(seed).normal(
        size=shape).astype(np.float32))


# ---------------------------------------------------------------------------
# kernel: table indirection vs the gather-then-flat oracle
# ---------------------------------------------------------------------------

def _arena_case(n_phys=10, hkv=2, bs=16, d=32, ks=0.3, vs=0.5, seed=0,
                n_periods=3, layer=1):
    """A frozen arena of ``n_phys`` independent compressed blocks in
    period ``layer`` of a pool-layout ``[P, n_phys, Hkv, rows, 128]``
    arena, whose other periods hold other blocks (the kernel must read the
    layer it is given)."""
    def period(seed):
        k = _rand((n_phys, hkv, bs, d), seed)
        v = _rand((n_phys, hkv, bs, d), seed + 1)
        cap = bs * d
        leaves = freeze_chunk_blocks(k, v, ks, vs, bs, cap, cap)
        return [to_arena_tiles(a[:, :, 0], arena_rows(a.shape[-1]))
                for a in leaves]                    # [n_phys,Hkv,rows,128]
    periods = [period(seed if p == layer else seed + 100 * (p + 1))
               for p in range(n_periods)]
    return tuple(jnp.stack(ls) for ls in zip(*periods))


# tables share physical pages across slots on purpose — that sharing is
# the feature the indirection exists for
PAGED_GRID = [
    # (table rows, prefix_blocks, tail_len)  b=4, sb=4
    pytest.param([[0, 1, 2, 3], [0, 1, 2, 3], [0, 1, 2, 3], [0, 1, 2, 3]],
                 [4, 4, 4, 4], [1, 9, 14, 16], id="all_shared"),
    pytest.param([[0, 1, 2, 3], [0, 1, 5, 6], [7, 8, 0, 0], [9, 0, 0, 0]],
                 [4, 4, 2, 1], [1, 5, 9, 13], id="cow_divergence"),
    pytest.param([[0, 1, 2, 3], [0, 1, 9, 9], [0, 0, 0, 0], [5, 6, 7, 8]],
                 [2, 2, 0, 4], [3, 14, 7, 1], id="dead_entries"),
    pytest.param([[0, 0, 0, 0]] * 4, [0, 0, 0, 0], [1, 4, 9, 16],
                 id="empty_prefix"),
]


@pytest.mark.parametrize("table,prefix_blocks,tail_len", PAGED_GRID)
@pytest.mark.parametrize("qn", [0, 3])
def test_paged_kernel_matches_gather_oracle(table, prefix_blocks, tail_len,
                                            qn):
    """Paged attention == gather each slot's blocks out of the arena, then
    flat attention: single-query ticks and [B, Q, Hq, D] verify panels,
    slots sharing pages, dead in-range table entries."""
    b, hkv, g, d, bs, t = 4, 2, 2, 32, 16, 16
    arena = _arena_case(hkv=hkv, bs=bs, d=d)
    tbl = jnp.asarray(table, jnp.int32)
    pl_ = jnp.asarray(prefix_blocks, jnp.int32) * bs
    tl = jnp.asarray(tail_len, jnp.int32)
    k_tail = _rand((b, hkv, t, d), 10)
    v_tail = _rand((b, hkv, t, d), 11)
    q = (_rand((b, hkv * g, d), 12) if qn == 0
         else _rand((b, qn, hkv * g, d), 12))
    if qn:                          # panel query j sees tail_len + j
        tl = jnp.minimum(tl, t - (qn - 1))
    sm = 1.0 / d ** 0.5
    layer = jnp.int32(1)
    with ops.backend("xla"):
        o_ref = ops.sparse_decode_attention_paged(
            q, *arena, tbl, layer, hkv, sm, bs, k_tail, v_tail, tl, pl_)
    with ops.backend("interpret"):
        o_k = ops.sparse_decode_attention_paged(
            q, *arena, tbl, layer, hkv, sm, bs, k_tail, v_tail, tl, pl_)
    np.testing.assert_allclose(np.asarray(o_k), np.asarray(o_ref),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("backend", ["interpret", "xla"])
def test_poisoned_arena_blocks_never_read(backend):
    """Poison every physical page NOT referenced by a live table entry
    (including the pages dead table entries point at): the output must be
    bit-identical to the clean arena — the ``n_blocks`` gate, not luck,
    keeps dead fetches out of the softmax."""
    b, hkv, g, d, bs, t = 4, 2, 2, 32, 16, 16
    n_phys = 10
    arena = _arena_case(n_phys=n_phys, hkv=hkv, bs=bs, d=d)
    table = jnp.asarray([[0, 1, 2, 3], [0, 1, 9, 9],
                         [4, 0, 0, 0], [5, 5, 5, 5]], jnp.int32)
    prefix_blocks = np.asarray([4, 2, 1, 0])
    live = {int(table[s, i]) for s in range(b)
            for i in range(prefix_blocks[s])}
    dead = np.asarray([p not in live for p in range(n_phys)])
    assert dead.any(), "case must exercise dead pages"
    # dead pages of the attended period, and every page of the others
    dead_pages = np.ones((3, n_phys), bool)
    dead_pages[1] = dead
    poisoned = tuple(
        jnp.where(dead_pages[:, :, None, None, None],
                  jnp.full(a.shape, ~np.uint32(0))
                  if a.dtype == jnp.uint32 else jnp.full(a.shape, 1e4),
                  a).astype(a.dtype)
        for a in arena)
    pl_ = jnp.asarray(prefix_blocks, jnp.int32) * bs
    tl = jnp.asarray([1, 9, 16, 4], jnp.int32)
    k_tail = _rand((b, hkv, t, d), 20)
    v_tail = _rand((b, hkv, t, d), 21)
    q = _rand((b, hkv * g, d), 22)
    sm = 1.0 / d ** 0.5
    layer = jnp.int32(1)
    with ops.backend(backend):
        o_clean = ops.sparse_decode_attention_paged(
            q, *arena, table, layer, hkv, sm, bs, k_tail, v_tail, tl, pl_)
        o_poison = ops.sparse_decode_attention_paged(
            q, *poisoned, table, layer, hkv, sm, bs, k_tail, v_tail, tl,
            pl_)
    np.testing.assert_array_equal(np.asarray(o_clean), np.asarray(o_poison))


# ---------------------------------------------------------------------------
# pool transitions: table / refcount bookkeeping
# ---------------------------------------------------------------------------

def _paged_pool(slots=3, kv_tail=16, bs=16, max_tokens=64, n_phys=0):
    cfg = get_config("qwen3-0.6b").reduced()
    cfg = dataclasses.replace(cfg, kv_k_sparsity=0.0, kv_v_sparsity=0.0,
                              kv_tail=kv_tail)
    pool = CachePool.build(cfg, slots=slots, max_tokens=max_tokens, bs=bs,
                           paged=True, n_phys=n_phys)
    return cfg, pool


def test_paged_build_defaults_and_errors():
    cfg, pool = _paged_pool(slots=3, max_tokens=64, bs=16)
    assert pool.paged and pool.n_phys == 3 * pool.max_blocks
    st0 = pool.init_state()
    assert st0["table"].shape == (3, pool.max_blocks)
    assert st0["refcount"].shape == (pool.n_phys,)
    # the build-time contracts read as errors, not asserts
    with pytest.raises(ValueError, match="not a multiple"):
        CachePool.build(cfg, 2, 64, bs=12)
    with pytest.raises(ValueError, match="cannot serve arch"):
        CachePool.build(get_config("rwkv6-7b").reduced(), 2, 64)


def test_assign_refreeze_release_refcount_bookkeeping():
    """One shared-prefix lifetime, by hand: slot 0 freezes two pages,
    slot 1 takes a shared reference (admission hit), slot 1 diverges onto
    a fresh page (CoW), then a batched release drops both slots and every
    refcount returns to zero."""
    cfg, pool = _paged_pool(slots=3, kv_tail=16, bs=16)
    tb = pool.tail // pool.bs
    state = pool.init_state()

    # slot 0 fills its tail twice and refreezes onto fresh pages 0, 1
    for newpage in range(2):
        fill = jnp.asarray([16, 0, 0], jnp.int32)
        state = dict(state, tail_len=fill, pos=state["pos"] + fill)
        ids = np.zeros((pool.slots, tb), np.int32)
        ids[0] = [newpage]
        state = jax.jit(pool.refreeze)(state, jnp.asarray(ids))
    assert np.asarray(state["prefix_blocks"]).tolist() == [2, 0, 0]
    assert np.asarray(state["table"])[0, :2].tolist() == [0, 1]
    assert np.asarray(state["refcount"])[:2].tolist() == [1, 1]

    # slot 1 admits on a prefix-cache hit over the same two pages
    pad = np.zeros(pool.max_blocks, np.int32)
    pad[:2] = [0, 1]
    state = jax.jit(pool.assign_blocks)(state, jnp.int32(1),
                                        jnp.asarray(pad), jnp.int32(2))
    assert np.asarray(state["refcount"])[:2].tolist() == [2, 2]
    assert np.asarray(state["pos"]).tolist() == [32, 32, 0]
    assert np.asarray(state["table"])[1, :2].tolist() == [0, 1]

    # slot 1 diverges: its own tail refreezes onto FRESH page 2 (CoW) —
    # the shared pages are untouched, only its table row grows
    before = [np.asarray(state["layers"]["l0"]["kv"][k])[:, :2].copy()
              for k in ("k_bitmap", "k_values")]
    fill = jnp.asarray([0, 16, 0], jnp.int32)
    state = dict(state, tail_len=fill, pos=state["pos"] + fill)
    ids = np.zeros((pool.slots, tb), np.int32)
    ids[1] = [2]
    state = jax.jit(pool.refreeze)(state, jnp.asarray(ids))
    assert np.asarray(state["table"])[1, :3].tolist() == [0, 1, 2]
    assert np.asarray(state["table"])[0, :2].tolist() == [0, 1]
    assert np.asarray(state["refcount"])[:3].tolist() == [2, 2, 1]
    for b4, key in zip(before, ("k_bitmap", "k_values")):
        np.testing.assert_array_equal(
            b4, np.asarray(state["layers"]["l0"]["kv"][key])[:, :2],
            err_msg=f"CoW wrote shared {key} pages")

    # batched release of both slots in ONE call: the shared pages are
    # decref'd once per referencing slot (scatter-add), all counts at 0
    rel = np.full(pool.slots, -1, np.int32)
    rel[:2] = [0, 1]
    state = jax.jit(pool.release)(state, jnp.asarray(rel))
    assert np.asarray(state["refcount"]).sum() == 0
    assert np.asarray(state["table"]).sum() == 0
    assert np.asarray(state["pos"]).tolist() == [0, 0, 0]


def test_release_vector_matches_scalar_loop():
    """Batched release == the scalar loop it replaces, flat and paged."""
    for paged in (False, True):
        cfg = get_config("qwen3-0.6b").reduced()
        cfg = dataclasses.replace(cfg, kv_k_sparsity=0.0, kv_v_sparsity=0.0,
                                  kv_tail=16)
        pool = CachePool.build(cfg, slots=4, max_tokens=64, paged=paged)
        state = pool.init_state()
        state["pos"] = jnp.asarray([5, 9, 3, 7], jnp.int32)
        state["tail_len"] = jnp.asarray([5, 9, 3, 7], jnp.int32)
        if paged:
            state["prefix_blocks"] = jnp.asarray([2, 1, 0, 0], jnp.int32)
            state["table"] = state["table"].at[0, :2].set(
                jnp.asarray([3, 4]))
            state["table"] = state["table"].at[1, :1].set(5)
            state["refcount"] = state["refcount"].at[
                jnp.asarray([3, 4, 5])].set(1)
        vec = jnp.asarray([0, 2, -1, -1], jnp.int32)
        batched = pool.release(state, vec)
        looped = pool.release(pool.release(state, jnp.int32(0)),
                              jnp.int32(2))
        for a, b in zip(jax.tree_util.tree_leaves(batched),
                        jax.tree_util.tree_leaves(looped)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@settings(max_examples=25, deadline=None)
@given(ops_seq=st.lists(st.integers(min_value=0, max_value=99),
                        min_size=1, max_size=12))
def test_refcount_conservation_property(ops_seq):
    """Any admit / refreeze(CoW) / release walk conserves refcounts:
    ``sum(refcount) == live table entries`` after every transition, the
    device vector mirrors the host allocator, nothing double-frees, and
    the allocator never hands out a page some slot still references."""
    cfg, pool = _paged_pool(slots=3, kv_tail=16, bs=16, max_tokens=64)
    tb = pool.tail // pool.bs
    alloc = BlockAllocator(pool.n_phys)
    state = pool.init_state()
    blocks = {}                                   # slot -> [ids]

    refreeze = jax.jit(pool.refreeze)
    assign = jax.jit(pool.assign_blocks)
    release = jax.jit(pool.release)

    def check():
        rc = np.asarray(state["refcount"])
        live = sum(len(v) for v in blocks.values())
        assert rc.sum() == live, (rc, blocks)
        assert rc.min() >= 0
        for bid in range(pool.n_phys):
            assert rc[bid] == alloc.refcount(bid), bid
        held = {b for ids in blocks.values() for b in ids}
        for bid in held:
            assert rc[bid] > 0

    for code in ops_seq:
        op, arg = code % 3, code // 3
        if op == 0:       # grow a slot: fill tail, refreeze onto fresh page
            slot = arg % pool.slots
            if (len(blocks.get(slot, ())) + tb > pool.max_blocks
                    or alloc.free_blocks() < tb):
                continue
            tl = np.zeros(pool.slots, np.int32)
            tl[slot] = pool.tail
            fresh = alloc.alloc(tb)
            ids = np.zeros((pool.slots, tb), np.int32)
            ids[slot] = fresh
            state = dict(state, tail_len=jnp.asarray(tl),
                         pos=state["pos"] + jnp.asarray(tl))
            state = dict(refreeze(state, jnp.asarray(ids)))
            blocks.setdefault(slot, []).extend(fresh)
        elif op == 1:     # admit a free slot on a hit over another's prefix
            free = [s for s in range(pool.slots) if s not in blocks]
            donors = [s for s in blocks if blocks[s]]
            if not free or not donors:
                continue
            slot, donor = free[0], donors[arg % len(donors)]
            n = arg % len(blocks[donor]) + 1
            hits = blocks[donor][:n]
            alloc.incref(hits)
            pad = np.zeros(pool.max_blocks, np.int32)
            pad[:n] = hits
            state = dict(assign(state, jnp.int32(slot),
                                jnp.asarray(pad), jnp.int32(n)))
            blocks[slot] = list(hits)
        else:             # release a subset of live slots in one call
            live_slots = sorted(blocks)
            if not live_slots:
                continue
            picked = live_slots[:arg % len(live_slots) + 1]
            vec = np.full(pool.slots, -1, np.int32)
            vec[:len(picked)] = picked
            state = dict(release(state, jnp.asarray(vec)))
            for s in picked:
                alloc.decref(blocks.pop(s))
        check()


# ---------------------------------------------------------------------------
# sanitized mode: the same transitions under checkify
# ---------------------------------------------------------------------------

def _checked_paged_pool():
    cfg = get_config("qwen3-0.6b").reduced()
    cfg = dataclasses.replace(cfg, kv_k_sparsity=0.0, kv_v_sparsity=0.0,
                              kv_tail=16)
    return CachePool.build(cfg, slots=3, max_tokens=64, bs=16, paged=True,
                           checkify=True)


def _checked(fn):
    """jit the functionalized transition, throw at the call site — the
    same composition the engine's checkify mode uses."""
    from repro.serving.cache_pool import checkified_raw
    checked = jax.jit(checkified_raw(fn))

    def run(*args):
        err, out = checked(*args)
        err.throw()
        return dict(out)
    return run


def test_checkify_clean_refcount_walk():
    """The shared-prefix lifetime (freeze twice, admit on a hit, CoW
    diverge, batched release) runs unchanged under the sanitized mode —
    every transition carries live checkify invariants and none fires."""
    pool = _checked_paged_pool()
    assert pool.checkify
    tb = pool.tail // pool.bs
    state = pool.init_state()
    refreeze = _checked(pool.refreeze)
    assign = _checked(pool.assign_blocks)
    release = _checked(pool.release)

    for newpage in range(2):
        fill = jnp.asarray([16, 0, 0], jnp.int32)
        state = dict(state, tail_len=fill, pos=state["pos"] + fill)
        ids = np.zeros((pool.slots, tb), np.int32)
        ids[0] = [newpage]
        state = refreeze(state, jnp.asarray(ids))
    pad = np.zeros(pool.max_blocks, np.int32)
    pad[:2] = [0, 1]
    state = assign(state, jnp.int32(1), jnp.asarray(pad), jnp.int32(2))
    fill = jnp.asarray([0, 16, 0], jnp.int32)
    state = dict(state, tail_len=fill, pos=state["pos"] + fill)
    ids = np.zeros((pool.slots, tb), np.int32)
    ids[1] = [2]
    state = refreeze(state, jnp.asarray(ids))
    rel = np.full(pool.slots, -1, np.int32)
    rel[:2] = [0, 1]
    state = release(state, jnp.asarray(rel))
    assert np.asarray(state["refcount"]).sum() == 0


def test_checkify_catches_cow_violation():
    """Refreezing onto a page another slot still references is the
    copy-on-write violation the sanitized mode exists to catch."""
    from jax.experimental.checkify import JaxRuntimeError
    pool = _checked_paged_pool()
    tb = pool.tail // pool.bs
    state = pool.init_state()
    refreeze = _checked(pool.refreeze)

    fill = jnp.asarray([16, 0, 0], jnp.int32)
    state = dict(state, tail_len=fill, pos=state["pos"] + fill)
    ids = np.zeros((pool.slots, tb), np.int32)
    state = refreeze(state, jnp.asarray(ids))       # slot 0 -> page 0
    fill = jnp.asarray([0, 16, 0], jnp.int32)
    state = dict(state, tail_len=fill, pos=state["pos"] + fill)
    ids = np.zeros((pool.slots, tb), np.int32)      # slot 1 -> page 0 again
    with pytest.raises(JaxRuntimeError, match="already referenced"):
        refreeze(state, jnp.asarray(ids))


def test_checkify_catches_release_underflow():
    from jax.experimental.checkify import JaxRuntimeError
    pool = _checked_paged_pool()
    state = pool.init_state()
    state["prefix_blocks"] = jnp.asarray([1, 0, 0], jnp.int32)
    state["table"] = state["table"].at[0, 0].set(3)
    state["pos"] = jnp.asarray([16, 0, 0], jnp.int32)
    # refcount[3] left at 0: a device-side double free
    release = _checked(pool.release)
    with pytest.raises(JaxRuntimeError, match="underflow"):
        release(state, jnp.int32(0))


def test_checkify_off_traces_no_check_eqns():
    """The default pool must trace ZERO check primitives — sanitized mode
    is opt-in, not a tax."""
    cfg, pool = _paged_pool()
    assert not pool.checkify
    state = pool.init_state()
    ids = jnp.zeros((pool.slots, pool.tail // pool.bs), jnp.int32)
    jaxpr = str(jax.make_jaxpr(pool.refreeze)(state, ids))
    assert "check " not in jaxpr and "check[" not in jaxpr


# ---------------------------------------------------------------------------
# refreeze: only the full rows are compressed, bit for bit the all-rows fold
# ---------------------------------------------------------------------------

def _all_rows_refreeze(pool, state, new_ids=None):
    """The fold as it was before it looped over the full slots: compress
    every slot's tail, then keep the full rows by masked scatters.  Kept
    here as the oracle the per-slot fold must match bit for bit."""
    cfg = pool.cfg
    t, tb = pool.tail, pool.tail // pool.bs
    full = state["tail_len"] >= t
    pb = state["prefix_blocks"]
    if pool.paged:
        ids = jnp.asarray(new_ids, jnp.int32)
        drop_ids = jnp.where(full[:, None], ids, pool.n_phys).reshape(-1)
    new_layers = {}
    for name, leaf in state["layers"].items():
        kv = leaf["kv"]
        p_, b_, hkv, _, hd = kv["k_tail"].shape
        flat = lambda a: a.reshape(p_ * b_, hkv, t, hd)
        k_bm, k_vl, v_bm, v_vl = freeze_chunk_blocks(
            flat(kv["k_tail"]), flat(kv["v_tail"]),
            cfg.kv_k_sparsity, cfg.kv_v_sparsity,
            pool.bs, pool.cap_k, pool.cap_v)
        if pool.paged:
            def write(dst, upd):
                u = upd.reshape(p_, b_, hkv, tb, -1)
                u = u.transpose(0, 1, 3, 2, 4).reshape(p_, b_ * tb, hkv, -1)
                u = to_arena_tiles(u, dst.shape[-2])     # the arena's tiles
                return dst.at[:, drop_ids].set(u.astype(dst.dtype),
                                               mode="drop")
        else:
            def write(dst, upd):
                upd = upd.reshape((p_, b_) + upd.shape[1:])
                out = jax.vmap(
                    lambda db, ub, off: jax.lax.dynamic_update_slice(
                        db, ub.astype(db.dtype), (0, 0, off, 0)),
                    in_axes=(1, 1, 0), out_axes=1)(dst, upd, pb)
                sel = full.reshape((1, b_) + (1,) * (dst.ndim - 2))
                return jnp.where(sel, out, dst)
        new_layers[name] = {"kv": {
            **kv,
            "k_bitmap": write(kv["k_bitmap"], k_bm),
            "k_values": write(kv["k_values"], k_vl),
            "v_bitmap": write(kv["v_bitmap"], v_bm),
            "v_values": write(kv["v_values"], v_vl),
        }}
    out = {**state, "layers": new_layers,
           "prefix_blocks": pb + jnp.where(full, tb, 0).astype(jnp.int32),
           "tail_len": jnp.where(full, 0, state["tail_len"])}
    if pool.paged:
        row_ids = jnp.clip(ids, 0, pool.n_phys - 1)
        grown = jax.vmap(
            lambda row, idr, off: jax.lax.dynamic_update_slice(
                row, idr, (off,)))(state["table"], row_ids, pb)
        out["table"] = jnp.where(full[:, None], grown, state["table"])
        out["refcount"] = state["refcount"].at[drop_ids].add(1, mode="drop")
    return out


def _fold_pool(paged, checkify):
    """A sparse pool whose V capacity truncates (both packing paths run):
    4 slots, 2-block tails, an arena with room for every slot's prefix."""
    cfg = get_config("qwen3-0.6b").reduced()
    cfg = dataclasses.replace(cfg, kv_k_sparsity=0.3, kv_v_sparsity=0.5,
                              kv_tail=32)
    return CachePool.build(cfg, slots=4, max_tokens=128, bs=16,
                           capacity_slack=1.0, paged=paged,
                           checkify=checkify)


def _fold_case(pool, full_slots, seed=0):
    """A mid-serving state: random tails and compressed storage, slots at
    different prefix lengths, ``full_slots`` with a full ring and the rest
    part-way; for a paged pool the live table entries hold referenced
    pages and the fresh ids point past them (non-full rows carry ids
    that are live, which the fold must ignore)."""
    rng = np.random.default_rng(seed)
    b, tb = pool.slots, pool.tail // pool.bs
    state = pool.init_state()
    layers = {}
    for name, leaf in state["layers"].items():
        kv = {}
        for k, a in leaf["kv"].items():
            if a.dtype == jnp.uint32:
                kv[k] = jnp.asarray(rng.integers(0, 2**32, a.shape,
                                                 dtype=np.uint32))
            else:
                kv[k] = jnp.asarray(rng.normal(size=a.shape)).astype(a.dtype)
        layers[name] = {"kv": kv}
    pb = np.array([0, 1, 3, 2], np.int32)[:b]
    tail_len = np.array([5, 17, 1, 30], np.int32)[:b]
    tail_len[list(full_slots)] = pool.tail
    state = {**state, "layers": layers,
             "prefix_blocks": jnp.asarray(pb),
             "tail_len": jnp.asarray(tail_len),
             "pos": jnp.asarray(pb * pool.bs + tail_len)}
    if not pool.paged:
        return state, None
    table = np.zeros((b, pool.max_blocks), np.int32)
    refcount = np.zeros(pool.n_phys, np.int32)
    nxt = 0
    for s in range(b):
        table[s, :pb[s]] = np.arange(nxt, nxt + pb[s])
        refcount[nxt:nxt + pb[s]] = 1
        nxt += pb[s]
    ids = np.zeros((b, tb), np.int32)          # non-full: a live page
    for s in full_slots:
        ids[s] = np.arange(nxt, nxt + tb)
        nxt += tb
    state = {**state, "table": jnp.asarray(table),
             "refcount": jnp.asarray(refcount)}
    return state, jnp.asarray(ids)


@pytest.fixture(scope="module")
def folds():
    """(pool, per-slot fold, all-rows oracle) for a pool kind, jitted once
    per kind for the module."""
    made = {}

    def get(paged, checkify):
        if (paged, checkify) not in made:
            pool = _fold_pool(paged, checkify)
            new = (_checked(pool.refreeze) if checkify
                   else jax.jit(pool.refreeze))
            oracle = jax.jit(lambda st, ids=None: _all_rows_refreeze(
                pool, st, ids))
            made[paged, checkify] = (pool, new, oracle)
        return made[paged, checkify]
    return get


@pytest.mark.parametrize("full_slots", [(), (2,), (0, 3), (0, 1, 2, 3)],
                         ids=["none", "one", "several", "all"])
@pytest.mark.parametrize("checkify", [False, True],
                         ids=["plain", "checkify"])
@pytest.mark.parametrize("paged", [False, True], ids=["flat", "paged"])
def test_refreeze_matches_all_rows_fold_bit_for_bit(folds, paged, checkify,
                                                    full_slots):
    """Compressing only the full slots' tails gives the state the all-rows
    fold gives, every leaf bit for bit: a slot's blocks do not depend on
    the slots folded beside it, and the rest of the state is untouched."""
    pool, new, oracle = folds(paged, checkify)
    state, ids = _fold_case(pool, full_slots)
    args = (state, ids) if paged else (state,)
    got, want = dict(new(*args)), oracle(*args)
    leaves = lambda t: jax.tree_util.tree_flatten_with_path(t)[0]
    assert len(leaves(got)) == len(leaves(want))
    for (path, g), (_, w) in zip(leaves(got), leaves(want)):
        assert g.dtype == w.dtype and g.shape == w.shape, path
        np.testing.assert_array_equal(
            np.asarray(g).view(np.uint8), np.asarray(w).view(np.uint8),
            err_msg=jax.tree_util.keystr(path))
    tb = pool.tail // pool.bs
    grew = np.asarray(got["prefix_blocks"]) - np.asarray(
        state["prefix_blocks"])
    assert grew.tolist() == [tb if s in full_slots else 0
                             for s in range(pool.slots)]
    assert pool.refreeze_rows(len(full_slots)) == len(full_slots)


@pytest.mark.parametrize("paged", [False, True], ids=["flat", "paged"])
def test_engine_refreeze_traces_once_across_full_counts(paged):
    """The engine's jitted fold is one program for every count of full
    slots: folding 1, 2, 0 and all slots adds no trace after the first."""
    from repro.serving import retrace_count
    cfg, params = _setup()
    eng = ContinuousEngine(params, cfg, slots=3, max_tokens=96,
                           prefill_chunk=16, paged=paged)
    tb = eng.pool.tail // eng.pool.bs
    for n in (1, 2, 0, 3):
        tail_len = np.zeros(eng.pool.slots, np.int32)
        tail_len[:n] = eng.pool.tail
        st = {**eng.state, "tail_len": jnp.asarray(tail_len),
              "prefix_blocks": jnp.zeros(eng.pool.slots, jnp.int32)}
        if paged:
            st["refcount"] = jnp.zeros_like(st["refcount"])
            ids = np.arange(eng.pool.slots * tb, dtype=np.int32)
            out = eng._refreeze(st, jnp.asarray(ids.reshape(-1, tb)))
        else:
            out = eng._refreeze(st)
        assert np.asarray(out["prefix_blocks"]).tolist() == \
            [tb] * n + [0] * (eng.pool.slots - n)
        assert retrace_count(eng._refreeze) == 1


# ---------------------------------------------------------------------------
# host side: allocator + prefix trie
# ---------------------------------------------------------------------------

def test_block_allocator_lru_eviction_and_revival():
    evicted = []
    alloc = BlockAllocator(3, on_evict=evicted.append)
    a, b, c = alloc.alloc(3)
    alloc.register(a, 100)
    alloc.register(b, 200)
    assert alloc.free_blocks() == 0
    alloc.decref([a, b])          # both park in the LRU, oldest = a
    assert alloc.free_blocks() == 2
    assert alloc.lookup(100) == a and alloc.lookup(200) == b
    alloc.incref([b])             # revive b out of the LRU
    [d] = alloc.alloc(1)          # must evict a (cold end), NEVER b or c
    assert d == a and evicted == [100]
    assert alloc.lookup(100) is None and alloc.lookup(200) == b
    alloc.decref([c])             # unregistered: straight to the free list
    assert alloc.free_blocks() == 1
    with pytest.raises(RuntimeError, match="double free"):
        alloc.decref([c])
    with pytest.raises(RuntimeError, match="exhausted"):
        alloc.alloc(2)            # only 1 reclaimable (b, d live)


def test_block_hashes_chain_and_trie_match():
    bs = 4
    a = list(range(12))
    b = list(range(8)) + [99, 98, 97, 96]
    ha, hb = block_hashes(a, bs), block_hashes(b, bs)
    assert len(ha) == 3 and ha[:2] == hb[:2] and ha[2] != hb[2]
    # a trailing partial block is never hashed; chaining => a block's hash
    # commits to the WHOLE prefix, so equal blocks at different depths
    # do not collide
    assert block_hashes(a[:11], bs) == ha[:2]
    same_block = block_hashes(a[4:8], bs)
    assert same_block[0] != ha[1]
    trie = PrefixTrie()
    for i, h in enumerate(ha):
        trie.insert(h, i + 10)
    assert trie.match(hb) == [10, 11]         # longest shared prefix
    assert trie.match(block_hashes([7] * 8, bs)) == []
    trie.drop(ha[1])                          # eviction invalidates mid-chain
    assert trie.match(ha) == [10]
    assert len(trie) == 2


# ---------------------------------------------------------------------------
# engine: token identity + zero retraces (the acceptance criterion)
# ---------------------------------------------------------------------------

def _setup(seed=0, kv_tail=16):
    cfg = get_config("qwen3-0.6b").reduced()
    cfg = dataclasses.replace(cfg, kv_k_sparsity=0.3, kv_v_sparsity=0.5,
                              kv_tail=kv_tail, compute_dtype="float32",
                              param_dtype="float32")
    params = lm.init_params(cfg, jax.random.PRNGKey(seed))
    return cfg, params


def _shared_wave(cfg, seed=0):
    """Mixed shared/unshared prompts: a 64-token system prefix with unique
    suffixes (prefix-cache hits), a divergence INSIDE the shared region
    (copy-on-write at block 2), and an unrelated prompt."""
    rng = np.random.default_rng(seed)
    shared = rng.integers(0, cfg.vocab, (64,)).tolist()
    return [
        shared + rng.integers(0, cfg.vocab, (5,)).tolist(),
        shared + rng.integers(0, cfg.vocab, (9,)).tolist(),
        shared[:32] + rng.integers(0, cfg.vocab, (20,)).tolist(),
        rng.integers(0, cfg.vocab, (40,)).tolist(),
    ]


def _drive(eng, prompts, steps=24):
    rids = [eng.submit(p, SamplingParams(max_new_tokens=steps))
            for p in prompts]
    res = eng.run()
    return [res[r].token_ids for r in rids], res


def test_paged_engine_token_identity_and_zero_retraces():
    """Greedy paged output == flat output on the mixed wave (refreeze:
    max_new_tokens > kv_tail; CoW divergence; hits), decode/verify traces
    stay at 1 across a second wave that replays admissions, evictions and
    prefix-cache hits against a warm trie."""
    cfg, params = _setup()
    prompts = _shared_wave(cfg)

    flat = ContinuousEngine(params, cfg, slots=2, max_tokens=128, bs=16,
                            prefill_chunk=32)
    out_flat, _ = _drive(flat, prompts)

    eng = ContinuousEngine(params, cfg, slots=2, max_tokens=128, bs=16,
                           prefill_chunk=32, paged=True)
    out_paged, res = _drive(eng, prompts)
    assert out_paged == out_flat
    warm = eng.trace_counts()
    assert warm["decode"] == 1 and warm["assign"] >= 1

    # second wave: every shared-prefix request now admits on a trie hit
    assert len(eng._trie) > 0
    out2, res2 = _drive(eng, prompts)
    out_flat2, _ = _drive(flat, prompts)
    assert out2 == out_flat2
    after = eng.trace_counts()
    assert stable_trace_counts(after) == stable_trace_counts(warm), \
        f"paged engine retraced: {warm} -> {after}"
    # hit TTFT < miss TTFT: the shared prefill was skipped outright
    ttft1 = min(o.metrics.ttft for o in res.values())
    ttft2 = min(o.metrics.ttft for o in res2.values())
    assert ttft2 < ttft1


def test_paged_prefix_hit_skips_prefill_and_shares_pages():
    cfg, params = _setup()
    rng = np.random.default_rng(1)
    shared = rng.integers(0, cfg.vocab, (64,)).tolist()
    p0 = shared + rng.integers(0, cfg.vocab, (6,)).tolist()
    p1 = shared + rng.integers(0, cfg.vocab, (3,)).tolist()

    eng = ContinuousEngine(params, cfg, slots=2, max_tokens=128, bs=16,
                           prefill_chunk=32, paged=True)
    eng.submit(p0, SamplingParams(max_new_tokens=4))
    eng.run()
    assert len(eng._trie) == 4                   # 64 tokens / bs, chunked
    cached = eng._alloc.free_blocks()

    rid = eng.submit(p1, SamplingParams(max_new_tokens=4))
    eng.step()                                   # admission tick
    req = eng.scheduler.active[
        next(s for s, r in eng.scheduler.active.items() if r.rid == rid)]
    # the 64-token hit IS the prefill: one tick covers hit + the 3-token
    # suffix chunk (a cold 67-token prompt at chunk=32 needs 3 ticks)
    assert req.prefill_done == len(p1)
    row = eng._blocks[req.slot]
    assert len(row) >= 4
    rc = np.asarray(eng.state["refcount"])
    assert all(rc[b] == 1 for b in row[:4])      # revived from the LRU
    assert eng._alloc.free_blocks() < cached
    out = eng.run()
    assert out[rid].finish_reason == "length"

    # flat engine on the same prompt agrees token-for-token
    flat = ContinuousEngine(params, cfg, slots=2, max_tokens=128, bs=16,
                            prefill_chunk=32)
    fid = flat.submit(p1, SamplingParams(max_new_tokens=4))
    assert flat.run()[fid].token_ids == out[rid].token_ids


def test_paged_eviction_invalidates_trie_and_stays_correct():
    """A tiny arena: new traffic must LRU-evict the cached shared prefix
    (trie entries drop), and a later request with that prefix re-prefills
    and still matches the flat engine."""
    cfg, params = _setup()
    rng = np.random.default_rng(2)
    shared = rng.integers(0, cfg.vocab, (48,)).tolist()
    p0 = shared + rng.integers(0, cfg.vocab, (4,)).tolist()
    other = [rng.integers(0, cfg.vocab, (52,)).tolist() for _ in range(2)]

    # arena of 7 pages; each request freezes 3 (and reserves 4), so the
    # third wave must evict the first's cached pages
    eng = ContinuousEngine(params, cfg, slots=1, max_tokens=64, bs=16,
                           prefill_chunk=16, paged=True, phys_blocks=7)
    sp = SamplingParams(max_new_tokens=8)
    r0 = eng.submit(p0, sp)
    first = eng.run()[r0].token_ids
    trie0 = len(eng._trie)
    assert trie0 > 0
    for p in other:                               # churn: forces eviction
        eng.submit(p, sp)
        eng.run()
    assert len(eng._trie) < trie0 + 2 * 3         # evictions really fired
    r2 = eng.submit(p0, sp)
    assert eng.run()[r2].token_ids == first

    flat = ContinuousEngine(params, cfg, slots=1, max_tokens=64, bs=16,
                            prefill_chunk=16)
    fid = flat.submit(p0, sp)
    assert flat.run()[fid].token_ids == first


def test_paged_spec_decode_token_identity():
    """Speculative decoding on the paged pool: draft-verify rollback is a
    pure tail decrement, so paged + spec greedy == flat spec-off greedy on
    a wave with draft hits (loopy) and misses (random)."""
    cfg, params = _setup()
    rng = np.random.default_rng(3)
    shared = rng.integers(0, cfg.vocab, (32,)).tolist()
    prompts = [shared + [3, 4, 5] * 4,
               shared + rng.integers(0, cfg.vocab, (7,)).tolist(),
               rng.integers(0, cfg.vocab, (20,)).tolist()]

    flat = ContinuousEngine(params, cfg, slots=2, max_tokens=96, bs=16,
                            prefill_chunk=32)
    out_flat, _ = _drive(flat, prompts, steps=20)

    eng = ContinuousEngine(params, cfg, slots=2, max_tokens=96, bs=16,
                           prefill_chunk=32, paged=True,
                           spec=SpecConfig(k=3))
    out_spec, _ = _drive(eng, prompts, steps=20)
    assert out_spec == out_flat
    assert eng.trace_counts()["verify"] == 1
    assert eng.spec_hist.sum() > 0


def test_paged_interpret_mode_parity():
    """The paged engine through the actual Pallas kernels (interpret mode)
    stays token-identical to the flat engine on the same backend — the CI
    paged-parity bar."""
    cfg, params = _setup(kv_tail=16)
    rng = np.random.default_rng(4)
    shared = rng.integers(0, cfg.vocab, (32,)).tolist()
    prompts = [shared + rng.integers(0, cfg.vocab, (4,)).tolist(),
               shared + rng.integers(0, cfg.vocab, (2,)).tolist()]
    sp = SamplingParams(max_new_tokens=6)
    with ops.backend("interpret"):
        flat = ContinuousEngine(params, cfg, slots=2, max_tokens=64, bs=16,
                                prefill_chunk=32)
        rf = [flat.submit(p, sp) for p in prompts]
        out_flat = [flat.run()[r].token_ids for r in rf]
        eng = ContinuousEngine(params, cfg, slots=2, max_tokens=64, bs=16,
                               prefill_chunk=32, paged=True)
        rp = [eng.submit(p, sp) for p in prompts]
        out_paged = [eng.run()[r].token_ids for r in rp]
        assert eng.trace_counts()["decode"] == 1
    assert out_paged == out_flat


def test_paged_interpret_spec_fold_and_hit_parity():
    """Through the Pallas kernels (interpret mode), the paged engine reads
    its stacked arena in place on every path a request takes: a
    speculative verify panel (``Qn = K + 1``), decode past a fold of the
    tail ring, and a prefix-cache hit of a second request.  Its greedy
    tokens are the flat engine's on the same backend."""
    cfg, params = _setup(kv_tail=16)
    rng = np.random.default_rng(5)
    shared = rng.integers(0, cfg.vocab, (32,)).tolist()
    prompts = [shared + [7, 8, 9] * 2,
               shared + rng.integers(0, cfg.vocab, (3,)).tolist()]
    sp = SamplingParams(max_new_tokens=14)    # 38 + 14 > 48: one fold
    def one_by_one(eng):
        out = []
        for p in prompts:
            rid = eng.submit(p, sp)
            out.append(eng.run()[rid].token_ids)
        return out
    with ops.backend("interpret"):
        out_flat = one_by_one(ContinuousEngine(
            params, cfg, slots=1, max_tokens=64, bs=16, prefill_chunk=32))
        eng = ContinuousEngine(params, cfg, slots=1, max_tokens=64, bs=16,
                               prefill_chunk=32, paged=True,
                               spec=SpecConfig(k=2))
        out_paged = one_by_one(eng)
        counts = eng.trace_counts()
    assert out_paged == out_flat
    assert counts["verify"] == 1 and counts["refreeze"] == 1
    assert counts["assign"] == 1              # the second prompt's hit


def test_snapshot_of_another_arena_layout_is_refused(tmp_path):
    """The arena's stored layout is part of a snapshot's geometry: pages
    saved in another layout are refused by name, never loaded."""
    import json
    import os
    cfg, params = _setup()
    eng = ContinuousEngine(params, cfg, slots=2, max_tokens=64, bs=16,
                           prefill_chunk=32, paged=True)
    assert eng.pool.geometry()["arena_layout"]
    eng.submit(list(range(40)), SamplingParams(max_new_tokens=2))
    eng.run()
    snap = str(tmp_path / "snap")
    step = eng.save_snapshot(snap)
    man = os.path.join(snap, f"step_{step:010d}", "manifest.json")
    with open(man) as f:
        manifest = json.load(f)
    del manifest["geometry"]["arena_layout"]   # a snapshot from before it
    with open(man, "w") as f:
        json.dump(manifest, f)
    fresh = ContinuousEngine(params, cfg, slots=2, max_tokens=64, bs=16,
                             prefill_chunk=32, paged=True)
    with pytest.raises(ValueError, match="arena_layout"):
        fresh.load_snapshot(snap)
    assert len(fresh._trie) == 0
