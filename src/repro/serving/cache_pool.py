"""Slot-pooled, jit-stable sparse-KV cache for continuous batching.

The legacy one-shot engine stored the compressed prefix at whatever
capacity the data produced, so every refreeze grew the cache shapes and
forced a fresh ``jax.jit`` trace of the decode step — fatal for a serving
engine.  The pool inverts that: **storage is sized once, data moves within
it**.

Per layer period, every slot owns

* a fixed grid of ``max_blocks`` compressed sequence blocks — bitmap words
  plus packed values at a *static* per-block capacity (``pack_blocks``
  drops overflow consistently from bitmap and values, so the bitmap always
  describes exactly what is stored);
* a dense ``tail`` ring of ``tail`` tokens for freshly decoded K/V.

Slot occupancy lives in three int32 ``[slots]`` vectors (``pos``,
``prefix_blocks``, ``tail_len``); validity is *masked*, never re-shaped.
Refreeze therefore folds a full tail into the next free prefix blocks **in
place**: compress the tail of every full slot at the pool's static
capacity, scatter the new blocks at each slot's own offset, bump the
lengths.  No shape changes, no retrace — the decode step compiles exactly
once per pool geometry, which is the property the paper's "cache frozen in
model state" design needs to survive heavy multi-tenant traffic.

Both dense and sparse KV live behind this one interface: a dense pool is
just ``k_sparsity = v_sparsity = 0`` (full per-block capacity), for which
compression is a bit-exact round trip.

**Paged mode** (``paged=True``) generalizes the per-slot block grid into a
pool-global arena: compressed blocks live once in
``[P, n_phys, Hkv, rows, 128]`` storage (each block's packed words or
values as one tile of lane rows, see :func:`arena_rows`), each slot's prefix is a row of the ``[slots, max_blocks]`` int32
**block table**, and a ``[n_phys]`` **refcount** vector tracks sharing —
N requests whose prompts share a prefix point their table rows at the SAME
physical blocks (stored once, attended over once).  Frozen blocks are
immutable; the dense tail ring is each slot's private working copy, and
refreeze/prefill always append FRESH physical ids past the shared prefix —
copy-on-write at the divergence block by construction, never a write into
shared storage.  The table and refcount are data, so every transition
below stays pure over static shapes and decode still compiles exactly once
per pool geometry.  The host-side id lifecycle (free list, LRU reuse of
refcount-0 cached blocks, hash bookkeeping) lives in
:class:`BlockAllocator`; the device transitions only consume the id
vectors it hands out.
"""
from __future__ import annotations

import dataclasses
import os
from collections import OrderedDict
from typing import Any, Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import checkify as _checkify

from repro.core.sparse_format import _ceil_to, LANE
from repro.core.sparse_kv import (ARENA_KEYS, append_tail_panel,
                                  arena_rows, freeze_chunk_blocks,
                                  to_arena_tiles)
from repro.models import lm


@dataclasses.dataclass(frozen=True)
class CachePool:
    """Geometry + pure state-transition ops for the pooled serving cache.

    The dataclass itself is immutable config; all state lives in the pytree
    returned by :meth:`init_state` and flows through the pure methods below
    (each is jitted exactly once by the engine).
    """
    cfg: Any
    slots: int
    max_blocks: int          # compressed-prefix capacity, in (bs,)-blocks
    bs: int                  # tokens per compressed block
    tail: int                # dense-tail ring size (tokens)
    cap_k: int               # packed K values per block (static)
    cap_v: int
    paged: bool = False      # pool-global arena + per-slot block table
    n_phys: int = 0          # physical blocks in the paged arena
    checkify: bool = False   # opt-in sanitized mode (see ``checkified``)

    @classmethod
    def build(cls, cfg, slots: int, max_tokens: int,
              bs: int = 0, capacity_slack: float = 1.25,
              paged: bool = False, n_phys: int = 0,
              checkify: Optional[bool] = None) -> "CachePool":
        """Size a pool for ``slots`` concurrent requests of up to
        ``max_tokens`` context each.

        Per-block value capacity is the nominal density times the block
        size, padded by ``capacity_slack`` and rounded to the lane size —
        headroom for the unevenness of the paper's layer-wide magnitude
        rule.  Zero sparsity always gets full capacity (exact round trip).

        ``paged=True`` stores compressed blocks in a shared physical arena
        of ``n_phys`` blocks (default ``slots * max_blocks`` — the same
        prefix bytes as the flat pool) indexed through per-slot block
        tables, so requests sharing a prefix store it once.

        Raises :class:`ValueError` for geometries the pool cannot serve:
        architecture families with state the pooled path would drop
        (cross-attention / frontend embeddings / recurrent layers), and a
        ``kv_tail`` that is not a whole number of blocks (refreeze folds
        the tail in whole blocks).
        """
        try:
            lm._attn_kinds(cfg)   # ssm/hybrid/encdec/frontend families
        except ValueError as e:
            raise ValueError(
                f"CachePool cannot serve arch {cfg.name!r} "
                f"(family {cfg.family!r}): {e}") from None
        bs = bs or min(128, cfg.kv_tail)
        if cfg.kv_tail % bs != 0:
            raise ValueError(
                f"kv_tail={cfg.kv_tail} is not a multiple of the block "
                f"size bs={bs}: refreeze folds the dense tail into whole "
                f"(bs,)-token compressed blocks")
        l = bs * cfg.hd

        def cap(sparsity: float) -> int:
            density = 1.0 - sparsity
            if density >= 1.0:
                return l
            return min(_ceil_to(int(round(density * l * capacity_slack)),
                                LANE), l)
        max_blocks = max(-(-int(max_tokens) // bs), 1)
        if paged:
            n_phys = n_phys or slots * max_blocks
        if checkify is None:
            checkify = os.environ.get("REPRO_CHECKIFY", "0") not in ("", "0")
        return cls(cfg=cfg, slots=slots, max_blocks=max_blocks, bs=bs,
                   tail=cfg.kv_tail, cap_k=cap(cfg.kv_k_sparsity),
                   cap_v=cap(cfg.kv_v_sparsity), paged=paged,
                   n_phys=n_phys if paged else 0, checkify=checkify)

    # -- geometry -----------------------------------------------------------
    @property
    def capacity_tokens(self) -> int:
        """Max context length a slot may be admitted for.

        Conservative: refreeze folds the whole live context into the prefix
        over time, so admission bounds by the prefix storage alone — the
        tail is working space, not extra capacity."""
        return self.max_blocks * self.bs

    def nbytes(self) -> int:
        """Total pooled storage, for capacity planning."""
        return sum(int(np.prod(l.shape)) * l.dtype.itemsize
                   for l in jax.tree_util.tree_leaves(
                       jax.eval_shape(self.init_state)))

    def geometry(self) -> Dict[str, Any]:
        """Everything a snapshot of the paged arena depends on.

        Two pools can exchange arena bytes + prefix hashes iff these match:
        block content is a function of the architecture, the block size,
        the packed capacities, and the compression rule; the arena layout
        adds ``n_phys`` / head-count / dtype and the arena's own layout
        (``arena_layout``, renamed whenever the stored shape of a block
        changes).  ``slots`` is deliberately ABSENT — the arena is
        ``[P, n_phys, Hkv, rows, 128]``, slot-independent,
        so a restarted server may resize its slot count and still restore
        a warm cache.  (Content-addressed chained hashes make *stale* data
        impossible by construction; geometry is the only thing to check.)
        """
        cfg = self.cfg
        return {
            "arch": cfg.name,
            "paged": self.paged,
            "bs": self.bs,
            "max_blocks": self.max_blocks,
            "n_phys": self.n_phys,
            "cap_k": self.cap_k,
            "cap_v": self.cap_v,
            "n_kv": cfg.n_kv,
            "hd": cfg.hd,
            "n_periods": cfg.n_layers // lm.period_len(cfg),
            "cdtype": np.dtype(cfg.cdtype).name,
            "kv_k_sparsity": cfg.kv_k_sparsity,
            "kv_v_sparsity": cfg.kv_v_sparsity,
            "arena_layout": ARENA_LAYOUT if self.paged else None,
        }

    # -- sanitized mode -----------------------------------------------------
    def _check(self, pred, msg: str) -> None:
        """Emit a checkify invariant when the pool was built with
        ``checkify=True`` (no-op otherwise, so the default engine path
        traces zero check primitives).  Eager callers fail immediately
        with ``JaxRuntimeError``; jitted callers must functionalize via
        :func:`checkified`."""
        if self.checkify:
            _checkify.check(pred, msg)

    # -- state --------------------------------------------------------------
    def init_state(self) -> Dict[str, Any]:
        """Zeroed pool pytree.  Leaves under ``layers`` carry a leading
        period axis so the model's ``lax.scan`` slices them per layer.

        Flat pool: compressed leaves are per-slot grids
        ``[P, slots, Hkv, max_blocks, X]``.  Paged pool: compressed leaves
        are the shared arena ``[P, n_phys, Hkv, rows, 128]`` (a block's
        ``X`` packed entries as ``rows`` lane rows, :func:`arena_rows`,
        the padding zero) plus the pool-level
        ``table [slots, max_blocks]`` / ``refcount [n_phys]`` int32
        vectors; the dense tails stay per-slot either way (the tail is
        private working state, never shared)."""
        cfg = self.cfg
        p = lm.period_len(cfg)
        n_periods = cfg.n_layers // p
        hkv, hd, dt = cfg.n_kv, cfg.hd, cfg.cdtype
        b, sb, w = self.slots, self.max_blocks, self.bs * hd // 32

        def kv_leaf():
            if self.paged:
                tile = lambda x, dtype: jnp.zeros(  # noqa: E731
                    (n_periods, self.n_phys, hkv, arena_rows(x), LANE),
                    dtype)
                comp = {"k_bitmap": tile(w, jnp.uint32),
                        "k_values": tile(self.cap_k, dt),
                        "v_bitmap": tile(w, jnp.uint32),
                        "v_values": tile(self.cap_v, dt)}
            else:
                comp = {
                    "k_bitmap": jnp.zeros((n_periods, b, hkv, sb, w),
                                          jnp.uint32),
                    "k_values": jnp.zeros((n_periods, b, hkv, sb, self.cap_k),
                                          dt),
                    "v_bitmap": jnp.zeros((n_periods, b, hkv, sb, w),
                                          jnp.uint32),
                    "v_values": jnp.zeros((n_periods, b, hkv, sb, self.cap_v),
                                          dt),
                }
            return {
                **comp,
                "k_tail": jnp.zeros((n_periods, b, hkv, self.tail, hd), dt),
                "v_tail": jnp.zeros((n_periods, b, hkv, self.tail, hd), dt),
            }
        state = {
            "pos": jnp.zeros((b,), jnp.int32),
            "prefix_blocks": jnp.zeros((b,), jnp.int32),
            "tail_len": jnp.zeros((b,), jnp.int32),
            "layers": {f"l{j}": {"kv": kv_leaf()} for j in range(p)},
        }
        if self.paged:
            state["table"] = jnp.zeros((b, sb), jnp.int32)
            state["refcount"] = jnp.zeros((self.n_phys,), jnp.int32)
        return state

    def state_axes(self) -> Dict[str, Any]:
        """Logical-axes pytree matching :meth:`init_state` leaf for leaf —
        the pool's own description of how its storage may shard
        (``distributed/serving_sharding`` turns it into NamedShardings).

        Slot occupancy vectors are ``[slots]`` -> the slot axis; every
        flat layer leaf is ``[P, slots, Hkv, ...]`` -> slots over the data
        axes, KV heads over the model axis, block/ring/packed dims
        unsharded (block storage is per-(slot, head) and refreeze's
        scatter is per-slot — no cross-shard writes ever happen).

        Paged: the block table shards with the slots it indexes; the
        arena's physical-block axis is REPLICATED over the data axes (any
        slot on any data shard may point at any physical block — that
        cross-slot reach is the whole point of sharing) while its KV-head
        axis still shards over the model axis, splitting the arena bytes
        the same way the flat grid split; the refcount vector is
        replicated (scatter-adds into it are identical on every shard).
        """
        p = lm.period_len(self.cfg)

        def kv_axes():
            comp = ((None, None, "kv_heads", None, None) if self.paged
                    else (None, "slots", "kv_heads", None, None))
            tail = (None, "slots", "kv_heads", None, None)
            return {**{k: comp for k in ("k_bitmap", "k_values",
                                         "v_bitmap", "v_values")},
                    "k_tail": tail, "v_tail": tail}
        axes = {
            "pos": ("slots",),
            "prefix_blocks": ("slots",),
            "tail_len": ("slots",),
            "layers": {f"l{j}": {"kv": kv_axes()} for j in range(p)},
        }
        if self.paged:
            axes["table"] = ("slots", None)
            axes["refcount"] = (None,)
        return axes

    # -- transitions (pure; the engine jits each exactly once) --------------
    def refreeze(self, state: Dict[str, Any],
                 new_ids: Optional[jax.Array] = None) -> Dict[str, Any]:
        """Fold every full tail into its slot's next free prefix blocks.

        In-place at static shapes: the full slots are picked on device
        from the ``tail_len >= tail`` mask, and a loop whose trip count is
        their number compresses one slot's tail per iteration (all
        periods, at the pool capacity) and writes its blocks at that
        slot's own ``prefix_blocks`` offset.  Only full rows are
        compressed: thresholds are per (row, block) and packing per row,
        so a slot's blocks are the same bytes whichever slots fold with
        it, and one compiled program covers every count of full slots.
        Slots whose tail is not full come back bit-identical.  The caller
        must ensure no full slot overflows ``max_blocks`` (see
        ``Scheduler`` admission).

        Paged pool: ``new_ids`` int32 ``[slots, tail // bs]`` must carry a
        FRESH physical block id per (full slot, tail block) — the host
        :class:`BlockAllocator` hands them out, which is what guarantees
        the fold never writes shared storage (copy-on-write at the
        divergence block: the tail is the private copy, the fold targets
        fresh pages).  Rows for non-full slots are ignored.  The ids land
        in the arena + each full slot's table row, and their refcounts go
        to 1.
        """
        cfg = self.cfg
        t, tb = self.tail, self.tail // self.bs
        full = state["tail_len"] >= t                           # [B]
        pb = state["prefix_blocks"]
        # the full slots in slot order; entries past n_full are never read
        rows = jnp.nonzero(full, size=self.slots, fill_value=0)[0]
        n_full = jnp.sum(full, dtype=jnp.int32)
        carry = {"layers": {name: {k: leaf["kv"][k] for k in ARENA_KEYS}
                            for name, leaf in state["layers"].items()}}
        if self.paged:
            if new_ids is None:
                raise ValueError("paged refreeze needs fresh ids")
            ids = jnp.asarray(new_ids, jnp.int32)               # [B, tb]
            if self.checkify:
                # sanitized mode: ids for full slots must be in-arena AND
                # unreferenced (fresh pages are what guarantees
                # copy-on-write); non-full rows are ignored.  Guarded so
                # the default path traces zero extra eqns.
                live_id = full[:, None] & (ids < self.n_phys)
                self._check(jnp.all(jnp.where(live_id, ids >= 0, True)),
                            "refreeze: fresh id out of arena range")
                rc = jnp.take(state["refcount"], ids, mode="clip")
                self._check(jnp.all(jnp.where(live_id, rc == 0, True)),
                            "refreeze: fresh id already referenced "
                            "(copy-on-write violation)")
            carry["table"] = state["table"]
            carry["refcount"] = state["refcount"]
        if self.checkify:
            self._check(jnp.all(jnp.where(full, pb + tb <= self.max_blocks,
                                          True)),
                        "refreeze: full slot would overflow max_blocks")

        def fold(i, c):
            s = rows[i]
            off = pb[s]
            tail = lambda a: jax.lax.dynamic_index_in_dim(
                a, s, axis=1, keepdims=False)                   # [P,Hkv,T,D]
            if self.paged:
                # an id past the arena is dropped by every scatter below
                slot_ids = ids[s]                               # [tb]
                # [P, Hkv, tb, X] -> [P, tb, Hkv, rows, 128] tiles at the
                # fresh ids on the arena's physical-block axis
                write = lambda dst, upd: dst.at[:, slot_ids].set(
                    to_arena_tiles(upd, dst.shape[-2])
                    .transpose(0, 2, 1, 3, 4).astype(dst.dtype), mode="drop")
            else:
                write = lambda dst, upd: jax.lax.dynamic_update_slice(
                    dst, upd[:, None].astype(dst.dtype), (0, s, 0, off, 0))
            new_layers = {}
            for name, comp in c["layers"].items():
                kv = state["layers"][name]["kv"]
                packed = freeze_chunk_blocks(
                    tail(kv["k_tail"]), tail(kv["v_tail"]),
                    cfg.kv_k_sparsity, cfg.kv_v_sparsity,
                    self.bs, self.cap_k, self.cap_v)            # [P,Hkv,tb,X]
                new_layers[name] = {k: write(comp[k], upd)
                                    for k, upd in zip(ARENA_KEYS, packed)}
            out = {"layers": new_layers}
            if self.paged:
                # the table row grows by tb entries at the slot's own
                # offset (ids clipped in range: table entries are consumed
                # by kernel index maps, so even dead ones must address
                # real storage)
                out["table"] = jax.lax.dynamic_update_slice(
                    c["table"], jnp.clip(slot_ids, 0, self.n_phys - 1)[None],
                    (s, off))
                out["refcount"] = c["refcount"].at[slot_ids].add(
                    1, mode="drop")
            return out

        carry = jax.lax.fori_loop(0, n_full, fold, carry)
        out = {**state,
               "layers": {name: {"kv": {**leaf["kv"],
                                        **carry["layers"][name]}}
                          for name, leaf in state["layers"].items()},
               "prefix_blocks": pb + jnp.where(full, tb, 0).astype(jnp.int32),
               "tail_len": jnp.where(full, 0, state["tail_len"])}
        if self.paged:
            out["table"] = carry["table"]
            out["refcount"] = carry["refcount"]
        return out

    def refreeze_rows(self, n_full: int) -> int:
        """Slot rows one :meth:`refreeze` compresses when ``n_full`` tails
        are full: exactly those.  The engine counts this per fold
        (``repro_refreeze_slots_total``), so it changes whenever the
        transition does."""
        return n_full

    def assign_blocks(self, state: Dict[str, Any], slot: jax.Array,
                      ids: jax.Array, n: jax.Array) -> Dict[str, Any]:
        """Point a freshly-admitted slot's table row at ``n`` existing
        physical blocks (a prefix-cache hit): entries ``[0, n)`` of the
        row become ``ids[:n]``, the blocks' refcounts increment, and the
        slot's lengths jump to the shared prefix (``n`` blocks, empty
        tail) — the prefill those blocks would have required is skipped.

        ``ids`` int32 ``[max_blocks]`` (entries past ``n`` ignored),
        ``slot``/``n`` scalar int32.  Paged pools only.  Pure data motion
        at static shapes: admitting a hit of any length reuses one trace.
        """
        if not self.paged:
            raise ValueError("assign_blocks is a paged-pool transition")
        sb = self.max_blocks
        live = jnp.arange(sb) < n
        if self.checkify:
            self._check(jnp.all((jnp.asarray(n) >= 0)
                                & (jnp.asarray(n) <= sb)),
                        "assign_blocks: n out of range")
            self._check(jnp.all(jnp.where(live,
                                          (ids >= 0) & (ids < self.n_phys),
                                          True)),
                        "assign_blocks: block id out of arena range")
        row = jnp.where(live, jnp.clip(ids, 0, self.n_phys - 1), 0)
        table = jax.lax.dynamic_update_slice(
            state["table"], row[None].astype(jnp.int32), (slot, 0))
        rc_ids = jnp.where(live, ids, self.n_phys)
        n = jnp.asarray(n, jnp.int32)
        return {**state,
                "table": table,
                "refcount": state["refcount"].at[rc_ids].add(1, mode="drop"),
                "pos": state["pos"].at[slot].set(n * self.bs),
                "prefix_blocks": state["prefix_blocks"].at[slot].set(n),
                "tail_len": state["tail_len"].at[slot].set(0)}

    def append_many(self, state: Dict[str, Any],
                    panels: Dict[str, Any], n: jax.Array) -> Dict[str, Any]:
        """Append up to ``m`` fresh K/V tokens per slot into every layer's
        dense tail ring at the slot's own ``tail_len`` offset.

        ``panels``: ``{layer: {"k": [P, B, Hkv, m, D], "v": ...}}``;
        ``n`` int32 scalar or ``[B]`` — valid panel tokens per slot
        (``<= m``; 0 = passthrough).  Advances ``pos``/``tail_len`` by
        ``n``.  Pool-level twin of the verify step's in-layer append:
        the engine's verify forward writes each layer inside its scan
        (``models.attention.pooled_attn_panel``) through the SAME
        :func:`~repro.core.sparse_kv.append_tail_panel` core this method
        uses — change the write semantics there, not here.  This entry
        appends across all layers at once for direct pool callers and the
        rollback/refreeze property tests.  Pure masked writes at static
        shapes — jits once per panel width.
        """
        n = jnp.broadcast_to(jnp.asarray(n, jnp.int32), (self.slots,))
        tl = state["tail_len"]
        new_layers = {}
        for name, leaf in state["layers"].items():
            kv, src = leaf["kv"], panels[name]
            write = jax.vmap(append_tail_panel, in_axes=(0, 0, None, None))
            new_layers[name] = {"kv": {
                **kv,
                "k_tail": write(kv["k_tail"], src["k"], tl, n),
                "v_tail": write(kv["v_tail"], src["v"], tl, n),
            }}
        return {**state, "layers": new_layers,
                "pos": state["pos"] + n, "tail_len": tl + n}

    def rollback(self, state: Dict[str, Any], n: jax.Array
                 ) -> Dict[str, Any]:
        """Un-append the last ``n`` tokens per slot: a pure masked length
        decrement (``pos``/``tail_len``), no storage touched — validity is
        length-gated everywhere, so decremented entries are dead.

        ``n`` int32 scalar or ``[B]``, clamped to ``tail_len`` — a
        rollback can only surrender tail tokens; it never crosses the
        frozen-prefix boundary (refrozen tokens are committed by
        construction: the engine rolls back *within* the tick that
        appended, before any refreeze can fold the tail).  This is what
        makes draft–verify speculation free on this cache: rejected
        drafts cost one subtraction, not a retrace or a re-pack.
        """
        n = jnp.broadcast_to(jnp.asarray(n, jnp.int32), (self.slots,))
        n = jnp.clip(n, 0, state["tail_len"])
        return {**state, "pos": state["pos"] - n,
                "tail_len": state["tail_len"] - n}

    def release(self, state: Dict[str, Any], slot: jax.Array
                ) -> Dict[str, Any]:
        """Recycle one or many slots: zero their lengths.  Stale
        prefix/tail contents stay in storage but are fully masked
        (validity is length-gated everywhere), so the next admission
        simply overwrites them.

        ``slot`` is a scalar or an int32 ``[R]`` vector (batched release —
        one jitted call recycles every slot a tick finished; pad with
        ``-1``, which matches nothing).  Paged pool: every released slot's
        live table entries decrement their blocks' refcounts (shared
        blocks scatter-add correctly when several released slots point at
        the same page) and its table row resets to 0 — the HOST allocator
        decides what a refcount-0 page becomes (cached for re-hit, or
        free).

        **Idempotent**: releasing an already-free slot is a no-op, not a
        refcount underflow — its lengths are already 0, so the paged decref
        mask (gated on ``prefix_blocks``) selects nothing and zeroing the
        lengths again changes nothing.  Even the sanitized-mode underflow
        check passes (it screens only live table entries).  The engine
        counts double releases as warnings (``fault_counters``); the
        device transition absorbs them — a crashing request-teardown path
        can retry safely.
        """
        slot = jnp.atleast_1d(jnp.asarray(slot, jnp.int32))     # [R]
        rel = jnp.any(slot[:, None] == jnp.arange(self.slots)[None, :],
                      axis=0)                                   # [B]
        z = lambda a: jnp.where(rel, 0, a)
        out = {**state, "pos": z(state["pos"]),
               "prefix_blocks": z(state["prefix_blocks"]),
               "tail_len": z(state["tail_len"])}
        if self.paged:
            live = rel[:, None] & (jnp.arange(self.max_blocks)[None, :]
                                   < state["prefix_blocks"][:, None])
            if self.checkify:
                rc = jnp.take(state["refcount"], state["table"],
                              mode="clip")
                self._check(jnp.all(jnp.where(live, rc > 0, True)),
                            "release: refcount underflow (device double "
                            "free)")
            ids = jnp.where(live, state["table"],
                            self.n_phys).reshape(-1)
            out["refcount"] = state["refcount"].at[ids].add(-1, mode="drop")
            out["table"] = jnp.where(rel[:, None], 0, state["table"])
        return out

    # -- snapshot (paged arena <-> host trees) -------------------------------
    def arena_leaves(self, state: Dict[str, Any]) -> Dict[str, Any]:
        """The shared-arena storage of a paged pool as a host tree
        ``{layer: {leaf: np.ndarray}}`` — exactly the leaves a warm-restart
        snapshot must persist (per-slot tails / tables / occupancy are
        in-flight request state and deliberately excluded: after a crash
        there are no in-flight requests, only shareable frozen content).
        """
        if not self.paged:
            raise ValueError("arena_leaves is a paged-pool helper")
        # host snapshot boundary, never traced
        return {name: {k: np.asarray(leaf["kv"][k])  # jitlint: disable=host-sync
                       for k in ARENA_KEYS}
                for name, leaf in state["layers"].items()}

    def load_arena(self, state: Dict[str, Any],
                   leaves: Dict[str, Any]) -> Dict[str, Any]:
        """Inverse of :meth:`arena_leaves`: a fresh state with the arena
        storage replaced by ``leaves`` (shape/dtype-checked per leaf, with
        the failing leaf named — restore must never half-apply)."""
        if not self.paged:
            raise ValueError("load_arena is a paged-pool helper")
        new_layers = {}
        for name, leaf in state["layers"].items():
            kv = dict(leaf["kv"])
            for k in ARENA_KEYS:
                # host restore boundary, never traced
                have, got = kv[k], np.asarray(leaves[name][k])  # jitlint: disable=host-sync
                if have.shape != got.shape or have.dtype != got.dtype:
                    raise ValueError(
                        f"arena leaf {name}/{k}: pool expects "
                        f"{have.shape} {have.dtype}, snapshot carries "
                        f"{got.shape} {got.dtype}")
                kv[k] = jnp.asarray(got)
            new_layers[name] = {"kv": kv}
        return {**state, "layers": new_layers}


# the paged arena's stored layout, part of a snapshot's geometry: a block
# is a [rows, 128] tile, the rows a multiple of 8, under [P, n_phys, Hkv]
ARENA_LAYOUT = "period-stacked-tiles-8"


# errors screened by the sanitized mode: the pool's own checkify.check
# invariants plus NaN and div-by-zero.  Built-in index OOB checks are
# deliberately NOT enabled — the pool's ``mode="drop"`` scatters use
# id == n_phys as an intentional out-of-range sentinel, which the generic
# OOB screen cannot distinguish from a bug; OOB discipline is covered by
# the explicit sentinel-aware checks above instead.
POOL_CHECKS = (_checkify.user_checks | _checkify.nan_checks
               | _checkify.div_checks)


def checkified_raw(fn: Callable) -> Callable:
    """The jit-composable half of :func:`checkified`: returns the
    functionalized transition ``(err, out) = fn'(*args)`` without the
    host-side throw (the engine jits this and throws at its own sync
    boundary)."""
    return _checkify.checkify(fn, errors=POOL_CHECKS)


def checkified(fn: Callable) -> Callable:
    """Functionalize a pool transition for the sanitized mode.

    ``CachePool(checkify=True)`` (or env ``REPRO_CHECKIFY=1``) plants
    ``checkify.check`` invariants in the transitions; those raise eagerly
    but cannot be traced by a plain ``jax.jit``.  This wrapper runs the
    transition under :func:`jax.experimental.checkify.checkify` and throws
    the first accumulated error on the host — usable under jit.
    """
    checked = _checkify.checkify(fn, errors=POOL_CHECKS)

    def run(*args, **kwargs):
        err, out = checked(*args, **kwargs)
        err.throw()
        return out
    return run


class BlockAllocator:
    """Host-side physical-block lifecycle for the paged pool.

    The device transitions above are pure data motion; THIS object decides
    which ids they move.  Three populations partition ``[0, n_phys)``:

    * **free** — never used or fully reclaimed; a LIFO stack.
    * **live** — refcount > 0: referenced by at least one slot's table row.
    * **cached** — refcount == 0 but still holding a registered
      (content-hashed) block; kept in an LRU so a future prompt sharing
      the prefix can revive it for free.  ``alloc`` evicts from the LRU's
      cold end only when the free stack runs dry, invalidating the hash
      through ``on_evict`` (the engine points that at its prefix index).

    The allocator mirrors refcounts so admission can reason about
    availability without a device sync; the device ``refcount`` vector
    carries the same counts for on-device masking and the property tests.
    """

    def __init__(self, n_phys: int,
                 on_evict: Optional[Callable[[int], None]] = None):
        self.n_phys = n_phys
        self.on_evict = on_evict
        self.evictions = 0               # lifetime LRU evictions (telemetry)
        self._free: List[int] = list(range(n_phys - 1, -1, -1))
        self._ref = np.zeros(n_phys, np.int64)
        self._cached: "OrderedDict[int, int]" = OrderedDict()  # id -> hash
        self._hash2id: Dict[int, int] = {}

    # -- queries -------------------------------------------------------------
    def free_blocks(self) -> int:
        """Blocks an ``alloc`` could hand out right now (free + evictable)."""
        return len(self._free) + len(self._cached)

    def refcount(self, bid: int) -> int:
        # host numpy bookkeeping array, not a device value
        return int(self._ref[bid])  # jitlint: disable=host-sync

    def lookup(self, h: int) -> Optional[int]:
        """Physical id of the block registered under chained hash ``h``."""
        return self._hash2id.get(h)

    # -- lifecycle -------------------------------------------------------------
    def alloc(self, n: int) -> List[int]:
        """Hand out ``n`` fresh ids at refcount 1, evicting the LRU's cold
        end when the free stack runs dry.  The engine's admission
        reservation guarantees this never runs out — treat failure as a
        bookkeeping bug, not backpressure."""
        ids = []
        for _ in range(n):
            if self._free:
                bid = self._free.pop()
            else:
                if not self._cached:
                    raise RuntimeError(
                        "BlockAllocator exhausted: admission reservations "
                        "must cover every alloc")
                bid, h = self._cached.popitem(last=False)      # LRU evict
                del self._hash2id[h]
                self.evictions += 1
                if self.on_evict is not None:
                    self.on_evict(h)
            self._ref[bid] = 1
            ids.append(bid)
        return ids

    def register(self, bid: int, h: int) -> bool:
        """Associate a live block with its chained content hash so future
        prompts can share it.  First writer wins (a concurrent duplicate
        simply stays private); returns whether the hash was recorded."""
        if h in self._hash2id:
            return False
        self._hash2id[h] = bid
        return True

    def hash_of(self, bid: int) -> Optional[int]:
        for h, i in self._hash2id.items():
            if i == bid:
                return h
        return None

    def incref(self, ids: Sequence[int]) -> None:
        """Take shared references (a prefix-cache hit); revives cached
        refcount-0 blocks out of the eviction LRU."""
        for bid in ids:
            if self._ref[bid] == 0:
                self._cached.pop(bid, None)
            self._ref[bid] += 1

    # -- snapshot -------------------------------------------------------------
    def export_registered(self) -> List[tuple]:
        """``(hash, id)`` pairs of every registered (content-hashed) block,
        coldest first — the persistent half of the allocator's state.

        Ordering is the restore-side LRU order: cached refcount-0 blocks in
        their eviction order (cold end first), then live blocks (hottest —
        they were in active use at snapshot time).  Unregistered live
        blocks (private pages of in-flight requests) are deliberately
        absent: after a restart there are no in-flight requests, and an
        unhashed page can never be revived by a prefix hit.
        """
        pairs = list((h, bid) for bid, h in self._cached.items())
        pairs.extend((h, bid) for h, bid in self._hash2id.items()
                     if self._ref[bid] > 0)
        return pairs

    def restore_registered(self, pairs: Sequence[tuple]) -> None:
        """Reset the allocator to a freshly-restarted warm state: every
        ``(hash, id)`` pair becomes a cached refcount-0 block (revivable by
        a prefix hit, evictable from the cold end), everything else is
        free.  Must be called before any allocation; raises ``ValueError``
        on ids out of range or duplicated (a corrupt snapshot must not
        half-apply)."""
        seen = set()
        for h, bid in pairs:
            if not 0 <= bid < self.n_phys:
                raise ValueError(
                    f"snapshot block id {bid} outside arena "
                    f"[0, {self.n_phys})")
            if bid in seen:
                raise ValueError(f"snapshot block id {bid} duplicated")
            seen.add(bid)
        self._ref = np.zeros(self.n_phys, np.int64)
        self._free = [i for i in range(self.n_phys - 1, -1, -1)
                      if i not in seen]
        self._cached = OrderedDict((bid, h) for h, bid in pairs)
        self._hash2id = {h: bid for h, bid in pairs}

    def decref(self, ids: Sequence[int]) -> None:
        """Drop references (slot release).  A block hitting refcount 0
        parks in the LRU if its content hash is registered (revivable),
        else returns to the free stack."""
        for bid in ids:
            if not self._ref[bid] > 0:
                raise RuntimeError(f"double free of block {bid}")
            self._ref[bid] -= 1
            if self._ref[bid] == 0:
                h = next((hh for hh, ii in self._hash2id.items()
                          if ii == bid), None)
                if h is None:
                    self._free.append(bid)
                else:
                    self._cached[bid] = h
                    self._cached.move_to_end(bid)
