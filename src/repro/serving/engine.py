"""Serving engines over the paper's §6.2 compressed-KV design.

Two engines share the kernels but differ in how they treat traffic:

* :class:`Engine` — the legacy **one-shot** engine: one static batch,
  prefill -> freeze -> decode.  Refreezing grows the cache shapes, so each
  refreeze re-traces the jitted decode.  Kept as the numerical baseline
  and for single-batch benchmarking.

* :class:`ContinuousEngine` — the **continuous-batching** engine: requests
  stream through a :class:`~repro.serving.cache_pool.CachePool` of
  fixed-geometry slots under a :class:`~repro.serving.scheduler.Scheduler`.
  Chunked prefill interleaves with decode ticks, slots recycle on EOS, and
  every jitted step — decode over ``(params, pool_state, tokens,
  slot_mask)``, per-chunk-length prefill, refreeze, release, lane set —
  compiles exactly once.  This is the paper's "cache frozen in model
  state" design made multi-tenant: refreeze folds tails into the prefix
  *in place* at static shapes instead of reallocating.

Both engines speak the request-level API of :mod:`repro.serving.sampling`:
callers pass :class:`SamplingParams` and get tokens / RequestOutputs back.
The model's decode steps return **logits**; token selection is the
sampler's job (per-slot on-device lanes in the continuous engine, one
broadcast lane in the legacy engine) — argmax is just the
``temperature=0`` lane of that sampler.
"""
from __future__ import annotations

import functools
from typing import (Any, Callable, Dict, Iterator, List, Optional, Tuple)

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core.sparse_kv import SparseKVCache, freeze_prefix
from repro.distributed import NULL_CTX, serving_sharding
from repro.kernels import ops
from repro.models import lm
from repro.models.attention import DenseKVCache
from repro.models.layers import pad_output_head
from repro.obs import spans

from . import sampling
from .cache_pool import BlockAllocator, CachePool
from .cache_pool import checkified_raw as cache_pool_checkified_raw
from .faults import (CANCEL_PREFILL, CANCEL_SPEC, DOUBLE_RELEASE,
                     DRAFTER_ERROR, PAGE_EXHAUSTION, FaultPlan)
from .sampling import RequestOutput, SamplingParams
from .scheduler import PrefixTrie, Scheduler, block_hashes
from .spec import AdaptiveDraft, SpecConfig


def retrace_count(jitted) -> int:
    """Number of traces a ``jax.jit``-wrapped callable has accumulated.

    The continuous engine's invariant is that this stays flat after warmup
    (one trace per shape family); tests assert it directly.
    """
    return int(jitted._cache_size())


def stable_trace_counts(counts: Dict[str, int],
                        ignore: tuple = ("prefill_chunk",)) -> Dict[str, int]:
    """The subset of :meth:`ContinuousEngine.trace_counts` that must stay
    FLAT after warmup.

    ``prefill_chunk`` legitimately accumulates one trace per distinct
    chunk length (a new prompt length is a new shape family, not a
    retrace), so zero-retrace assertions compare the rest.  One shared
    utility — the engine benchmarks and the serving/spec/sharding test
    suites all filter through here instead of re-implementing the drop.
    """
    return {k: v for k, v in counts.items() if k not in ignore}


class Engine:
    def __init__(self, params, cfg, ctx=NULL_CTX, kv_mode: str = "sparse"):
        self.params = params
        self.cfg = cfg
        self.ctx = ctx
        self.kv_mode = kv_mode
        self._decode = jax.jit(
            lambda p, c, t: lm.forward_decode(p, c, t, cfg, ctx))
        self._prefill = jax.jit(
            lambda p, b: lm.forward_prefill(p, b, cfg, ctx))
        self._sample = jax.jit(sampling.sample_step)

    # ------------------------------------------------------------------
    def prefill(self, batch: Dict[str, jax.Array]):
        cfg = self.cfg
        hidden, collected = self._prefill(self.params, batch)
        p = lm.period_len(cfg)
        kinds = [lm.layer_kind(cfg, j) for j in range(p)]
        layers: Dict[str, Any] = {}
        for j, kind in enumerate(kinds):
            got = collected["layers"][f"l{j}"]
            if kind[0] == "attn":
                layers[f"l{j}"] = {"kv": self._build_kv(got["k"], got["v"])}
            else:
                layers[f"l{j}"] = {"state": got["state"]}
        cache = {"pos": jnp.asarray(collected["len"], jnp.int32),
                 "layers": layers}
        if cfg.family == "encdec":
            cross = collected["cross"]["l0"]
            cache["cross"] = {"k": cross["k"], "v": cross["v"]}
        logits = lm.logits_fn(self.params, hidden[:, -1:], cfg, self.ctx)
        return cache, logits[:, 0]

    def _build_kv(self, k_stack, v_stack):
        """k/v [P, B, Hkv, S, hd] -> per-period cache, host-packed.

        Pass 1 finds the max per-block nnz across layers (global magnitude
        pruning gives ragged block occupancy); pass 2 packs every layer at
        that common capacity so the stacked cache has static shapes — the
        stacked analogue of the paper's fixed offline capacity."""
        cfg = self.cfg
        n_periods = k_stack.shape[0]
        per = []
        cap_k = cap_v = None
        if self.kv_mode == "sparse" and n_periods > 1:
            probes = [freeze_prefix(
                k_stack[i], v_stack[i], cfg.kv_k_sparsity,
                cfg.kv_v_sparsity, tail_size=cfg.kv_tail,
                bs=min(128, k_stack.shape[3])) for i in range(n_periods)]
            cap_k = max(p.k_sp.capacity for p in probes)
            cap_v = max(p.v_sp.capacity for p in probes)
        for i in range(n_periods):
            k, v = k_stack[i], v_stack[i]
            s = k.shape[2]
            if self.kv_mode == "sparse":
                bs = min(128, s)
                per.append(freeze_prefix(
                    k, v, cfg.kv_k_sparsity, cfg.kv_v_sparsity,
                    tail_size=cfg.kv_tail, bs=bs,
                    capacity_k=cap_k, capacity_v=cap_v))
            else:
                pad = cfg.kv_tail
                kp = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
                vp = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
                per.append(DenseKVCache(kp, vp,
                                        jnp.asarray(s, jnp.int32)))
        return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *per)

    # ------------------------------------------------------------------
    def generate(self, batch: Dict[str, jax.Array],
                 params: Optional[SamplingParams] = None):
        """Decode ``params.max_new_tokens`` tokens for the whole batch.

        Every row shares ``params`` (a static batch is one lockstep wave,
        not a request stream — per-request params, eos/stop handling and
        streaming live on :class:`ContinuousEngine`; eos/stop params are
        rejected here rather than silently decoded past).  The decode step
        returns logits; token selection happens in the shared jitted
        sampler, so ``temperature=0`` is exactly the old greedy path.
        Returns ``([B, max_new_tokens] int32 tokens, final cache)`` — the
        first token is sampled from the prompt's last logits.
        """
        params = params if params is not None else SamplingParams()
        if params.eos_id is not None or params.stop_ids:
            raise ValueError(
                "the one-shot Engine decodes fixed-length lockstep batches "
                "and cannot honor eos_id/stop_ids; submit to "
                "ContinuousEngine for per-request stop handling")
        cache, logits = self.prefill(batch)
        b = batch["tokens"].shape[0]
        lanes = sampling.broadcast_lanes(params, b)
        live = jnp.ones((b,), bool)
        toks = []
        tok, _, lanes = self._sample(logits, lanes, live)
        for i in range(params.max_new_tokens - 1):
            toks.append(tok)
            if self.kv_mode == "sparse":
                cache = self._maybe_refreeze(cache)
            logits, cache = self._decode(self.params, cache, tok[:, None])
            tok, _, lanes = self._sample(logits, lanes, live)
        toks.append(tok)
        return jnp.stack(toks, axis=1), cache

    # ------------------------------------------------------------------
    def _maybe_refreeze(self, cache):
        """Fold full tails back into the compressed prefix (paper §6.2's
        amortized step).  Host-side, between jitted decode steps; note the
        prefix growth changes cache shapes -> one re-trace per refreeze."""
        from repro.core.sparse_kv import refreeze
        cfg = self.cfg
        layers = dict(cache["layers"])
        changed = False
        for name, leaf in layers.items():
            if "kv" not in leaf:
                continue
            kv = leaf["kv"]
            t = kv.k_tail.shape[3]          # stacked [P, B, Hkv, T, D]
            if int(kv.tail_len[0]) < t:
                continue
            n_periods = kv.k_tail.shape[0]
            per = [refreeze(jax.tree_util.tree_map(lambda a: a[i], kv),
                            cfg.kv_k_sparsity, cfg.kv_v_sparsity)
                   for i in range(n_periods)]
            cap_k = max(p.k_sp.capacity for p in per)
            cap_v = max(p.v_sp.capacity for p in per)
            if any(p.k_sp.capacity != cap_k or p.v_sp.capacity != cap_v
                   for p in per):
                # re-pack at a common capacity so the stack is rectangular
                per = [self._repack(p, cap_k, cap_v) for p in per]
            layers[name] = {**leaf, "kv": jax.tree_util.tree_map(
                lambda *xs: jnp.stack(xs), *per)}
            changed = True
        if not changed:
            return cache
        return {**cache, "layers": layers}

    def _repack(self, kvc, cap_k, cap_v):
        """Re-store one period's cache at the stack-wide common capacity.

        Uses :func:`repack_capacity`, which keeps bitmap and values
        consistent in both directions (the old grow-only pad left the
        bitmap claiming truncated values when capacities shrank)."""
        from repro.core.sparse_format import repack_capacity
        return SparseKVCache(repack_capacity(kvc.k_sp, cap_k),
                             repack_capacity(kvc.v_sp, cap_v),
                             kvc.k_tail, kvc.v_tail, kvc.tail_len)


# ---------------------------------------------------------------------------
# continuous batching
# ---------------------------------------------------------------------------

class ContinuousEngine:
    """Continuous-batching serving engine on the pooled sparse-KV cache.

    One engine tick (:meth:`step`):

    1. **refreeze** — any decoding slot whose tail ring is full gets its
       tail pruned + folded into its compressed prefix, in place;
    2. **admission / chunked prefill** — admitted requests get their
       sampling lane (temperature / top-k / top-p / seeded RNG key)
       written into device state; the oldest request owed prompt work gets
       one chunk processed against its slot's frozen prefix, and finishing
       the prompt samples the request's first token;
    3. **decode** — every decoding slot advances one token in a single
       batched step jitted over ``(params, pool_state, tokens, slot_mask)``
       — the model returns per-slot logits and the on-device sampler draws
       each slot's token under its own lane, splitting the ``[slots, 2]``
       RNG lane in place.

    Decode, speculative verify and spec-off ticks are all the SAME
    canonical **panel forward** (:func:`repro.models.lm.forward_panel_pooled`
    at static width ``Q``): decode is the ``Q == 1`` panel (squeezed onto
    the single-query fused dispatch for bit-identical greedy output),
    verify the ``Q == K+1`` panel — one scan body, one per-layer fused
    attention kernel, one shape family per panel width.

    With ``mesh=`` the WHOLE serving state is mesh-sharded — slots over
    the data axes, KV heads over the model axis
    (``repro.distributed.serving_sharding``) — and every jitted step is
    pinned with ``in_shardings``/``out_shardings`` so state never moves
    between ticks.  The scheduler is untouched (slot placement is a device
    concern, not a request concern); non-dividing dims fall back to
    replication, and a 1-device mesh is exactly the unsharded engine.

    All device work reuses five compiled functions (decode / refreeze /
    release / set_lane, plus one prefill per distinct chunk length);
    admissions, evictions, refreezes and *heterogeneous sampling params*
    never retrace — see :func:`retrace_count`.  Host<->device traffic per
    tick is one token vector plus one chosen-token logprob vector (surfaced
    on :attr:`RequestOutput.logprobs`); slot lengths are mirrored
    host-side.  Per layer, the decode tick's attention is ONE fused
    prefix+tail flash-decode kernel — the XLA-side tail attention + lse
    merge the two-pass design paid per token is gone.

    With ``spec=SpecConfig(k>0)`` the decode tick becomes a **draft–verify
    step**: a model-free n-gram drafter proposes up to ``k`` continuation
    tokens per slot from the request's own history, and one jitted verify
    forward scores all ``k+1`` positions against the pooled cache (a query
    panel through the same fused kernel), accepts per lane (greedy: exact
    match — token-identical to this engine with spec off; sampled:
    rejection sampling — distribution unchanged) and rolls rejected drafts
    back by a pure length decrement.  The verify step compiles once per
    (pool geometry, k); accept lengths 0..k never retrace.
    ``spec_hist[a]`` counts ticks that committed ``a`` accepted drafts.

    With ``paged=True`` the pool stores compressed blocks ONCE in a shared
    physical arena of ``phys_blocks`` pages indexed through per-slot block
    tables.  Admission content-addresses each prompt's block-aligned
    prefix against a host prefix index (:class:`~.scheduler.PrefixTrie` of
    chained block hashes): a hit points the new slot's table row at the
    already-frozen pages (refcount++) and SKIPS their prefill entirely —
    N requests sharing a system prompt pay its prefill and its arena bytes
    once.  Frozen pages are immutable; prefill/refreeze always append
    fresh pages past the shared prefix (copy-on-write at the divergence
    block), and releases decref — a refcount-0 page parks in the
    allocator's LRU, revivable by a future hit until evicted for reuse.
    Admission reserves each request's worst-case page demand up front, so
    device-side allocation can never fail mid-flight.  The table and
    refcount are data: decode still never retraces.

    **Fault-tolerant lifecycle** (all host-side control flow — the jitted
    transitions and their compile manifest are untouched): per-request
    deadlines (``SamplingParams.deadline_s`` / ``ttft_deadline_s``)
    enforced at tick start, :meth:`cancel` for any live request, bounded
    admission with load shedding (``max_queue``; rejected requests finish
    ``"shed"`` at submit time), exponential-backoff requeue when paged
    admission can't reserve pages, and an optional degraded mode
    (``degrade_queue``) that drops speculative drafting to zero under
    queue pressure.  ``fault_counters`` tallies every abnormal event.  A
    seeded :class:`~repro.serving.faults.FaultPlan` (``faults=``) injects
    failures at the named host sites for the fault-injection harness.
    :meth:`save_snapshot` / :meth:`load_snapshot` persist the paged
    arena + prefix index for crash-safe warm restarts.

    **Overlapped (double-buffered) ticks** (``overlap=True``): the tick
    loop is pipelined — tick *t+1*'s device work is dispatched BEFORE
    tick *t*'s tokens are synced, so host scheduler work (admission,
    stop scanning, callbacks, releases) hides behind the in-flight
    device step instead of serializing with it.  On the plain decode
    path the input token chains **on device**: :attr:`_decode_chain`
    consumes the previous tick's un-synced token vector (a jax async
    value) and host-overrides only the lanes where the chain breaks (a
    slot fresh out of prefill, re-admitted, or the first tick after an
    idle pipeline).  Under speculation the in-flight verify window is
    committed after the next tick's admission/prefill dispatch but
    before drafting (the n-gram drafter needs the committed history and
    the paged refreeze scatter needs exact tail mirrors).  Either way
    there is exactly ONE sync site — :meth:`_sync_inflight`, the
    registry-designated ``jax.block_until_ready`` — and commit re-checks
    ``(slot, rid)`` liveness, so a request that expired, was cancelled,
    or finished while its window was in flight never has speculatively
    dispatched tokens committed.  Greedy *and* seeded-sampled output is
    token-identical to ``overlap=False`` (the oracle): each request's
    RNG stream is a pure function of its sampled-token count, and
    discarded speculative draws happen strictly after the request's
    last committed draw.  :meth:`quiesce` drains the pipeline (snapshot
    paths call it implicitly).

    **Donated state** (``donate``; default: on unless the backend is the
    CPU): every jitted program takes the pool state as a donated argument
    and returns its successor in the same buffers, so the pool is held
    once and a state handed to a program is deleted by it — the engine
    only ever passes ``self.state`` and replaces it with the result.  An
    untied output head whose width is not a whole number of 128-wide tiles
    is padded once at build (:func:`~repro.models.layers.pad_output_head`);
    the logits are sliced back to the vocabulary before sampling.

    **Program spans and names**: each tick opens the
    ``jax.profiler.TraceAnnotation`` spans of :mod:`repro.obs.spans`
    (``repro.tick`` holding ``repro.admit``, ``repro.refreeze``,
    ``repro.prefill``, ``repro.decode``, ``repro.sync``,
    ``repro.release``), recorded only under a profiler trace, on the
    clock of the device's operations; every jitted step is a named
    function, so its XLA program is ``jit_<name>`` (``jit__refreeze``,
    ``jit__decode_chain``) on one device, under a mesh and under checkify
    alike.
    """

    def __init__(self, params, cfg, ctx=NULL_CTX, slots: int = 4,
                 max_tokens: int = 0, bs: int = 0,
                 prefill_chunk: Optional[int] = None,
                 spec: Optional[SpecConfig] = None,
                 capacity_slack: float = 1.25,
                 mesh=None, paged: bool = False, phys_blocks: int = 0,
                 checkify: Optional[bool] = None,
                 max_queue: int = 0, degrade_queue: int = 0,
                 faults: Optional[FaultPlan] = None, clock=None,
                 obs=None, overlap: bool = False,
                 donate: Optional[bool] = None):
        if mesh is not None:
            # mesh-sharded serving: slots over the data axes, KV heads over
            # the model axis.  The ctx also constrains activations inside
            # the forwards so the residual stream follows the state.
            if ctx is not NULL_CTX:
                raise ValueError(
                    "pass either ctx= or mesh=, not both: mesh= derives "
                    "its own serving ShardCtx (slots over data, KV heads "
                    "over model)")
            ctx = serving_sharding.serving_ctx(mesh, cfg)
        self.cfg = cfg
        self.ctx = ctx
        self.mesh = mesh
        # an untied head of unaligned width is padded once, here, instead
        # of by the dense kernel in every step
        params = pad_output_head(params, cfg)
        # every program takes the pool state and returns its successor:
        # donated, the state is updated in place and the pool is held once.
        # The CPU backend keeps its inputs unless asked: its memory is the
        # host's, and CPU tests and planted faults read a state after the
        # step it fed
        self.donate = (jax.default_backend() != "cpu" if donate is None
                       else bool(donate))
        max_tokens = max_tokens or 4 * cfg.kv_tail
        if not bs:
            # largest tail divisor <= min(128, prefill_chunk): chunks stay
            # block-aligned and the tail folds in whole blocks
            limit = min(128, prefill_chunk or 128, cfg.kv_tail)
            bs = next(d for d in range(limit, 0, -1)
                      if cfg.kv_tail % d == 0)
        self.pool = CachePool.build(cfg, slots, max_tokens, bs=bs,
                                    capacity_slack=capacity_slack,
                                    paged=paged, n_phys=phys_blocks,
                                    checkify=checkify)
        if mesh is not None and self.pool.checkify:
            raise ValueError("checkify mode is unsharded-only: the "
                             "functionalized error output has no mesh "
                             "placement")
        # pool storage + per-slot sampling lanes travel as one state pytree
        # through every jitted transition (the pool ops pass unknown keys
        # through untouched)
        self.state = {**self.pool.init_state(),
                      "sample": sampling.init_lanes(slots)}
        sch_kw = {} if clock is None else {"clock": clock}
        self.scheduler = Scheduler(slots, self.pool.capacity_tokens,
                                   self.pool.bs, chunk=prefill_chunk,
                                   max_queue=max_queue, **sch_kw)
        bs_ = self.pool.bs

        # mesh placement: every jitted step below is pinned with explicit
        # in_shardings/out_shardings so (a) the state NEVER leaves its
        # placement between ticks and (b) host-fed operands (token panels,
        # masks, lane params) land directly on their shards.  Weights are
        # replicated (serving decode streams the cache, not the weights);
        # all placements degrade to replication when a dim doesn't divide
        # its mesh axis, so a 1-device mesh IS the unsharded engine.
        self.state_axes = {**self.pool.state_axes(),
                           "sample": sampling.lane_axes()}
        if mesh is not None:
            st_sh = serving_sharding.state_shardings(ctx, self.state,
                                                     self.state_axes)
            tok_sh = serving_sharding.token_sharding(ctx, slots)
            vec_sh = serving_sharding.vec_sharding(ctx, slots)
            rep = serving_sharding.replicated(ctx)
            par_sh = jax.tree_util.tree_map(lambda _: rep, params)
            params = jax.device_put(params, par_sh)
            self.state = jax.device_put(self.state, st_sh)

            def _jit(fn, in_s, out_s, donate):
                # the Pallas kernels cannot be partitioned by XLA: they
                # trace as shard_maps that keep the serving placement
                # (kernels/ops.py).  wraps keeps fn's name, so the
                # program compiles as jit_<fn name>, not jit_on_mesh
                @functools.wraps(fn)
                def on_mesh(*args):
                    with ops.kernel_mesh(ctx):
                        return fn(*args)
                return jax.jit(on_mesh, in_shardings=in_s,
                               out_shardings=out_s, donate_argnums=donate)
        elif self.pool.checkify:
            # sanitized mode: the pool transitions plant checkify.check
            # invariants, which a plain jit cannot trace — functionalize
            # each step and throw the accumulated error at the host
            # boundary.  trace_counts() keeps working through the
            # forwarded _cache_size.
            st_sh = tok_sh = vec_sh = rep = par_sh = None

            def _jit(fn, in_s, out_s, donate):
                checked = jax.jit(
                    functools.wraps(fn)(cache_pool_checkified_raw(fn)),
                    donate_argnums=donate)

                @functools.wraps(fn)
                def run(*args):
                    err, out = checked(*args)
                    err.throw()
                    return out
                run._cache_size = checked._cache_size
                run.lower = checked.lower
                return run
        else:
            st_sh = tok_sh = vec_sh = rep = par_sh = None

            def _jit(fn, in_s, out_s, donate):
                return jax.jit(fn, donate_argnums=donate)
        self.params = params
        # the position of the state among each program's arguments
        st_at = lambda i: (i,) if self.donate else ()  # noqa: E731

        # sampling stays on device: only [slots]-sized token + logprob
        # vectors cross the host boundary each tick, never [slots, vocab]
        # logits.  A decode tick is the Q == 1 instance of the SAME panel
        # forward the speculative verify step uses (lm.forward_panel_pooled
        # — the per-layer attention is one fused prefix+tail kernel), so
        # decode and verify share one scan body and differ only in their
        # static panel width.
        def _decode(p, st, t, m):
            logits, st = lm.forward_panel_pooled(p, st, t, m, cfg, ctx, bs_)
            tok, logp, lanes = sampling.sample_step(
                logits[:, 0], st["sample"], m)
            return tok, logp, {**st, "sample": lanes}

        def _prefill(p, st, t, s, final, ids=None):
            logits, st = lm.forward_prefill_chunk(
                p, st, t, s, cfg, ctx, bs_, new_ids=ids,
                caps=(self.pool.cap_k, self.pool.cap_v))
            lanes = st["sample"]
            lane = {k: jax.lax.dynamic_slice_in_dim(v, s, 1, axis=0)
                    for k, v in lanes.items()}
            # the key advances only when the chunk is final (= a token is
            # actually sampled), keeping the request's RNG stream a pure
            # function of its sampled-token count
            tok, logp, lane = sampling.sample_step(
                logits, lane, jnp.reshape(final, (1,)))
            lanes = {**lanes, "rng": jax.lax.dynamic_update_slice_in_dim(
                lanes["rng"], lane["rng"], s, axis=0)}
            return tok, logp, {**st, "sample": lanes}

        # every jitted step is a named function: XLA names its program
        # jit_<function name> (jit__decode_chain, jit__refreeze, ...), which
        # is how a profiler trace tells the programs apart
        def _refreeze(st, ids=None):
            return self.pool.refreeze(st, new_ids=ids)

        def _assign(st, s, ids, n):
            return self.pool.assign_blocks(st, s, ids, n)

        # a fresh function object, NOT sampling.set_lane itself: pjit's
        # fastpath cache is keyed on the function, so jitting the shared
        # module function would let other engines' pool geometries count
        # against this engine's trace_counts()
        def _set_lane(st, slot, t, k, p, key):
            return sampling.set_lane(st, slot, t, k, p, key)

        self._decode = _jit(_decode, (par_sh, st_sh, tok_sh, vec_sh),
                            (vec_sh, vec_sh, st_sh), st_at(1))
        if paged:
            self._prefill_chunk = _jit(
                _prefill, (par_sh, st_sh, rep, rep, rep, rep),
                (rep, rep, st_sh), st_at(1))
            self._refreeze = _jit(_refreeze, (st_sh, rep), st_sh, st_at(0))
            self._assign = _jit(_assign, (st_sh, rep, rep, rep), st_sh,
                                st_at(0))
        else:
            self._prefill_chunk = _jit(
                _prefill, (par_sh, st_sh, rep, rep, rep), (rep, rep, st_sh),
                st_at(1))
            self._refreeze = _jit(_refreeze, (st_sh,), st_sh, st_at(0))
            self._assign = None
        self._release = _jit(self.pool.release, (st_sh, rep), st_sh,
                             st_at(0))
        self._set_lane = _jit(_set_lane, (st_sh, rep, rep, rep, rep, rep),
                              st_sh, st_at(0))

        # speculative decoding: one jitted draft–verify step scores all
        # K+1 panel positions in a single forward over the pooled cache,
        # accepts per lane on device, and rolls the rejected suffix back
        # by a pure length decrement — zero retraces across accept lengths
        # 0..K.  When disabled the non-spec path above is preserved
        # bit-for-bit (the verify step is never built, never traced).
        self._spec = spec if spec is not None and spec.active else None
        self._verify = None
        self._adaptive = None
        if self._spec is not None:
            self.drafter = self._spec.build_drafter()
            qn = self._spec.k + 1
            self.spec_hist = np.zeros(qn, np.int64)   # committed-1 per tick
            if self._spec.adaptive:
                # host-side per-slot draft-length controller: each slot's
                # recent acceptance rate scales its next draft window
                # (data only — the [slots, K+1] panel shape never changes)
                self._adaptive = AdaptiveDraft(self._spec)

            def _verify(p, st, toks, m, dl):
                logits, st = lm.forward_panel_pooled(p, st, toks, m, cfg,
                                                     ctx, bs_)
                tok, logp, nc, lanes = sampling.accept_step(
                    logits, toks, dl, st["sample"], m)
                # appended qn per live slot; keep 1 + accepted = nc
                roll = qn * m.astype(jnp.int32) - nc
                st = self.pool.rollback({**st, "sample": lanes}, roll)
                return tok, logp, nc, st

            self._verify = _jit(_verify,
                                (par_sh, st_sh, tok_sh, vec_sh, vec_sh),
                                (tok_sh, tok_sh, vec_sh, st_sh), st_at(1))

        # overlapped pipeline: the chained decode entry consumes the
        # PREVIOUS tick's un-synced token vector as a device operand and
        # host-overrides only the broken-chain lanes — the input panel of
        # tick t+1 never round-trips through the host, so the scheduler
        # tick runs while the device computes.  Same forward, same sampler,
        # one extra shape family; built only when overlap is on so the
        # serial engine's trace_counts() are untouched.
        self.overlap = bool(overlap)
        self._inflight: Optional[Dict[str, Any]] = None
        if self.overlap:
            def _decode_chain(p, st, prev, ov, ovm, m):
                t = jnp.where(ovm, ov, prev)[:, None]
                logits, st = lm.forward_panel_pooled(p, st, t, m, cfg, ctx,
                                                     bs_)
                tok, logp, lanes = sampling.sample_step(
                    logits[:, 0], st["sample"], m)
                return tok, logp, {**st, "sample": lanes}

            self._decode_chain = _jit(
                _decode_chain,
                (par_sh, st_sh, vec_sh, vec_sh, vec_sh, vec_sh),
                (vec_sh, vec_sh, st_sh), st_at(1))
            # steady-state device-operand caches: on an uninterrupted
            # chain the override vectors are all-zero and the decode mask
            # repeats, so reuse ONE transferred array per shape instead of
            # a fresh device_put every tick.  All entries are built with
            # jnp.asarray(np.ndarray) so every dispatch hands
            # _decode_chain the same operand provenance (the jit cache
            # keys committed device_puts apart from jit outputs — mixing
            # them would double-compile).
            self._ov_zero: Optional[Tuple[jax.Array, jax.Array]] = None
            self._mask_cache: Dict[Tuple[int, ...], jax.Array] = {}
        else:
            self._decode_chain = None

        # host mirrors (avoid a device sync per tick)
        self._tail_len = np.zeros(slots, np.int64)
        self._last_tok: Dict[int, int] = {}           # slot -> last token
        self._callbacks: Dict[int, Callable[[RequestOutput], None]] = {}
        self._pending_release: List[int] = []         # flushed once per tick

        # fault-tolerant lifecycle: deadline/cancel/shed accounting, the
        # seeded fault plan (None in production), and the degraded-mode
        # queue threshold (queue >= degrade_queue drops spec drafting to 0
        # so verify ticks commit exactly one token — pressure relief
        # without a shape change).  _slot_live mirrors which slots hold
        # admitted device state so a double release is detected host-side
        # as a warning, never acted on twice.
        self._faults = faults
        self._degrade_queue = degrade_queue
        self._tick_no = 0
        self._in_tick = False
        self._slot_live = np.zeros(slots, bool)
        self.fault_counters: Dict[str, int] = {
            "shed": 0, "timeout": 0, "cancelled": 0, "double_release": 0,
            "drafter_error": 0, "deferred": 0, "degraded_ticks": 0,
            "injected_page_exhaustion": 0}

        # observability (repro.obs.Observability or None): a host-only
        # telemetry sink fed exclusively at the tick-boundary sync point
        # and on the host-side submit/cancel paths.  Every call site is
        # guarded on `self._obs is not None`, no jitted function knows it
        # exists, and it receives plain ints/floats/lists — the obs-on
        # engine is token-identical and retrace-identical to obs-off
        # (tests/test_obs.py pins all three properties).
        self._obs = obs
        self._tick_committed = 0          # tokens committed this tick
        if obs is not None:
            obs.pool_built(self.pool.nbytes())
        if obs is not None and faults is not None:
            faults.on_fire = (
                lambda site, tick: obs.fault(site, tick,
                                             self.scheduler.clock()))

        # paged pool: host-side id lifecycle + prefix index.  Sharing needs
        # deterministic block content, which needs deterministic chunk
        # boundaries — the trie only indexes blocks frozen by full-width
        # chunks, so it is active iff prefill is chunked.
        self._trie = PrefixTrie() if paged else None
        self._alloc = (BlockAllocator(self.pool.n_phys,
                                      on_evict=self._trie.drop)
                       if paged else None)
        self._blocks: Dict[int, List[int]] = {}       # slot -> table row ids
        self._reserved: Dict[int, int] = {}           # slot -> pages owed

    # -- public API ---------------------------------------------------------
    def submit(self, prompt, params: Optional[SamplingParams] = None,
               on_token: Optional[Callable[[RequestOutput], None]] = None
               ) -> int:
        """Queue a request (any iterable of token ids) under its own
        :class:`SamplingParams`.  Returns the request id.

        ``on_token`` is called with a :class:`RequestOutput` snapshot after
        every token window this request commits — one token per tick on
        the non-speculative path, up to ``spec.k + 1`` tokens per verify
        tick under speculation (the last snapshot has ``finished``).

        Under load shedding (``max_queue`` set, queue full) the request is
        rejected immediately: ``on_token`` fires exactly once with a final
        ``finish_reason="shed"`` snapshot and nothing is registered — the
        shed costs no slot, no pages, and no tick work.
        """
        toks = [int(t) for t in np.asarray(prompt)]
        rid = self.scheduler.submit(toks, params)
        if self._obs is not None:
            # queue_depth is passed from the post-submit queue so the gauge
            # is consistent even when the request was shed at submit time
            # (sheds never enter the queue) — the asyncio frontend submits
            # between ticks, where obs.tick cannot refresh it
            self._obs.request_submitted(rid, len(toks),
                                        self.scheduler.clock(),
                                        queue_depth=len(self.scheduler.queue))
        req = self.scheduler.finished.get(rid)
        if req is not None and req.finish_reason == "shed":
            # one counter path: the scheduler sheds, the scheduler counts
            # (Scheduler.shed_count); the engine mirror re-syncs instead of
            # incrementing so a shed can never be double-counted no matter
            # which layer observed it first
            self.fault_counters["shed"] = self.scheduler.shed_count
            out = req.output()
            if self._obs is not None:
                self._obs.request_finished(out, self.scheduler.clock())
            if on_token is not None:
                on_token(out)
            return rid
        if on_token is not None:
            self._callbacks[rid] = on_token
        return rid

    def cancel(self, rid: int) -> bool:
        """Cancel a request wherever it lives (queued / prefilling /
        decoding).  Returns whether anything was cancelled — a rid that
        already finished (or was never submitted) is a quiet ``False``,
        so cancellation racing normal completion is safe to lose.

        An active request's slot is released through the same batched
        release path normal completion uses (paged blocks decref'd, LRU
        retains revivable prefixes); co-tenant slots are untouched — their
        token streams are bit-identical to a run where the cancelled
        request never existed past its release tick.  The final
        ``finish_reason="cancelled"`` snapshot fires the request's
        ``on_token`` callback once.
        """
        return self._cancel_inner(rid) is not None

    def _cancel_inner(self, rid: int) -> Optional[RequestOutput]:
        req = self.scheduler.cancel(rid)
        if req is None:
            return None
        self.fault_counters["cancelled"] += 1
        if req.slot >= 0:
            self._abort_slot(req.slot)
        out = req.output()
        if self._obs is not None:
            self._obs.request_finished(out, self.scheduler.clock())
        cb = self._callbacks.pop(rid, None)
        if cb is not None:
            cb(out)
        return out

    def _abort_slot(self, slot: int) -> None:
        """Tear down an active slot outside the normal finish path (cancel
        or deadline expiry): queue its batched release and reset the host
        mirrors.  Outside a tick the release flushes immediately (a caller
        cancelling between ticks must not leave pages pinned)."""
        self._pending_release.append(slot)
        self._tail_len[slot] = 0
        self._last_tok.pop(slot, None)
        if self._adaptive is not None:
            self._adaptive.reset(slot)
        if not self._in_tick:
            self._flush_releases()

    def _expire_deadlines(self, now: float,
                          events: List[RequestOutput]) -> None:
        """Finish every request past its deadline (``finish_reason=
        "timeout"``), releasing the slots of active ones.  Runs at tick
        start — BEFORE this tick's decode — so a stop committed last tick
        has already won; a deadline can never retract emitted output."""
        for req in self.scheduler.expire(now):
            self.fault_counters["timeout"] += 1
            if req.slot >= 0:
                self._abort_slot(req.slot)
            out = req.output()
            if self._obs is not None:
                self._obs.request_finished(out, now)
            events.append(out)
            cb = self._callbacks.pop(req.rid, None)
            if cb is not None:
                cb(out)

    def run(self) -> Dict[int, RequestOutput]:
        """Tick until every submitted request finished; returns
        ``{request id: RequestOutput}``."""
        while not self.scheduler.done():
            self.step()
        self.quiesce()
        return {rid: req.output()
                for rid, req in self.scheduler.finished.items()}

    def stream(self) -> Iterator[RequestOutput]:
        """Tick until the queue drains, yielding a :class:`RequestOutput`
        snapshot per committed token window (interleaved across live
        requests, in emission order) — per token without speculation, per
        accepted window with it.  Submitting more work mid-iteration
        extends the stream."""
        while not self.scheduler.done():
            yield from self.step()
        yield from self.quiesce()

    def quiesce(self) -> List[RequestOutput]:
        """Drain the overlapped pipeline: commit (or, for requests that
        died in flight, discard) the in-flight tick's window and flush any
        pending releases.  A no-op on the serial engine or when nothing is
        in flight.  Snapshot paths and the asyncio frontend's shutdown
        call this so the arena is never serialized under an un-synced
        dispatch; returns the snapshots it committed."""
        events: List[RequestOutput] = []
        self._sync_inflight(events)
        self._flush_releases()
        return events

    def program_memory(self) -> Dict[str, Dict[str, int]]:
        """``{program: {kind: bytes}}`` for every program that has run:
        XLA's ``memory_analysis`` of its compiled executable (``argument``,
        ``output``, ``alias`` -- the outputs that reuse a donated argument
        -- and ``temp``).  Meant for the end of a warm-up: each program is
        then a compile-cache hit at its abstract arguments, so nothing
        compiles (the prefill program is read at the scheduler's chunk
        width, and skipped for unchunked prefill, which compiles per
        prompt length).  With telemetry attached the numbers also go to
        ``repro.obs`` (``repro_program_bytes``)."""
        ran = self.trace_counts()
        if self.scheduler.chunk is None:
            ran.pop("prefill_chunk")
        out: Dict[str, Dict[str, int]] = {}
        for name, (fn, args) in self.entry_points(
                self.scheduler.chunk or 0).items():
            if not ran.get(name):
                continue
            m = fn.lower(*args).compile().memory_analysis()
            out[name] = {"argument": m.argument_size_in_bytes,
                         "output": m.output_size_in_bytes,
                         "alias": m.alias_size_in_bytes,
                         "temp": m.temp_size_in_bytes}
            if self._obs is not None:
                self._obs.program_memory(name, out[name])
        return out

    def generate_batch(self, prompts: jax.Array,
                       params: Optional[SamplingParams] = None) -> jax.Array:
        """Convenience mirror of the legacy ``Engine.generate``: submit all
        rows of ``prompts [B, S]`` under one ``params``, return
        ``[B, max_new_tokens]`` tokens (the first comes from the prompt's
        last logits, like the legacy engine's prefill token)."""
        params = params if params is not None else SamplingParams()
        rids = [self.submit(row, params) for row in np.asarray(prompts)]
        out = self.run()
        return jnp.asarray([out[r].token_ids for r in rids], jnp.int32)

    def trace_counts(self) -> Dict[str, int]:
        counts = {"decode": retrace_count(self._decode),
                  "prefill_chunk": retrace_count(self._prefill_chunk),
                  "refreeze": retrace_count(self._refreeze),
                  "release": retrace_count(self._release),
                  "set_lane": retrace_count(self._set_lane)}
        if self._assign is not None:
            counts["assign"] = retrace_count(self._assign)
        if self._verify is not None:
            counts["verify"] = retrace_count(self._verify)
        if self._decode_chain is not None:
            counts["decode_chain"] = retrace_count(self._decode_chain)
        return counts

    def entry_points(self, chunk: int = 0):
        """Every registered jitted transition with abstract example args.

        Returns ``{name: (jitted, args)}`` where ``args`` is a tuple of
        ``ShapeDtypeStruct`` pytrees matching one representative call from
        :meth:`step`; the names are exactly :meth:`trace_counts` keys.
        The static analyzer (:mod:`repro.analysis`) traces each entry
        under these avals to audit its jaxpr and pin its compile manifest
        without touching real data.  ``chunk`` is the prefill chunk width
        to describe (default: one block — each distinct width is its own
        legitimate shape family, see :func:`stable_trace_counts`).
        """
        ab = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)  # noqa: E731
        p = jax.tree_util.tree_map(ab, self.params)
        st = jax.tree_util.tree_map(ab, self.state)
        b, sb = self.pool.slots, self.pool.max_blocks
        c = chunk or self.pool.bs

        def i32(*shape):
            return jax.ShapeDtypeStruct(shape, jnp.int32)

        def f32(*shape):
            return jax.ShapeDtypeStruct(shape, jnp.float32)
        boolv = jax.ShapeDtypeStruct((b,), jnp.bool_)
        scalar_b = jax.ShapeDtypeStruct((), jnp.bool_)
        key = jax.ShapeDtypeStruct((2,), jnp.uint32)

        out = {"decode": (self._decode, (p, st, i32(b, 1), boolv)),
               "release": (self._release, (st, i32(b))),
               "set_lane": (self._set_lane,
                            (st, i32(), f32(), i32(), f32(), key))}
        if self.pool.paged:
            tb = self.pool.tail // self.pool.bs
            out["prefill_chunk"] = (
                self._prefill_chunk,
                (p, st, i32(1, c), i32(), scalar_b, i32(c // self.pool.bs)))
            out["refreeze"] = (self._refreeze, (st, i32(b, tb)))
            out["assign"] = (self._assign, (st, i32(), i32(sb), i32()))
        else:
            out["prefill_chunk"] = (
                self._prefill_chunk, (p, st, i32(1, c), i32(), scalar_b))
            out["refreeze"] = (self._refreeze, (st,))
        if self._verify is not None:
            qn = self._spec.k + 1
            out["verify"] = (self._verify,
                             (p, st, i32(b, qn), boolv, i32(b)))
        if self._decode_chain is not None:
            # the overlapped dispatch path: prev is the in-flight tick's
            # un-synced token vector, (ov, ovm) the host override lanes
            out["decode_chain"] = (self._decode_chain,
                                   (p, st, i32(b), i32(b), boolv, boolv))
        return out

    @property
    def adaptive_hist(self) -> Optional[np.ndarray]:
        """Histogram of per-slot draft windows actually *proposed* under
        ``SpecConfig(adaptive=True)`` (index = draft tokens a slot put up
        for verification that tick); ``None`` when adaptive K is off."""
        return None if self._adaptive is None else self._adaptive.hist

    # -- crash-safe warm restart --------------------------------------------
    def _snapshot_guard(self, what: str) -> None:
        if self._alloc is None:
            raise ValueError(f"{what} needs the paged pool: only the "
                             "shared arena + prefix index persist "
                             "(build the engine with paged=True)")
        if self.mesh is not None:
            raise ValueError(f"{what} is unsharded-only: arena leaves are "
                             "persisted as full host tensors")

    def save_snapshot(self, directory: str) -> int:
        """Persist the warm-restart state of the paged pool under
        ``directory`` (atomic write-temp-then-rename via
        :class:`~repro.checkpoint.manager.CheckpointManager`): the shared
        arena leaves, the chained-hash -> physical-page pairs of the
        prefix index, and the allocator's registered population.  In-flight
        request state (tails, tables, occupancy) is deliberately NOT
        saved — after a crash there are no in-flight requests; what
        survives is exactly the shareable frozen content a restarted
        server can hit on.  Returns the step number written.
        """
        self._snapshot_guard("save_snapshot")
        # quiesce first: the arena must never be serialized while a
        # dispatched-but-unsynced tick could still scatter into it
        self.quiesce()
        from repro.checkpoint.manager import CheckpointManager
        t0 = self.scheduler.clock() if self._obs is not None else 0.0
        pairs = self._alloc.export_registered()
        tree = {"arena": self.pool.arena_leaves(self.state),
                "hashes": np.asarray([h for h, _ in pairs], np.int64),
                "ids": np.asarray([b for _, b in pairs], np.int32)}
        mgr = CheckpointManager(directory, keep=2)
        step = (mgr.latest_step() or 0) + 1
        mgr.save(step, tree,
                 meta={"kind": "serving-prefix-cache",
                       "geometry": self.pool.geometry(),
                       "n_registered": len(pairs)},
                 blocking=True)
        if self._obs is not None:
            self._obs.snapshot_event("save", t0,
                                     self.scheduler.clock() - t0,
                                     len(pairs))
        return step

    def load_snapshot(self, directory: str) -> int:
        """Warm-restart from the newest snapshot under ``directory``:
        reload the arena leaves, rebuild the prefix trie and the
        allocator's cached population (every restored page enters at
        refcount 0, revivable by a prefix hit, evictable from the LRU's
        cold end in snapshot order).  The next admission of a prompt whose
        prefix was frozen before the crash skips its prefill entirely.

        Idle-only (restore before serving traffic) and geometry-checked:
        a snapshot from a different pool geometry raises a ``ValueError``
        naming every mismatched field — never a half-restore.  Content-
        addressed hash chains make *stale* content impossible, so geometry
        is the only validation needed; the slot count may freely differ
        (the arena is slot-independent).  Returns the number of restored
        pages.
        """
        self._snapshot_guard("load_snapshot")
        self.quiesce()
        t0 = self.scheduler.clock() if self._obs is not None else 0.0
        if self.scheduler.active or self.scheduler.queue or self._blocks:
            raise ValueError("load_snapshot on a busy engine: restore "
                             "before submitting traffic")
        from repro.checkpoint.manager import CheckpointManager
        mgr = CheckpointManager(directory, keep=2)
        step = mgr.latest_step()
        if step is None:
            raise ValueError(f"no snapshot under {directory!r}")
        manifest = mgr.read_manifest(step)
        if manifest.get("kind") != "serving-prefix-cache":
            raise ValueError(
                f"snapshot step {step} under {directory!r} is not a "
                f"serving prefix cache (kind={manifest.get('kind')!r})")
        mine, theirs = self.pool.geometry(), manifest.get("geometry") or {}
        bad = [f"{k}: engine has {mine[k]!r}, snapshot has "
               f"{theirs.get(k)!r}" for k in mine if theirs.get(k) != mine[k]]
        if bad:
            raise ValueError("snapshot geometry mismatch — "
                             + "; ".join(bad))
        n = int(manifest["n_registered"])
        # to_device=False: the int64 hash chain must come back exactly
        # (jnp.asarray would truncate it to int32 under x64-disabled jax)
        like = {"arena": self.pool.arena_leaves(self.pool.init_state()),
                "hashes": np.zeros(n, np.int64),
                "ids": np.zeros(n, np.int32)}
        tree, _ = mgr.restore(step, like, to_device=False)
        pairs = list(zip((int(h) for h in tree["hashes"]),
                         (int(b) for b in tree["ids"])))
        self._alloc.restore_registered(pairs)     # validates ids first
        self._trie.reload(pairs)
        self.state = self.pool.load_arena(self.state, tree["arena"])
        if self._obs is not None:
            self._obs.snapshot_event("load", t0,
                                     self.scheduler.clock() - t0,
                                     len(pairs))
        return len(pairs)

    # -- one tick -----------------------------------------------------------
    def step(self) -> List[RequestOutput]:
        """Advance the engine one tick; returns a snapshot per token emitted
        (empty while the pool is still prefilling).  Slots freed this tick
        are recycled in ONE batched release at the end (host-padded
        ``[slots]`` vector — a tick finishing many requests costs one
        jitted call, not one per slot).

        Tick order is the fault-tolerance contract: deadline expiry and the
        release flush run FIRST (inside :meth:`_step_inner`), so a slot
        freed by a timeout is re-admittable the same tick but a request
        admitted this tick can never land in a slot whose release is still
        pending from an expiry — admission only sees fully-released slots.
        """
        with TraceAnnotation(spans.TICK):
            obs = self._obs
            t_start = self.scheduler.clock() if obs is not None else 0.0
            self._tick_no += 1
            self._in_tick = True
            self._tick_committed = 0
            try:
                return self._step_inner()
            finally:
                faults = self._faults
                if faults is not None:
                    # double-release fault: push an already-freed slot
                    # through the release path again.  The flush must absorb
                    # it as a counted warning (and the device transition as
                    # a no-op).
                    cand = (list(self._pending_release)
                            or [s for s in range(self.pool.slots)
                                if s not in self.scheduler.active])
                    if cand and faults.take(DOUBLE_RELEASE, self._tick_no):
                        self._pending_release.append(faults.choose(cand))
                if self._pending_release:
                    with TraceAnnotation(spans.RELEASE):
                        self._flush_releases()
                self._in_tick = False
                if obs is not None:
                    sch = self.scheduler
                    obs.tick(
                        start=t_start, now=sch.clock(), tick_no=self._tick_no,
                        committed=self._tick_committed,
                        queue_depth=len(sch.queue), active=len(sch.active),
                        slots=self.pool.slots, counters=self.fault_counters,
                        free_blocks=(self._alloc.free_blocks()
                                     if self._alloc is not None else None),
                        n_phys=(self.pool.n_phys
                                if self._alloc is not None else 0),
                        evictions=(self._alloc.evictions
                                   if self._alloc is not None else 0),
                        trie_blocks=(len(self._trie)
                                     if self._trie is not None else 0),
                        spec_hist=(self.spec_hist.tolist()
                                   if self._spec is not None else None))

    def _flush_releases(self) -> None:
        """Recycle every pending slot in one batched device release.

        Idempotent at both layers: a slot appearing twice (or pushed again
        after an earlier flush) is detected against the ``_slot_live``
        mirror and counted as a ``double_release`` warning — its allocator
        decref is skipped (host refcounts stay exact) while the device
        release vector, which is naturally idempotent on a free slot,
        still runs once per unique slot.
        """
        if not self._pending_release:
            return
        seen = list(dict.fromkeys(self._pending_release))   # ordered unique
        doubles = len(self._pending_release) - len(seen)
        live = []
        for s in seen:
            if self._slot_live[s]:
                self._slot_live[s] = False
                live.append(s)
            else:
                doubles += 1
        self._pending_release = []
        self.fault_counters["double_release"] += doubles
        # the whole unique set goes to the device — releasing an already
        # free slot there is a masked no-op (even under checkify), which is
        # exactly the property the double-release fault site exercises
        vec = np.full(self.pool.slots, -1, np.int32)
        vec[:len(seen)] = seen
        self.state = self._release(self.state, jnp.asarray(vec))
        if self._alloc is not None:
            for s in live:
                ids = self._blocks.pop(s, [])
                if ids:
                    self._alloc.decref(ids)
                self._reserved.pop(s, None)

    def _admit_paged(self, now: float):
        """Reservation + prefix-hit admission for the queue's head.

        Returns the admitted :class:`~.scheduler.Request`, or None (leaving
        the request queued) when the arena cannot guarantee its worst-case
        page demand on top of every already-admitted request's outstanding
        reservation — the paged analogue of running out of slots.  On
        admission, a prefix-trie hit points the slot's table row at the
        shared pages and skips their prefill.
        """
        sch, bs, alloc = self.scheduler, self.pool.bs, self._alloc
        nxt = sch.queue[0]
        plen = len(nxt.prompt)
        hits: List[int] = []
        if sch.chunk is not None:
            hits = self._trie.match(block_hashes(nxt.prompt, bs))
            # a full-prompt hit would leave no tokens to produce the first
            # token's logits; and hits are quantized down to whole chunks
            # so the remaining prefill reuses the full-width chunk
            # boundaries the frozen blocks were hashed under
            cw = sch.chunk // bs
            n_hit = min(len(hits), (plen - 1) // bs) // cw * cw
            hits = hits[:n_hit]
        revived = sum(1 for i in hits if alloc.refcount(i) == 0)
        need = -(-(plen + nxt.params.max_new_tokens) // bs) - len(hits)
        outstanding = sum(self._reserved.values())
        exhausted = need + revived + outstanding > alloc.free_blocks()
        if (not exhausted and self._faults is not None
                and self._faults.take(PAGE_EXHAUSTION, self._tick_no)):
            # injected arena pressure: behave exactly as if no physical
            # blocks were free, driving the backoff-requeue path
            self.fault_counters["injected_page_exhaustion"] += 1
            exhausted = True
        if exhausted:
            # defer with exponential backoff (head-of-line: FIFO preserved)
            sch.defer_admission(now)
            self.fault_counters["deferred"] += 1
            return None
        req = sch.admit(now)
        if self._obs is not None and sch.chunk is not None:
            self._obs.prefix_match(len(hits), plen // bs)
        self._reserved[req.slot] = need
        self._blocks[req.slot] = list(hits)
        if hits:
            alloc.incref(hits)
            pad = np.zeros(self.pool.max_blocks, np.int32)
            pad[:len(hits)] = hits
            self.state = self._assign(self.state, jnp.int32(req.slot),
                                      jnp.asarray(pad),
                                      jnp.int32(len(hits)))
            req.prefill_done = len(hits) * bs   # shared prefix: no prefill
            self._tail_len[req.slot] = 0
        return req

    def _admit(self, now: float) -> None:
        """Fill every free slot from the queue, writing each new request's
        sampling lane into device state."""
        sch = self.scheduler
        while sch.queue and sch.free_slots():
            if sch.queue[0].next_admit > now:
                break                          # head backing off: FIFO waits
            req = (sch.admit(now) if self._alloc is None
                   else self._admit_paged(now))
            if req is None:
                break                          # arena full: wait for releases
            p = req.params
            self.state = self._set_lane(
                self.state, jnp.int32(req.slot),
                jnp.float32(p.temperature), jnp.int32(p.top_k),
                jnp.float32(p.top_p), sampling.request_key(p))
            self._slot_live[req.slot] = True

    def _step_inner(self) -> List[RequestOutput]:
        events: List[RequestOutput] = []
        sch = self.scheduler
        # deadline expiry, then the release flush, THEN admission: slots
        # freed by a timeout (or by a between-tick cancel) are fully
        # released before any new request can be admitted into them — a
        # pending release must never fire on a slot a fresh tenant just
        # claimed.
        now = sch.clock()
        with TraceAnnotation(spans.ADMIT):
            self._expire_deadlines(now, events)
            self._flush_releases()
            self._admit(now)

        # cancellation-mid-prefill fault: kill a partially-prefilled
        # request between its chunks — its pages must come back (at tick
        # end) without perturbing co-tenant streams
        if self._faults is not None:
            mid = [r for r in sch.active.values()
                   if 0 < r.prefill_done < len(r.prompt)]
            if mid and self._faults.take(CANCEL_PREFILL, self._tick_no):
                out = self._cancel_inner(self._faults.choose(mid).rid)
                if out is not None:
                    events.append(out)

        if self.overlap and self._spec is not None:
            # SPEC PIPELINE (shallow): the verify dispatched last tick is
            # still in flight — the admission work above and the prefill
            # dispatch below overlap it on the host.  It must commit before
            # the refreeze decision (the paged fold scatters into exactly
            # the rows the device deems full, so the tail mirrors need the
            # data-dependent accept counts) and before drafting (the n-gram
            # drafter reads the committed history) — so the designated sync
            # sits between the prefill dispatch and this tick's
            # draft/verify dispatch, which tick t+1 will sync in turn.
            self._prefill_tick(events)
            self._sync_inflight(events)
            self._refreeze_tick(events)
            slots = sch.decoding_slots()
            if not slots:
                return events
            return self._spec_tick(slots, events)

        # refreeze before decode appends (under overlap the mirrors are
        # still exact here: a plain decode appends exactly one token, and
        # the in-flight tick's +1 was applied at its dispatch)
        self._refreeze_tick(events)
        self._prefill_tick(events)

        # decode tick for every slot with a live request past prefill
        slots = sch.decoding_slots()
        if not slots:
            if self.overlap:
                self._sync_inflight(events)   # pipeline drains when idle
            return events
        if self._spec is not None:
            return self._spec_tick(slots, events)
        if self.overlap:
            return self._overlap_decode_tick(slots, events)
        b = self.pool.slots
        t_dec = sch.clock() if self._obs is not None else 0.0
        with TraceAnnotation(spans.DECODE):
            tokens = np.zeros((b, 1), np.int32)
            mask = np.zeros((b,), bool)
            for s in slots:
                tokens[s, 0] = self._last_tok[s]
                mask[s] = True
            tok, logp, self.state = self._decode(
                self.params, self.state, jnp.asarray(tokens),
                jnp.asarray(mask))
        with TraceAnnotation(spans.SYNC):
            picked, logps = np.asarray(tok), np.asarray(logp)
            if self._obs is not None:
                # span covers dispatch through the np.asarray token sync —
                # the tick's designated host<->device boundary
                self._obs.decode_tick(t_dec, sch.clock() - t_dec, len(slots),
                                      spec=False)
            for s in slots:
                if s not in sch.active:
                    continue      # cancelled mid-tick (reentrant callback):
                                  # the sampled token dies with the slot
                self._tail_len[s] += 1
                self._emit(s, [int(picked[s])], [float(logps[s])], events)
        return events

    def _refreeze_tick(self, events: List[RequestOutput]) -> None:
        """Refreeze every slot whose tail ring is full (only decoding slots
        can fill one; the host list must mirror the device-side
        ``tail_len == tail`` mask exactly, because the paged fold scatters
        into precisely the rows the device deems full)."""
        full = [s for s in range(self.pool.slots)
                if self._tail_len[s] >= self.pool.tail]
        if not full:
            return
        with TraceAnnotation(spans.REFREEZE):
            self._fold(full, events)

    def _fold(self, full: List[int], events: List[RequestOutput]) -> None:
        """Dispatch one refreeze of the ``full`` slots' tails and count
        it."""
        if self._alloc is not None:
            tb = self.pool.tail // self.pool.bs
            if (self._inflight is not None
                    and len(full) * tb + sum(self._reserved.values())
                    > self._alloc.free_blocks()):
                # a slot whose FINISHING window is still in flight can show
                # a speculatively-full tail one tick past its reservation;
                # folding it would alloc pages admission promised to other
                # requests.  Rare fallback: drain the pipeline first — the
                # commit releases dead slots (and their pages) and drops
                # them out of `full`, restoring the never-fails invariant.
                self._sync_inflight(events)
                self._flush_releases()
                full = [s for s in range(self.pool.slots)
                        if self._tail_len[s] >= self.pool.tail]
                if not full:
                    return
            ids = np.zeros((self.pool.slots, tb), np.int32)
            for s in full:
                fresh = self._alloc.alloc(tb)    # CoW: never shared pages
                ids[s] = fresh
                self._blocks.setdefault(s, []).extend(fresh)
                self._reserved[s] = max(0, self._reserved.get(s, 0) - tb)
            self.state = self._refreeze(self.state, jnp.asarray(ids))
        else:
            self.state = self._refreeze(self.state)
        for s in full:
            self._tail_len[s] = 0
        if self._obs is not None:
            # host integers only: the full slots the host mirrors counted,
            # and the rows the refreeze program compresses for them
            self._obs.refreeze(len(full),
                               self.pool.refreeze_rows(len(full)))

    def _prefill_tick(self, events: List[RequestOutput]) -> None:
        """One prefill chunk for the oldest request still owed prompt work.

        The final chunk's first-token sync stays SYNCHRONOUS even under
        overlap — it happens once per request and is the TTFT the SLO
        benchmarks measure; the one-tick commit delay applies to the
        steady-state decode/verify windows only.
        """
        sch = self.scheduler
        req = sch.next_prefill()
        if req is None:
            return
        with TraceAnnotation(spans.PREFILL):
            t_pf = sch.clock() if self._obs is not None else 0.0
            off0 = req.prefill_done
            chunk = sch.prefill_chunk(req)
            final = req.prefill_done >= len(req.prompt)
            toks = jnp.asarray(np.asarray(chunk, np.int32)[None, :])
            args = (self.params, self.state, toks, jnp.int32(req.slot),
                    jnp.asarray(final))
            if self._alloc is not None:
                nb_new = len(chunk) // self.pool.bs
                fresh = self._alloc.alloc(nb_new) if nb_new else []
                tok, logp, self.state = self._prefill_chunk(
                    *args, jnp.asarray(np.asarray(fresh, np.int32)))
                self._blocks.setdefault(req.slot, []).extend(fresh)
                self._reserved[req.slot] = max(
                    0, self._reserved.get(req.slot, 0) - nb_new)
                # content-address the new blocks, but only when this chunk
                # ran at full width: block bytes depend on the whole token
                # prefix AND the chunk boundaries it was processed under,
                # so only full-width-chunk blocks are reproducible by a
                # future prompt prefilling through the same scheduler
                if sch.chunk is not None and len(chunk) == sch.chunk:
                    hs = block_hashes(req.prompt[:req.prefill_done],
                                      self.pool.bs)
                    for i, bid in enumerate(fresh):
                        h = hs[off0 // self.pool.bs + i]
                        if self._alloc.register(bid, h):
                            self._trie.insert(h, bid)
            else:
                tok, logp, self.state = self._prefill_chunk(*args)
            # device-side tail_len after a chunk = chunk_len % bs, and all
            # chunks before the last are block-aligned
            self._tail_len[req.slot] = req.prefill_done % self.pool.bs
            if final:
                self._emit(req.slot, [int(np.asarray(tok)[0])],
                           [float(np.asarray(logp)[0])], events,
                           prefill=True)
            if self._obs is not None:
                # non-final chunks are async dispatch wall time; the final
                # chunk's span includes the first-token sync above
                self._obs.prefill_chunk(req.rid, req.slot, t_pf,
                                        sch.clock() - t_pf, len(chunk),
                                        final)

    def _overlap_decode_tick(self, slots: List[int],
                             events: List[RequestOutput]
                             ) -> List[RequestOutput]:
        """DEEP PIPELINE: dispatch this tick's decode BEFORE committing the
        previous one.

        The input token panel chains on device: each slot's token is the
        in-flight decode's un-synced output (a jax async value the device
        already holds), host-overridden only where the chain breaks — a
        slot fresh out of prefill, a slot re-admitted since the record was
        taken, or the first tick after an idle pipeline.  The host tail
        mirror advances at dispatch; a plain decode appends exactly one
        token, so the mirror stays exact without waiting, which is what
        keeps the refreeze decision (made before this sync) correct.  The
        dispatched tick is recorded and committed one tick later by
        :meth:`_sync_inflight` — where ``(slot, rid)`` liveness is
        re-checked, so tokens speculatively dispatched for a request that
        dies this tick are never committed.
        """
        sch = self.scheduler
        b = self.pool.slots
        t_dec = sch.clock() if self._obs is not None else 0.0
        with TraceAnnotation(spans.DECODE):
            rec = self._inflight
            if rec is None:
                # cold pipeline (first tick, or just drained): there is no
                # device token to chain on, so dispatch through the regular
                # decode entry from the host mirrors — same computation, and
                # _decode_chain only ever sees jit-output `prev` operands
                # (mixing host arrays in would key a second compile-cache
                # entry and break the zero-retrace bar)
                tokens = np.zeros((b, 1), np.int32)
                mask = np.zeros((b,), bool)
                for s in slots:
                    tokens[s, 0] = self._last_tok[s]
                    mask[s] = True
                tok, logp, self.state = self._decode(
                    self.params, self.state, jnp.asarray(tokens),
                    jnp.asarray(mask))
            else:
                chained = set()
                if rec["kind"] == "decode":
                    for s, rid in rec["slots"]:
                        req = sch.active.get(s)
                        if req is not None and req.rid == rid:
                            chained.add(s)
                broken = [s for s in slots if s not in chained]
                if broken:
                    ov = np.zeros((b,), np.int32)
                    ovm = np.zeros((b,), bool)
                    for s in broken:
                        ovm[s] = True
                        ov[s] = self._last_tok[s]
                    dov, dovm = jnp.asarray(ov), jnp.asarray(ovm)
                else:
                    # unbroken chain (the steady state): constant all-zero
                    # overrides, transferred once and reused
                    if self._ov_zero is None:
                        self._ov_zero = (
                            jnp.asarray(np.zeros((b,), np.int32)),
                            jnp.asarray(np.zeros((b,), bool)))
                    dov, dovm = self._ov_zero
                mkey = tuple(slots)
                dmask = self._mask_cache.get(mkey)
                if dmask is None:
                    if len(self._mask_cache) >= 64:
                        self._mask_cache.clear()
                    mask = np.zeros((b,), bool)
                    mask[list(slots)] = True
                    dmask = self._mask_cache[mkey] = jnp.asarray(mask)
                tok, logp, self.state = self._decode_chain(
                    self.params, self.state, rec["tok"], dov, dovm, dmask)
            for s in slots:
                self._tail_len[s] += 1
            new_rec = {"kind": "decode", "tok": tok, "logp": logp,
                       "ncommit": None, "dlen": None,
                       "slots": [(s, sch.active[s].rid) for s in slots],
                       "t0": t_dec, "n_slots": len(slots)}
        # commit tick t-1 while tick t computes behind it
        self._sync_inflight(events)
        self._inflight = new_rec
        return events

    def _sync_inflight(self, events: List[RequestOutput]) -> None:
        """Commit the in-flight tick's token window — THE designated sync
        point of the overlapped pipeline.

        This is the engine's only ``jax.block_until_ready`` and is
        registered (file, function) in
        :data:`repro.analysis.lint.DESIGNATED_SYNCS`; the block-until-ready
        lint rule flags the call anywhere else in the tree.  Liveness is
        re-checked per slot against the rid recorded at dispatch: a request
        that expired, was cancelled, or whose slot was re-admitted while
        the window was in flight has its tokens DISCARDED — the release /
        lane-set transitions already wiped the slot's device state, so the
        speculative appends were dead writes.  No-op when nothing is in
        flight (serial engine, drained pipeline).
        """
        rec, self._inflight = self._inflight, None
        if rec is None:
            return
        with TraceAnnotation(spans.SYNC):
            sch = self.scheduler
            jax.block_until_ready((rec["tok"], rec["logp"]))
            picked = np.asarray(rec["tok"])
            logps = np.asarray(rec["logp"])
            ncs = (np.asarray(rec["ncommit"])
                   if rec["ncommit"] is not None else None)
            if self._obs is not None:
                # the decode/verify host span under overlap runs dispatch
                # -> delayed sync on the host clock, the next tick's host
                # work included; device time is in the profiler trace
                now = sch.clock()
                self._obs.decode_tick(rec["t0"], now - rec["t0"],
                                      rec["n_slots"], spec=ncs is not None,
                                      overlapped=True)
            for s, rid in rec["slots"]:
                req = sch.active.get(s)
                if req is None or req.rid != rid:
                    continue          # died in flight: the window is discarded
                if ncs is None:
                    self._emit(s, [int(picked[s])], [float(logps[s])], events)
                else:
                    nc = int(ncs[s])
                    self._tail_len[s] += nc      # t0 + accepted stay appended
                    self.spec_hist[nc - 1] += 1  # nc - 1 = accepted drafts
                    if self._adaptive is not None:
                        self._adaptive.update(s, int(rec["dlen"][s]), nc - 1)
                    self._emit(s, [int(t) for t in picked[s, :nc]],
                               [float(l) for l in logps[s, :nc]], events)

    def _spec_tick(self, slots: List[int],
                   events: List[RequestOutput]) -> List[RequestOutput]:
        """One draft–verify decode tick over every decoding slot.

        Per live slot the host drafter proposes up to K continuations of
        the request's own history; the panel is clamped to the slot's tail
        headroom (a nearly-full tail simply speculates less — the regular
        refreeze machinery keeps working unchanged).  One jitted verify
        scores the whole [slots, K+1] panel, accepts per lane, and rolls
        back rejections; the host then commits each slot's window with
        stop scanning inside it.
        """
        sch = self.scheduler
        t_dec = sch.clock() if self._obs is not None else 0.0
        with TraceAnnotation(spans.DECODE):
            b, k = self.pool.slots, self._spec.k
            # degraded mode: under queue pressure drop the draft window to
            # 0 — every verify tick commits exactly one token, shrinking
            # per-tick latency so live slots finish (and free) sooner.  Host
            # data only: the [slots, K+1] panel shape never changes, so no
            # retrace.
            degraded = (self._degrade_queue > 0
                        and len(sch.queue) >= self._degrade_queue)
            if degraded:
                self.fault_counters["degraded_ticks"] += 1
            tokens = np.zeros((b, k + 1), np.int32)
            mask = np.zeros((b,), bool)
            dlen = np.zeros((b,), np.int32)
            for s in slots:
                req = sch.active[s]
                tokens[s, 0] = self._last_tok[s]
                mask[s] = True
                room = self.pool.tail - 1 - int(self._tail_len[s])
                cap = 0 if degraded else min(k, room)
                if self._adaptive is not None and not degraded:
                    # per-slot adaptive K: a slot whose drafts keep getting
                    # rejected speculates less (host-side data only — the
                    # [slots, K+1] panel shape, and hence the trace, is fixed)
                    cap = min(cap, self._adaptive.draft_len(s))
                if cap > 0:
                    try:
                        if (self._faults is not None
                                and self._faults.take(DRAFTER_ERROR,
                                                      self._tick_no)):
                            self._faults.raise_fault(DRAFTER_ERROR)
                        drafts = self.drafter.propose(
                            req.prompt + req.generated, cap)
                    except Exception:
                        # a crashing drafter degrades its slot to a draftless
                        # tick (one committed token) — never the engine
                        self.fault_counters["drafter_error"] += 1
                        drafts = []
                    dlen[s] = len(drafts)
                    tokens[s, 1:1 + len(drafts)] = drafts
            slot_rids = [(s, sch.active[s].rid) for s in slots]
            tok, logp, ncommit, self.state = self._verify(
                self.params, self.state, jnp.asarray(tokens),
                jnp.asarray(mask), jnp.asarray(dlen))
            # cancellation-mid-spec-window fault: the victim's drafts were
            # built into the panel and verified, but the window has not
            # committed — the verified tokens must be discarded with the slot
            if self._faults is not None:
                alive = [s for s in slots if s in sch.active]
                if alive and self._faults.take(CANCEL_SPEC, self._tick_no):
                    out = self._cancel_inner(
                        sch.active[self._faults.choose(alive)].rid)
                    if out is not None:
                        events.append(out)
        if self.overlap:
            # shallow pipeline: the window was dispatched, not synced — it
            # commits at the NEXT tick's _sync_inflight (after that tick's
            # admission/prefill dispatch), rid-checked so a cancellation
            # landing between now and then discards it
            self._inflight = {"kind": "spec", "tok": tok, "logp": logp,
                              "ncommit": ncommit, "dlen": dlen,
                              "slots": slot_rids, "t0": t_dec,
                              "n_slots": len(slots)}
            return events
        with TraceAnnotation(spans.SYNC):
            picked, logps = np.asarray(tok), np.asarray(logp)
            ncs = np.asarray(ncommit)
            if self._obs is not None:
                # draft + verify dispatch through the window sync
                self._obs.decode_tick(t_dec, sch.clock() - t_dec, len(slots),
                                      spec=True)
            for s in slots:
                if s not in sch.active:
                    continue      # cancelled inside the window: its verified
                                  # tokens are never committed
                nc = int(ncs[s])
                self._tail_len[s] += nc          # t0 + accepted stay appended
                self.spec_hist[nc - 1] += 1      # nc - 1 = accepted drafts
                if self._adaptive is not None:
                    self._adaptive.update(s, int(dlen[s]), nc - 1)
                self._emit(s, [int(t) for t in picked[s, :nc]],
                           [float(l) for l in logps[s, :nc]], events)
        return events

    def _emit(self, slot: int, toks: List[int], logprobs: List[float],
              events: List[RequestOutput], prefill: bool = False) -> None:
        """Commit one tick's token window for a slot; recycle the slot if
        that finished the request.  One RequestOutput snapshot (and one
        ``on_token`` callback) is emitted per window — per token on the
        non-speculative path, per accepted window under speculation."""
        req = self.scheduler.active[slot]
        before = len(req.generated)
        finished = self.scheduler.record_tokens(
            slot, toks, logprobs, decode_tick=not prefill) is not None
        # a stop inside a speculative window truncates the commit, so count
        # what actually landed, not what the tick offered
        self._tick_committed += len(req.generated) - before
        out = req.output()
        events.append(out)
        cb = self._callbacks.get(req.rid)
        if cb is not None:
            cb(out)
        if finished:
            if self._obs is not None:
                self._obs.request_finished(out, self.scheduler.clock())
            self._callbacks.pop(req.rid, None)
            self._pending_release.append(slot)   # batched flush at tick end
            self._tail_len[slot] = 0
            self._last_tok.pop(slot, None)
            if self._adaptive is not None:
                self._adaptive.reset(slot)   # next tenant starts fresh
        elif req.finish_reason is None:
            # (a reentrant cancel from this request's own callback leaves
            # finish_reason "cancelled" — _abort_slot already reset the
            # slot mirrors, so only a still-live request updates them)
            self._last_tok[slot] = req.generated[-1]
