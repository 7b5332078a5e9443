"""Unified model builder for all assigned architecture families.

A model is a sequence of *periods* (period = lcm of the attention/MoE
interleave patterns; 1 for homogeneous stacks, 8 for Jamba).  Per-position
param subtrees are stacked across periods with a leading "layers" axis and
the stack runs under ``lax.scan`` — 95-layer models lower to compact HLO.

Families:
  dense/moe/vlm — decoder-only LM (vlm/audio prepend stub frontend embeds)
  ssm           — RWKV-6 (time-mix + channel-mix per layer)
  hybrid        — Jamba (mamba x7 : attn x1, MoE every other layer)
  encdec        — bidirectional encoder + causal decoder w/ cross attention
"""
from __future__ import annotations

import dataclasses
from math import gcd
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.sparse_kv import (ARENA_KEYS, SparseKVCache, abstract_cache,
                                  freeze_prefix, to_arena_tiles)
from repro.kernels import ops
from . import module as mod
from .module import ParamSpec
from .layers import (rms_norm, norm_spec, embed_specs, embed_apply,
                     unembed_apply, mlp_specs, mlp_apply)
from .attention import (attn_specs, attn_apply, attn_decode, DenseKVCache,
                        cross_attn_decode, pooled_attn_panel,
                        pooled_attn_prefill_chunk)
from .moe import moe_specs, moe_apply
from .ssm import (mamba_specs, mamba_apply, mamba_decode, mamba_init_state,
                  rwkv_specs, rwkv_time_mix, rwkv_channel_mix,
                  rwkv_init_state, rwkv_time_mix_decode,
                  rwkv_channel_mix_decode)


def _lcm(a: int, b: int) -> int:
    return a * b // gcd(a, b)


def period_len(cfg) -> int:
    p = 1
    if cfg.family == "hybrid":
        p = _lcm(p, cfg.attn_every)
    if cfg.n_experts:
        p = _lcm(p, cfg.moe_every)
    return p


def layer_kind(cfg, i: int) -> Tuple[str, str]:
    if cfg.family == "ssm":
        return ("rwkv", "cmix")
    mixer = "attn" if cfg.is_attn_layer(i) else "mamba"
    ffn = "moe" if cfg.is_moe_layer(i) else "mlp"
    return (mixer, ffn)


# ---------------------------------------------------------------------------
# specs
# ---------------------------------------------------------------------------

def _block_specs(cfg, kind: Tuple[str, str], cross: bool = False
                 ) -> Dict[str, Any]:
    mixer, ffn = kind
    if mixer == "rwkv":
        return {"ln1": norm_spec(cfg), "tmix": rwkv_specs(cfg),
                "ln2": norm_spec(cfg)}
    s: Dict[str, Any] = {"ln1": norm_spec(cfg)}
    s["mixer"] = attn_specs(cfg) if mixer == "attn" else mamba_specs(cfg)
    if cross:
        s["ln_cross"] = norm_spec(cfg)
        s["cross"] = attn_specs(cfg, cross=True)
    s["ln2"] = norm_spec(cfg)
    s["ffn"] = moe_specs(cfg) if ffn == "moe" else mlp_specs(cfg)
    return s


def _stack_specs(tree: Any, n: int) -> Any:
    def one(p: str, s: ParamSpec) -> ParamSpec:
        return ParamSpec((n,) + tuple(s.shape), s.dtype,
                         ("layers",) + tuple(s.axes or (None,) * len(s.shape)),
                         init=s.init, scale=s.scale)
    return mod._map_with_path(one, tree)


def model_specs(cfg) -> Dict[str, Any]:
    p = period_len(cfg)
    n_periods = cfg.n_layers // p
    if cfg.n_layers % p != 0:
        raise ValueError(f"n_layers {cfg.n_layers} not a multiple of period {p}")
    kinds = [layer_kind(cfg, j) for j in range(p)]
    cross = cfg.family == "encdec"
    period = {f"l{j}": _block_specs(cfg, kinds[j], cross=cross)
              for j in range(p)}
    specs: Dict[str, Any] = {
        "embed": embed_specs(cfg),
        "blocks": _stack_specs(period, n_periods),
        "final_norm": norm_spec(cfg),
    }
    if cfg.family == "encdec":
        enc_period = {"l0": _block_specs(cfg, ("attn", "mlp"))}
        specs["encoder"] = _stack_specs(enc_period, cfg.enc_layers)
        specs["enc_norm"] = norm_spec(cfg)
    return specs


def abstract_params(cfg):
    return mod.abstract(model_specs(cfg))


def init_params(cfg, key):
    return mod.initialize(model_specs(cfg), key)


# ---------------------------------------------------------------------------
# full-sequence forward (train / prefill)
# ---------------------------------------------------------------------------

def _sublayer(x, p, kind, cfg, ctx, positions, memory, attn_impl,
              collect_kv: Optional[list] = None):
    mixer, ffn = kind
    if mixer == "rwkv":
        h = rwkv_time_mix(p["tmix"], rms_norm(x, p["ln1"], cfg.rms_eps),
                          cfg, ctx)
        x = ctx.constrain(x + h, ("batch", "seq", "embed"))
        h = rwkv_channel_mix(p["tmix"], rms_norm(x, p["ln2"], cfg.rms_eps),
                             cfg)
        return ctx.constrain(x + h, ("batch", "seq", "embed"))

    h = rms_norm(x, p["ln1"], cfg.rms_eps)
    if mixer == "attn":
        h = attn_apply(p["mixer"], h, cfg, ctx, positions,
                       causal=(memory is None) or None, attn_impl=attn_impl)
    else:
        h = mamba_apply(p["mixer"], h, cfg, ctx)
    x = ctx.constrain(x + h, ("batch", "seq", "embed"))
    if "cross" in p and memory is not None:
        h = attn_apply(p["cross"], rms_norm(x, p["ln_cross"], cfg.rms_eps),
                       cfg, ctx, positions, memory=memory)
        x = ctx.constrain(x + h, ("batch", "seq", "embed"))
    h2 = rms_norm(x, p["ln2"], cfg.rms_eps)
    if ffn == "moe":
        h2 = moe_apply(p["ffn"], h2, cfg, ctx)
    else:
        h2 = mlp_apply(p["ffn"], h2, ctx)
    return ctx.constrain(x + h2, ("batch", "seq", "embed"))


def _stack_forward(blocks, x, cfg, ctx, positions, kinds, memory=None,
                   attn_impl="masked", causal=True):
    def body(xc, pp):
        for j, kind in enumerate(kinds):
            k = kind if causal else ("attn", "mlp")
            xc = _sublayer(xc, pp[f"l{j}"], k, cfg, ctx, positions,
                           memory, attn_impl)
        return xc, None

    if cfg.remat:
        body = jax.checkpoint(body, prevent_cse=False)
    x, _ = lax.scan(body, x, blocks)
    return x


def forward_train(params, batch: Dict[str, jax.Array], cfg, ctx,
                  attn_impl: str = "masked") -> jax.Array:
    """Returns final hidden states [B, S, d] (logits are computed chunked in
    the loss to keep the [B,S,V] tensor off the residency list)."""
    if cfg.family == "encdec":
        return _encdec_forward(params, batch, cfg, ctx, attn_impl)

    tokens = batch["tokens"]
    x = embed_apply(params["embed"], tokens, cfg)
    if cfg.frontend and "frontend_embeds" in batch:
        fe = batch["frontend_embeds"].astype(x.dtype)
        # frontend prefill only (pooled serving rejects frontend families)
        x = jnp.concatenate([fe, x], axis=1)  # jitlint: disable=hot-path-op
    x = ctx.constrain(x, ("batch", "seq", "embed"))
    s = x.shape[1]
    positions = jnp.arange(s)
    p = period_len(cfg)
    kinds = [layer_kind(cfg, j) for j in range(p)]
    x = _stack_forward(params["blocks"], x, cfg, ctx, positions, kinds,
                       attn_impl=attn_impl)
    return rms_norm(x, params["final_norm"], cfg.rms_eps)


def _encdec_forward(params, batch, cfg, ctx, attn_impl):
    src = batch["src_embeds"].astype(cfg.cdtype)
    src = ctx.constrain(src, ("batch", "seq", "embed"))
    positions_src = jnp.arange(src.shape[1])
    enc = _stack_forward(params["encoder"], src, cfg, ctx, positions_src,
                         [("attn", "mlp")], causal=False)
    enc = rms_norm(enc, params["enc_norm"], cfg.rms_eps)

    x = embed_apply(params["embed"], batch["tokens"], cfg)
    x = ctx.constrain(x, ("batch", "seq", "embed"))
    positions = jnp.arange(x.shape[1])
    x = _stack_forward(params["blocks"], x, cfg, ctx, positions,
                       [("attn", "mlp")], memory=enc, attn_impl=attn_impl)
    return rms_norm(x, params["final_norm"], cfg.rms_eps)


def logits_fn(params, hidden: jax.Array, cfg, ctx) -> jax.Array:
    logits = unembed_apply(params["embed"], hidden, cfg)
    return ctx.constrain(logits, ("batch", None, "vocab"))


# ---------------------------------------------------------------------------
# prefill: full forward + per-layer state collection (for the serving engine)
# ---------------------------------------------------------------------------

def _sublayer_prefill(x, p, kind, cfg, ctx, positions, memory):
    mixer, ffn = kind
    if mixer == "rwkv":
        xin1 = rms_norm(x, p["ln1"], cfg.rms_eps)
        h, st = rwkv_time_mix(p["tmix"], xin1, cfg, ctx, return_state=True)
        x = x + h
        xin2 = rms_norm(x, p["ln2"], cfg.rms_eps)
        h = rwkv_channel_mix(p["tmix"], xin2, cfg)
        st = {**st, "cm_x": xin2.astype(jnp.float32)[:, -1]}
        return x + h, {"state": st}

    h = rms_norm(x, p["ln1"], cfg.rms_eps)
    if mixer == "attn":
        h, (k, v) = attn_apply(p["mixer"], h, cfg, ctx, positions,
                               return_kv=True)
        collected = {"k": k, "v": v}
    else:
        h, st = mamba_apply(p["mixer"], h, cfg, ctx, return_state=True)
        collected = {"state": st}
    x = x + h
    if "cross" in p and memory is not None:
        h = attn_apply(p["cross"], rms_norm(x, p["ln_cross"], cfg.rms_eps),
                       cfg, ctx, positions, memory=memory)
        x = x + h
    h2 = rms_norm(x, p["ln2"], cfg.rms_eps)
    h2 = moe_apply(p["ffn"], h2, cfg, ctx) if ffn == "moe" \
        else mlp_apply(p["ffn"], h2, ctx)
    return x + h2, collected


def forward_prefill(params, batch, cfg, ctx) -> Tuple[jax.Array, Dict]:
    """Full forward returning (final hidden, per-layer collected states).

    Collected states are stacked over periods: {"l{j}": {...(P, ...)}}.
    For encdec, also returns the per-layer cross K/V of the encoder memory.
    """
    memory = None
    if cfg.family == "encdec":
        src = batch["src_embeds"].astype(cfg.cdtype)
        pos_s = jnp.arange(src.shape[1])
        enc = _stack_forward(params["encoder"], src, cfg, ctx, pos_s,
                             [("attn", "mlp")], causal=False)
        memory = rms_norm(enc, params["enc_norm"], cfg.rms_eps)

    tokens = batch["tokens"]
    x = embed_apply(params["embed"], tokens, cfg)
    if cfg.frontend and "frontend_embeds" in batch:
        x = jnp.concatenate(  # jitlint: disable=hot-path-op
            [batch["frontend_embeds"].astype(x.dtype), x], axis=1)
    positions = jnp.arange(x.shape[1])
    p = period_len(cfg)
    kinds = [layer_kind(cfg, j) for j in range(p)]

    def body(xc, pp):
        out = {}
        cross_kv = {}
        for j, kind in enumerate(kinds):
            pj = pp[f"l{j}"]
            xc, out[f"l{j}"] = _sublayer_prefill(
                xc, pj, kind, cfg, ctx, positions, memory)
            if "cross" in pj and memory is not None:
                from .attention import _project_kv
                ck, cv = _project_kv(pj["cross"], memory, cfg)
                cross_kv[f"l{j}"] = {"k": ck.transpose(0, 2, 1, 3),
                                     "v": cv.transpose(0, 2, 1, 3)}
        return xc, (out, cross_kv)

    x, (collected, cross) = lax.scan(body, x, params["blocks"])
    hidden = rms_norm(x, params["final_norm"], cfg.rms_eps)
    return hidden, {"layers": collected, "cross": cross,
                    "len": x.shape[1]}


# ---------------------------------------------------------------------------
# decode caches
# ---------------------------------------------------------------------------

def init_cache(cfg, batch: int, prefix: int, mode: str = "sparse",
               abstract: bool = False) -> Dict[str, Any]:
    """Cache pytree for one period position x n_periods (stacked leading dim).

    mode "sparse": the paper's compressed frozen prefix + dense tail.
    mode "dense":  baseline preallocated cache of size prefix + tail.
    """
    p = period_len(cfg)
    n_periods = cfg.n_layers // p
    kinds = [layer_kind(cfg, j) for j in range(p)]
    hkv, hd = cfg.n_kv, cfg.hd
    dt = cfg.cdtype

    def attn_cache():
        if mode == "sparse":
            c = abstract_cache(batch, hkv, prefix, hd,
                               1.0 - cfg.kv_k_sparsity,
                               1.0 - cfg.kv_v_sparsity,
                               tail_size=cfg.kv_tail, dtype=dt)
            return c
        s_max = prefix + cfg.kv_tail
        k = jax.ShapeDtypeStruct((batch, hkv, s_max, hd), dt)
        return DenseKVCache(k, k, jax.ShapeDtypeStruct((), jnp.int32))

    def leaf_cache(kind):
        mixer, _ = kind
        if mixer == "attn":
            return {"kv": attn_cache()}
        if mixer == "mamba":
            st = mamba_init_state(cfg, batch)
            return {"state": jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), st)}
        st = rwkv_init_state(cfg, batch)
        return {"state": jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), st)}

    def stack(tree):
        return jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct((n_periods,) + tuple(s.shape),
                                           s.dtype), tree)

    cache = {"pos": jax.ShapeDtypeStruct((), jnp.int32),
             "layers": {f"l{j}": stack(leaf_cache(kinds[j]))
                        for j in range(p)}}
    if cfg.family == "encdec":
        # static cross K/V from the encoder (prefill-computed; ideal
        # candidates for the paper's frozen compressed format)
        kv = jax.ShapeDtypeStruct((n_periods, batch, hkv, prefix, hd), dt)
        cache["cross"] = {"k": kv, "v": kv}
    if abstract:
        return cache
    return jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype)
        if not isinstance(s, jax.Array) else s, cache)


# ---------------------------------------------------------------------------
# one-token decode step
# ---------------------------------------------------------------------------

def _sublayer_decode(x_t, p, cache_j, kind, cfg, ctx, position,
                     cross_kv=None):
    mixer, ffn = kind
    new_cache = dict(cache_j)
    if mixer == "rwkv":
        h, st = rwkv_time_mix_decode(
            p["tmix"], rms_norm(x_t, p["ln1"], cfg.rms_eps),
            cache_j["state"], cfg)
        x_t = x_t + h
        h, st = rwkv_channel_mix_decode(
            p["tmix"], rms_norm(x_t, p["ln2"], cfg.rms_eps), st, cfg)
        new_cache["state"] = st
        return x_t + h, new_cache

    h = rms_norm(x_t, p["ln1"], cfg.rms_eps)
    if mixer == "attn":
        h, kv = attn_decode(p["mixer"], h, cache_j["kv"], cfg, ctx, position)
        new_cache["kv"] = kv
    else:
        h, st = mamba_decode(p["mixer"], h, cache_j["state"], cfg)
        new_cache["state"] = st
    x_t = x_t + h
    if "cross" in p and cross_kv is not None:
        h = cross_attn_decode(
            p["cross"], rms_norm(x_t, p["ln_cross"], cfg.rms_eps),
            cross_kv[0], cross_kv[1], cfg)
        x_t = x_t + h
    h2 = rms_norm(x_t, p["ln2"], cfg.rms_eps)
    if ffn == "moe":
        h2 = moe_apply(p["ffn"], h2[:, None, :], cfg, ctx)[:, 0]
    else:
        h2 = mlp_apply(p["ffn"], h2)
    return x_t + h2, new_cache


def _attn_kinds(cfg) -> List[Tuple[str, str]]:
    if cfg.family == "encdec" or cfg.frontend:
        raise ValueError(
            "pooled serving has no cross-attention / frontend-embedding path")
    pl = period_len(cfg)
    kinds = [layer_kind(cfg, j) for j in range(pl)]
    if not all(k[0] == "attn" for k in kinds):
        raise ValueError(
            "pooled serving supports attention stacks (dense/moe families)")
    return kinds


def _pooled_ffn(pj, kind, h2, cfg, ctx):
    """The shared MLP/MoE half of a pooled panel block, flattened to rows.

    The flatten is a bit-exactness requirement, not a style choice: XLA
    fuses the SwiGLU epilogue differently at ``[B, 1, d]`` than at
    ``[B, d]`` (the silu·up product rounds through different fusions), so
    running the ``Q == 1`` panel at its natural rank would perturb bf16
    decode logits vs the pre-unification decode step.  Row-flattening
    makes the panel width invisible to the FFN — ``Q == 1`` compiles the
    exact 2-D program the old ``forward_decode_pooled`` ran.

    Deliberately NO sharding constraint on the silu·up hidden (``ctx`` is
    the MoE router's API argument only): pinning the ffn dim to the model
    axis would partial-sum + all-reduce the ``w_down`` contraction, and
    that reassociation breaks the sharded-vs-unsharded token-identity bar
    mesh serving asserts.  The rows stay data-sharded through the
    residual stream; with serving weights replicated, duplicating the FFN
    across the tensor axis is the explicit cost of exact parity (the
    TP-weights ROADMAP follow-up owns removing it).
    """
    lead = h2.shape[:-1]
    rows = h2.reshape(-1, h2.shape[-1])
    if kind[1] == "moe":
        out = moe_apply(pj["ffn"], rows[:, None, :], cfg, ctx)[:, 0]
    else:
        out = mlp_apply(pj["ffn"], rows)
    return out.reshape(*lead, out.shape[-1])


def forward_panel_pooled(params, state, tokens: jax.Array,
                         slot_mask: jax.Array, cfg, ctx, bs: int
                         ) -> Tuple[jax.Array, Any]:
    """THE per-token serving forward: score a ``[B, Qn]`` token panel per
    slot in ONE pass over the pooled serving cache.

    One function, three serving roles — the old ``forward_decode_pooled``
    and ``forward_verify_pooled`` scan bodies collapsed into this single
    panel path with a static ``Qn``:

    * ``Qn == 1`` — a plain decode tick (``tokens [B, 1]`` is each slot's
      last committed token); the ops layer squeezes the panel onto the
      exact single-query fused dispatch, so greedy decode stays
      bit-identical to the pre-unification engine;
    * ``Qn == K+1`` — a speculative verify step (``tokens[:, 1:]`` the
      padded draft window);
    * spec-off engines simply never build a ``Qn > 1`` trace.

    Panel position ``j`` decodes at absolute position ``pos + j`` with
    intra-window causal attention, so ``logits[:, j]`` is exactly what
    ``j`` sequential decode ticks past ``tokens[:, 0]`` would have
    produced for that continuation.  All ``Qn`` fresh K/V are appended
    and ``pos``/``tail_len`` advance by ``Qn`` per live slot — a caller
    that keeps fewer (speculative rejection) rolls the suffix back by a
    pure masked length decrement.  Masked slots are bit-identical
    passthrough, and every shape is static: one trace per
    (pool geometry, Qn), whatever the accept lengths turn out to be.

    Paged pool: the arena leaves stay out of the layer scan.  The body
    closes over the stacked ``[P, n_phys, Hkv, rows, 128]`` arena and takes
    its period index from the scan, and the kernel fetches each block
    where it lies; only the per-slot tails ride the scan.  The arena comes
    back as it went in (donated, its buffers are aliased, never rewritten).

    Returns (logits [B, Qn, V] f32, new state); unknown ``state`` keys
    (e.g. the sampler lanes) pass through untouched.
    """
    qn = tokens.shape[1]
    x = embed_apply(params["embed"], tokens, cfg)            # [B, Qn, d]
    x = ctx.constrain(x, ("batch", "seq", "embed"))
    kinds = _attn_kinds(cfg)
    positions = state["pos"][:, None] + jnp.arange(qn)[None, :]
    prefix_blocks = state["prefix_blocks"]
    tail_len = state["tail_len"]
    # paged pool: the block table and the stacked arena are pool-level
    # state threaded to every layer's attention, the layer's period index
    # rides the scan
    table = state.get("table")
    arena, scanned, layer_ids = _split_arena(state)

    def body(xc, xs):
        pp, cc, layer = xs
        new_cc = {}
        for j, kind in enumerate(kinds):
            pj, name = pp[f"l{j}"], f"l{j}"
            h = rms_norm(xc, pj["ln1"], cfg.rms_eps)
            h, new_kv = pooled_attn_panel(
                pj["mixer"], h, {**cc[name]["kv"], **arena.get(name, {})},
                cfg, ctx, positions, prefix_blocks, tail_len, slot_mask, bs,
                table=table, layer=layer)
            xc = xc + h
            xc = xc + _pooled_ffn(
                pj, kind, rms_norm(xc, pj["ln2"], cfg.rms_eps), cfg, ctx)
            new_cc[name] = {"kv": {k: new_kv[k] for k in cc[name]["kv"]}}
        return xc, new_cc

    x, new_layers = lax.scan(body, x,
                             (params["blocks"], scanned, layer_ids))
    new_layers = _join_arena(arena, new_layers)
    x = rms_norm(x, params["final_norm"], cfg.rms_eps)
    logits = unembed_apply(params["embed"], x, cfg)
    logits = ctx.constrain(logits, ("batch", None, "vocab"))
    grow = qn * slot_mask.astype(jnp.int32)
    new_state = {**state, "layers": new_layers,
                 "pos": positions[:, 0] + grow,
                 "tail_len": tail_len + grow}
    return logits, new_state


def _split_arena(state):
    """``(arena, scanned, layer_ids)`` of a pool state for a layer scan.

    Paged pool: ``arena`` holds each layer's stacked arena leaves, which
    the scan body closes over (fed to the scan as ``xs``, every layer's
    arena would be sliced out, and relaid out for the kernel, per step);
    ``scanned`` the remaining per-slot leaves; ``layer_ids`` the periods'
    indices, which the body addresses the arena by.  Flat pool: every leaf
    is per-slot and scanned, ``arena`` empty and ``layer_ids`` None."""
    layers = state["layers"]
    if "table" not in state:
        return {}, layers, None
    arena = {name: {k: leaf["kv"][k] for k in ARENA_KEYS}
             for name, leaf in layers.items()}
    scanned = {name: {"kv": {k: a for k, a in leaf["kv"].items()
                             if k not in ARENA_KEYS}}
               for name, leaf in layers.items()}
    n_periods = next(iter(arena.values()))["k_bitmap"].shape[0]
    return arena, scanned, jnp.arange(n_periods, dtype=jnp.int32)


def _join_arena(arena, layers):
    """The state's layers again: the scan's per-slot leaves beside the
    arena leaves, which come back as they went in."""
    return {name: {"kv": {**arena.get(name, {}), **leaf["kv"]}}
            for name, leaf in layers.items()}


def forward_prefill_chunk(params, state, tokens: jax.Array, slot: jax.Array,
                          cfg, ctx, bs: int,
                          new_ids: Optional[jax.Array] = None,
                          caps: Optional[Tuple[int, int]] = None
                          ) -> Tuple[jax.Array, Any]:
    """Prefill one prompt chunk for a single slot of the pooled cache.

    tokens [1, C]; slot scalar int32.  The chunk attends to the slot's
    already-frozen prefix, then its full (bs)-token blocks are pruned +
    packed straight into the slot's prefix storage at the pool's static
    capacity; a trailing remainder (< bs tokens — last chunk only) lands in
    the dense tail.  One ``jax.jit`` trace per distinct chunk length; the
    slot index and start position are traced values, so admitting a request
    into *any* slot at *any* offset reuses the same compiled step.
    Returns (last-token logits [1, V], new state) — the engine samples the
    request's first token from these logits under the slot's lane; unknown
    ``state`` keys pass through untouched.

    Paged pool (``state`` carries a block table): the slot attends to its
    prefix THROUGH its table row (blocks a cache hit pointed at were
    frozen by other requests), gathered out of the stacked arena the layer
    scan closes over, and the chunk's ``C // bs`` new blocks are frozen
    into FRESH arena pages ``new_ids`` (int32 ``[C // bs]``,
    host-allocated) appended to the table row — never into shared storage,
    which is the copy-on-write guarantee.  Only those pages and the
    gathered ones are ever reshaped, never the arena.

    ``caps`` is the pool's packed ``(K, V)`` values per block.  A flat
    pool's leaves hold exactly that many, so it may be left out; the paged
    arena stores whole padded tiles, so a paged pool must pass it.
    """
    c = tokens.shape[1]
    nb_new, rem = c // bs, c % bs
    kinds = _attn_kinds(cfg)
    paged = "table" in state
    x = embed_apply(params["embed"], tokens, cfg)            # [1, C, d]
    start = jnp.take(state["pos"], slot)
    pb0 = jnp.take(state["prefix_blocks"], slot)
    positions = start + jnp.arange(c)
    ctx_len = pb0 * bs
    if paged:
        if new_ids is None and nb_new != 0:
            raise ValueError(
                "paged prefill needs fresh arena ids for its full blocks")
        if caps is None:
            raise ValueError("paged prefill needs the pool's capacities: "
                             "the arena's tiles are padded")
        sb = state["table"].shape[1]
        table_row = lax.dynamic_slice(
            state["table"], (slot, jnp.int32(0)), (1, sb))[0]
    else:
        table_row = None
    # the arena leaves are pool-global (the body closes over them); only
    # the per-slot leaves slice
    arena, scanned, layer_ids = _split_arena(state)
    slot_layers = jax.tree_util.tree_map(
        lambda a: lax.dynamic_slice_in_dim(a, slot, 1, axis=1), scanned)

    def body(xc, xs):
        pp, cc, layer = xs
        chunk_kv = {}
        for j, kind in enumerate(kinds):
            pj, name = pp[f"l{j}"], f"l{j}"
            h = rms_norm(xc, pj["ln1"], cfg.rms_eps)
            h, k_c, v_c = pooled_attn_prefill_chunk(
                pj["mixer"], h, {**cc[name]["kv"], **arena.get(name, {})},
                cfg, ctx, positions, ctx_len, bs, table_row=table_row,
                layer=layer)
            xc = xc + h
            h2 = rms_norm(xc, pj["ln2"], cfg.rms_eps)
            if kind[1] == "moe":
                h2 = moe_apply(pj["ffn"], h2, cfg, ctx)
            else:
                h2 = mlp_apply(pj["ffn"], h2, ctx)
            xc = xc + h2
            chunk_kv[name] = {"k": k_c, "v": v_c}
        return xc, chunk_kv

    x, chunk_kv = lax.scan(body, x,
                           (params["blocks"], slot_layers, layer_ids))
    hidden = rms_norm(x, params["final_norm"], cfg.rms_eps)
    logits = logits_fn(params, hidden[:, -1:], cfg, ctx)[:, 0]

    from repro.core.sparse_kv import freeze_chunk_blocks
    if paged and nb_new:
        new_ids = jnp.asarray(new_ids, jnp.int32)            # [nb_new]
    new_layers = {}
    for name, leaf in state["layers"].items():
        kv = dict(leaf["kv"])
        ck, cv = chunk_kv[name]["k"], chunk_kv[name]["v"]    # [P,1,Hkv,C,hd]
        p_, _, hkv, _, hd = ck.shape
        if nb_new:
            cap_k, cap_v = caps or (kv["k_values"].shape[-1],
                                    kv["v_values"].shape[-1])
            k_bm, k_vl, v_bm, v_vl = freeze_chunk_blocks(
                ck[:, 0, :, :nb_new * bs], cv[:, 0, :, :nb_new * bs],
                cfg.kv_k_sparsity, cfg.kv_v_sparsity, bs, cap_k, cap_v)
            for key, upd in (("k_bitmap", k_bm), ("k_values", k_vl),
                             ("v_bitmap", v_bm), ("v_values", v_vl)):
                if paged:
                    # [P, Hkv, nb, X] -> [P, nb, Hkv, rows, 128] tiles into
                    # the fresh arena pages (never shared storage: CoW)
                    kv[key] = kv[key].at[:, new_ids].set(
                        to_arena_tiles(upd, kv[key].shape[-2])
                        .transpose(0, 2, 1, 3, 4).astype(kv[key].dtype))
                else:
                    kv[key] = lax.dynamic_update_slice(
                        kv[key], upd[:, None].astype(kv[key].dtype),
                        (0, slot, 0, pb0, 0))
        if rem:
            for key, src in (("k_tail", ck), ("v_tail", cv)):
                kv[key] = lax.dynamic_update_slice(
                    kv[key], src[:, :, :, nb_new * bs:].astype(
                        kv[key].dtype),
                    (0, slot, 0, 0, 0))
        new_layers[name] = {"kv": kv}

    new_state = {**state, "layers": new_layers,
                 "pos": state["pos"].at[slot].set(start + c),
                 "prefix_blocks":
                     state["prefix_blocks"].at[slot].set(pb0 + nb_new),
                 "tail_len": state["tail_len"].at[slot].set(rem)}
    if paged and nb_new:
        new_state["table"] = lax.dynamic_update_slice(
            state["table"], new_ids[None], (slot, pb0))
        new_state["refcount"] = state["refcount"].at[new_ids].add(1)
    return logits, new_state


def forward_decode(params, cache, tokens: jax.Array, cfg, ctx
                   ) -> Tuple[jax.Array, Any]:
    """tokens [B, 1] -> (logits [B, V] f32, updated cache)."""
    b = tokens.shape[0]
    x_t = embed_apply(params["embed"], tokens[:, 0], cfg)
    x_t = ctx.constrain(x_t, ("batch", "embed"))
    position = cache["pos"]
    pl = period_len(cfg)
    kinds = [layer_kind(cfg, j) for j in range(pl)]
    has_cross = cfg.family == "encdec"

    def body(xc, xs):
        pp, cc, cross = xs
        new_cc = {}
        for j, kind in enumerate(kinds):
            ck = (cross["k"], cross["v"]) if has_cross else None
            xc, new_cc[f"l{j}"] = _sublayer_decode(
                xc, pp[f"l{j}"], cc[f"l{j}"], kind, cfg, ctx, position, ck)
        return xc, new_cc

    n_periods = cfg.n_layers // pl
    xs = (params["blocks"], cache["layers"],
          cache["cross"] if has_cross else
          {"k": jnp.zeros((n_periods, 0)), "v": jnp.zeros((n_periods, 0))})
    x_t, new_layers = lax.scan(body, x_t, xs)
    x_t = rms_norm(x_t, params["final_norm"], cfg.rms_eps)
    logits = unembed_apply(params["embed"], x_t, cfg)
    logits = ctx.constrain(logits, ("batch", "vocab"))
    new_cache = dict(cache)
    new_cache["layers"] = new_layers
    new_cache["pos"] = position + 1
    return logits, new_cache
