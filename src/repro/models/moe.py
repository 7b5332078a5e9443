"""Mixture-of-Experts layer: top-k routing, capacity-based, sort-free dispatch.

Dispatch is memory-sane (no (T, E, C) one-hot einsum): per top-k slot, each
token's position in its expert queue comes from an exclusive cumsum over the
(T, E) one-hot, tokens are gathered into an (E, C, d) buffer, experts run as
a stacked einsum, and results scatter-add back with the routing weights.

Distribution (DESIGN.md §6): the dispatch math runs *per data shard* inside
``shard_map`` — tokens never cross the data axis (baseline; expert-parallel
all-to-all is the §Perf variant).  Expert FFNs are tensor-parallel on the ffn
dim with a single reduce(-scatter) per layer, Megatron-SP style when the
residual stream is sequence-sharded.
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.sparse_format import BlockSparseWeight, unpack
from repro.kernels import ops
from .module import ParamSpec
from .layers import mlp_specs, mlp_apply


def moe_specs(cfg) -> Dict[str, ParamSpec]:
    d, f, e = cfg.d_model, cfg.d_ff, cfg.n_experts
    dt = cfg.pdtype
    specs = {
        "router": ParamSpec((d, e), jnp.float32, ("embed", None)),
        "w_gate": ParamSpec((e, d, f), dt, ("experts", "embed", "ffn")),
        "w_up": ParamSpec((e, d, f), dt, ("experts", "embed", "ffn")),
        "w_down": ParamSpec((e, f, d), dt, ("experts", "ffn", "embed")),
    }
    if cfg.shared_expert:
        specs["shared"] = mlp_specs(cfg)
    return specs


def _expert_w(w, e: int):
    """Dense (E, K, N) view of a (possibly sparse) expert weight."""
    if isinstance(w, BlockSparseWeight):
        dense = unpack(w)                       # (E*K, N) — XLA fallback
        return dense.reshape(e, dense.shape[0] // e, dense.shape[1])
    return w


def _capacity(t: int, k: int, e: int, cf: float) -> int:
    c = int(-(-t * k * cf // e))
    return max(-(-c // 8) * 8, 8)


def moe_local(p, x: jax.Array, cfg, tp_axis: Optional[str] = None
              ) -> jax.Array:
    """Token dispatch + expert FFN on local tokens x [T, d].

    If ``tp_axis`` is set, w_gate/w_up/w_down arrive ffn-sliced and the
    partial down-projection output is NOT reduced here (caller reduces).
    """
    t, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    c = _capacity(t, k, e, cfg.capacity_factor)

    logits = jnp.dot(x.astype(jnp.float32), p["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, k)                    # (T, k)
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)

    wg = _expert_w(p["w_gate"], e)
    wu = _expert_w(p["w_up"], e)
    wd = _expert_w(p["w_down"], e)

    # static one-row sentinel pad, not a growing buffer
    x_pad = jnp.concatenate([x, jnp.zeros((1, d), x.dtype)], axis=0)  # jitlint: disable=hot-path-op
    out = jnp.zeros((t + 1, d), jnp.float32)
    for slot in range(k):
        eid = top_i[:, slot]                                  # (T,)
        oh = jax.nn.one_hot(eid, e, dtype=jnp.int32)          # (T, E)
        pos_all = jnp.cumsum(oh, axis=0) - oh
        pos = jnp.take_along_axis(pos_all, eid[:, None], axis=1)[:, 0]
        keep = pos < c
        buf = jnp.full((e, c), t, jnp.int32)
        buf = buf.at[eid, jnp.where(keep, pos, c)].set(
            jnp.arange(t, dtype=jnp.int32), mode="drop")      # (E, C)
        xg = x_pad[buf]                                       # (E, C, d)
        h = (jax.nn.silu(jnp.einsum("ecd,edf->ecf", xg, wg))
             * jnp.einsum("ecd,edf->ecf", xg, wu)).astype(x.dtype)
        o = jnp.einsum("ecf,efd->ecd", h, wd)                 # (E, C, d)
        wcomb = jnp.concatenate(  # jitlint: disable=hot-path-op
            [top_p[:, slot], jnp.zeros((1,), jnp.float32)])[buf]
        out = out.at[buf.reshape(-1)].add(
            (o * wcomb[..., None]).reshape(-1, d), mode="drop")
    return out[:t].astype(x.dtype)


def moe_apply(p, x: jax.Array, cfg, ctx) -> jax.Array:
    """x [B, S, d] -> [B, S, d].  shard_map'd dispatch when a mesh is live."""
    b, s, d = x.shape
    if ctx is None or ctx.mesh is None:
        out = moe_local(p, x.reshape(-1, d), cfg).reshape(b, s, d)
        if cfg.shared_expert:
            out = out + mlp_apply(p["shared"], x)
        return out
    if getattr(cfg, "ep_moe", False):
        out = moe_apply_ep(p, x, cfg, ctx)
        if out is not None:
            return out

    mesh = ctx.mesh
    dp = ctx.rules.get("batch")
    dp = tuple(dp) if isinstance(dp, (tuple, list)) else (dp,)
    dp = tuple(a for a in dp if a is not None)
    dp_size = 1
    for a in dp:
        dp_size *= mesh.shape[a]
    if dp_size == 1 or b % dp_size != 0:
        dp = ()   # e.g. batch=1 long-context decode: replicate dispatch
    tp = ctx.rules.get("ffn")
    tp_size = mesh.shape[tp] if tp else 1
    seq_sharded = (cfg.seq_shard and tp and s % tp_size == 0 and s > 1)

    x_spec = P(dp if dp else None, tp if seq_sharded else None, None)
    w_col = P(None, None, tp)       # (E, d, f_local)
    w_row = P(None, tp, None)       # (E, f_local, d)
    p_specs = {"router": P(None, None), "w_gate": w_col, "w_up": w_col,
               "w_down": w_row}
    if cfg.shared_expert:
        p_specs["shared"] = {"w_gate": P(None, tp), "w_up": P(None, tp),
                             "w_down": P(tp, None)}
    moe_p = {k: p[k] for k in p_specs}

    def body(pl, xl):
        # xl: (B_local, S or S/tp, d)
        bl = xl.shape[0]
        if seq_sharded:
            xl = jax.lax.all_gather(xl, tp, axis=1, tiled=True)
        tok = xl.reshape(-1, d)
        out = moe_local(pl, tok, cfg, tp_axis=tp)
        if cfg.shared_expert:
            h = (jax.nn.silu(ops.linear(tok, pl["shared"]["w_gate"]))
                 * ops.linear(tok, pl["shared"]["w_up"]))
            out = out + ops.linear(h, pl["shared"]["w_down"])
        out = out.reshape(bl, -1, d)
        if tp:
            if seq_sharded:
                out = jax.lax.psum_scatter(out, tp, scatter_dimension=1,
                                           tiled=True)
            else:
                out = jax.lax.psum(out, tp)
        return out

    fn = jax.shard_map(body, mesh=mesh, in_specs=(p_specs, x_spec),
                           out_specs=x_spec, check_vma=False)
    return fn(moe_p, x)


# ---------------------------------------------------------------------------
# expert-parallel variant (§Perf: kills the FSDP expert-weight all-gathers)
# ---------------------------------------------------------------------------

def moe_apply_ep(p, x: jax.Array, cfg, ctx):
    """Experts sharded over the DP axes (E/ep per group), ffn over TP.

    Weights stay resident (no per-step gathers).  Tokens are all-gathered
    over DP inside the region (activations << expert weights), each group
    computes only its local experts' contributions, and one
    psum(+scatter) over (dp, tp) combines.  Returns None when E doesn't
    divide the DP degree (caller falls back to the TP path)."""
    mesh = ctx.mesh
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    dp = ctx.rules.get("batch")
    dp = tuple(a for a in (dp if isinstance(dp, (tuple, list)) else (dp,))
               if a is not None)
    ep_size = 1
    for a in dp:
        ep_size *= mesh.shape[a]
    if ep_size <= 1 or e % ep_size != 0:
        return None
    e_loc = e // ep_size
    tp = ctx.rules.get("ffn")
    b_sharded = b % ep_size == 0
    x_spec = P(dp if b_sharded else None, None, None)
    w_col = P(dp, None, tp)      # (E_loc, d, f_loc)
    w_row = P(dp, tp, None)
    p_specs = {"router": P(None, None), "w_gate": w_col, "w_up": w_col,
               "w_down": w_row}
    if cfg.shared_expert:
        p_specs["shared"] = {"w_gate": P(None, tp), "w_up": P(None, tp),
                             "w_down": P(tp, None)}
    moe_p = {key: p[key] for key in p_specs}

    def body(pl, xl):
        bl = xl.shape[0]
        if b_sharded:
            xl = jax.lax.all_gather(xl, dp, axis=0, tiled=True)
        tok = xl.reshape(-1, d)
        t = tok.shape[0]
        c = _capacity(t, k, e, cfg.capacity_factor)
        idx = 0
        for a in dp:
            idx = idx * mesh.shape[a] + jax.lax.axis_index(a)
        e0 = idx * e_loc

        logits = jnp.dot(tok.astype(jnp.float32), pl["router"])
        probs = jax.nn.softmax(logits, axis=-1)
        top_p, top_i = jax.lax.top_k(probs, k)
        top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)

        wg = _expert_w(pl["w_gate"], e_loc)
        wu = _expert_w(pl["w_up"], e_loc)
        wd = _expert_w(pl["w_down"], e_loc)
        x_pad = jnp.concatenate([tok, jnp.zeros((1, d), tok.dtype)], axis=0)  # jitlint: disable=hot-path-op
        out = jnp.zeros((t + 1, d), jnp.float32)
        for slot in range(k):
            eid = top_i[:, slot]
            mine = (eid >= e0) & (eid < e0 + e_loc)
            le = jnp.where(mine, eid - e0, e_loc)          # E_loc = drop
            oh = jax.nn.one_hot(jnp.where(mine, le, e_loc), e_loc + 1,
                                dtype=jnp.int32)[:, :e_loc]
            pos_all = jnp.cumsum(oh, axis=0) - oh
            pos = jnp.take_along_axis(
                pos_all, jnp.minimum(le, e_loc - 1)[:, None], axis=1)[:, 0]
            keep = mine & (pos < c)
            buf = jnp.full((e_loc, c), t, jnp.int32)
            buf = buf.at[jnp.where(mine, le, e_loc),
                         jnp.where(keep, pos, c)].set(
                jnp.arange(t, dtype=jnp.int32), mode="drop")
            xg = x_pad[buf]
            h = (jax.nn.silu(jnp.einsum("ecd,edf->ecf", xg, wg))
                 * jnp.einsum("ecd,edf->ecf", xg, wu)).astype(tok.dtype)
            o = jnp.einsum("ecf,efd->ecd", h, wd)
            wcomb = jnp.concatenate(  # jitlint: disable=hot-path-op
                [top_p[:, slot], jnp.zeros((1,), jnp.float32)])[buf]
            out = out.at[buf.reshape(-1)].add(
                (o * wcomb[..., None]).reshape(-1, d), mode="drop")
        out = out[:t]
        if cfg.shared_expert:
            hsh = (jax.nn.silu(ops.linear(tok, pl["shared"]["w_gate"]))
                   * ops.linear(tok, pl["shared"]["w_up"]))
            sh = ops.linear(hsh, pl["shared"]["w_down"]).astype(jnp.float32)
            out = out + jnp.where(idx == 0, 1.0, 0.0) * sh
        out = out.reshape(-1, s, d)
        if tp:
            out = jax.lax.psum(out, tp)
        if b_sharded:
            out = jax.lax.psum_scatter(out, dp, scatter_dimension=0,
                                       tiled=True)
        else:
            out = jax.lax.psum(out, dp)
        return out.astype(x.dtype)

    fn = jax.shard_map(body, mesh=mesh, in_specs=(p_specs, x_spec),
                           out_specs=x_spec, check_vma=False)
    return fn(moe_p, x)
