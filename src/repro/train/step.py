"""Training step builders: loss, grads, optimizer, microbatch accumulation,
and the compressed-DP variant (gradient compression + error feedback).

The loss computes logits in sequence chunks so the [B, S, vocab] tensor
(53 GB for llama4-scout at train_4k) never materializes — each chunk is
vocab-sharded over the model axis and reduced immediately.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.models import lm
from repro.optim import (OptConfig, adamw_step, init_opt_state,
                         compress_and_reduce)


def chunked_ce_loss(params, hidden: jax.Array, labels: jax.Array,
                    mask: jax.Array, cfg, ctx, chunk: int = 1024
                    ) -> jax.Array:
    """hidden [B, S, d] -> scalar mean CE.  Never materializes [B,S,V]."""
    b, s, d = hidden.shape
    if s % chunk != 0:
        chunk = s
    n = s // chunk
    h_c = hidden.reshape(b, n, chunk, d).transpose(1, 0, 2, 3)
    l_c = labels.reshape(b, n, chunk).transpose(1, 0, 2)
    m_c = mask.reshape(b, n, chunk).transpose(1, 0, 2)

    def body(carry, xs):
        h, lab, m = xs
        logits = lm.logits_fn(params, h, cfg, ctx)         # [B, c, V] f32
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, lab[..., None],
                                   axis=-1)[..., 0]
        loss = jnp.sum((lse - gold) * m)
        return (carry[0] + loss, carry[1] + jnp.sum(m)), None

    (tot, cnt), _ = lax.scan(body, (jnp.zeros(()), jnp.zeros(())),
                             (h_c, l_c, m_c))
    return tot / jnp.maximum(cnt, 1.0)


def loss_fn(params, batch: Dict[str, jax.Array], cfg, ctx,
            attn_impl: str = None) -> jax.Array:
    attn_impl = attn_impl or getattr(cfg, "attn_impl", "masked")
    hidden = lm.forward_train(params, batch, cfg, ctx, attn_impl=attn_impl)
    labels, mask = batch["labels"], batch["mask"]
    if cfg.frontend and "frontend_embeds" in batch:
        # loss only over text positions (frontend prefix is input-only)
        hidden = hidden[:, batch["frontend_embeds"].shape[1]:]
    return chunked_ce_loss(params, hidden, labels, mask, cfg, ctx)


def make_train_step(cfg, ctx, optc: OptConfig,
                    microbatch: Optional[int] = None,
                    attn_impl: str = None) -> Callable:
    """(params, opt_state, batch) -> (params, opt_state, metrics)."""

    def grads_of(params, batch):
        return jax.value_and_grad(
            lambda p: loss_fn(p, batch, cfg, ctx, attn_impl))(params)

    def step(params, opt_state, batch):
        if microbatch is None:
            loss, grads = grads_of(params, batch)
        else:
            b = batch["tokens"].shape[0]
            n = b // microbatch
            mb = jax.tree_util.tree_map(
                lambda a: a.reshape(n, microbatch, *a.shape[1:]), batch)

            def acc_body(carry, xs):
                loss_acc, g_acc = carry
                loss, g = grads_of(params, xs)
                g_acc = jax.tree_util.tree_map(
                    lambda a, b_: a + b_.astype(jnp.float32) / n, g_acc, g)
                return (loss_acc + loss / n, g_acc), None

            g0 = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (loss, grads), _ = lax.scan(acc_body, (jnp.zeros(()), g0), mb)
        params, opt_state, mets = adamw_step(grads, opt_state, optc,
                                             params_like=params)
        return params, opt_state, {"loss": loss, **mets}

    return step


# ---------------------------------------------------------------------------
# compressed-DP train step (gradient compression + error feedback)
# ---------------------------------------------------------------------------

def make_compressed_grads(cfg, ctx, scheme: str = "bf16",
                          attn_impl: str = "masked") -> Callable:
    """(params, err_state, batch) -> (loss, grads, new_err).

    Runs loss+backward per DP shard inside shard_map (manual over the DP
    axes, auto over model) and reduces compressed gradients explicitly —
    the DCN-crossing reduce operand in the HLO is bf16/int8, not fp32.
    Requires cfg.fsdp == False (params replicated across DP).
    """
    if cfg.fsdp:
        raise ValueError("compressed-DP requires DP-replicated params")
    mesh = ctx.mesh
    dp = ctx.rules.get("batch")
    dp = tuple(dp) if isinstance(dp, (tuple, list)) else (dp,)
    # manual over the DP axes; size-1 axes included so CPU test meshes run
    # full-manual (XLA CPU miscompiles partial-auto shard_map; on TPU the
    # model axis stays auto and composes with TP).
    manual = set(dp) | {a for a in mesh.axis_names if mesh.shape[a] == 1}
    # inside the manual region, sharding constraints must not mention
    # manual axes: strip them from the model-visible rules
    from repro.distributed.sharding import ShardCtx as _Ctx

    def _strip(v):
        axes = tuple(a for a in (v if isinstance(v, (tuple, list)) else (v,))
                     if a is not None and a not in manual)
        return axes if len(axes) > 1 else (axes[0] if axes else None)

    inner_rules = {k: _strip(v) for k, v in ctx.rules.items()}
    inner_ctx = _Ctx(None, {}) if all(v is None for v in
                                      inner_rules.values()) \
        else _Ctx(mesh, inner_rules)

    def body(params, err_local, batch_local):
        err = jax.tree_util.tree_map(lambda e: e[0], err_local)
        loss, grads = jax.value_and_grad(
            lambda p: loss_fn(p, batch_local, cfg, inner_ctx,
                              attn_impl))(params)
        g_hat, new_err = compress_and_reduce(grads, err, dp, scheme)
        loss = jax.lax.pmean(loss, dp)
        new_err = jax.tree_util.tree_map(lambda e: e[None], new_err)
        return loss, g_hat, new_err

    rep = lambda tree: jax.tree_util.tree_map(lambda _: P(), tree)

    def fn(params, err_state, batch):
        return jax.shard_map(
            body, mesh=mesh,
            in_specs=(rep(params),
                      jax.tree_util.tree_map(lambda _: P(dp), err_state),
                      jax.tree_util.tree_map(lambda _: P(dp), batch)),
            out_specs=(P(), rep(params),
                       jax.tree_util.tree_map(lambda _: P(dp), err_state)),
            axis_names=manual, check_vma=False,
        )(params, err_state, batch)

    return fn


def init_dp_error_state(params, dp_size: int):
    """Per-DP-shard error-feedback buffers: leading dp dim, sharded over DP."""
    return jax.tree_util.tree_map(
        lambda p: jnp.zeros((dp_size,) + p.shape, jnp.float32), params)
