"""Shared model components: RMSNorm, RoPE, embeddings, SwiGLU MLP.

All matmuls route through ``repro.kernels.ops.linear`` so the paper's
sparse-format weights drop in transparently after ``convert_to_sparse``.
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.kernels import ops
from .module import ParamSpec


def rms_norm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    """RMSNorm over the last axis; ``eps`` is the model's own
    (``ArchConfig.rms_eps``)."""
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)
            ).astype(x.dtype)


def rope_angles(positions: jax.Array, hd: int, theta: float):
    """positions [...,] -> (cos, sin) of shape [..., hd//2] (f32)."""
    freq = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = positions.astype(jnp.float32)[..., None] * freq
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x [..., S, H, D] with cos/sin [..., S, D//2] (broadcast over H)."""
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    c = cos[..., None, :]
    s = sin[..., None, :]
    # static half-rotate (shape never varies per token)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s],  # jitlint: disable=hot-path-op
                           axis=-1).astype(x.dtype)


# ---------------------------------------------------------------------------
# embeddings
# ---------------------------------------------------------------------------

def embed_specs(cfg) -> Dict[str, ParamSpec]:
    d = cfg.pdtype
    specs = {"tok": ParamSpec((cfg.vocab, cfg.d_model), d,
                              ("vocab", "embed"), init="embed")}
    if not cfg.tie_embeddings:
        specs["lm_head"] = ParamSpec((cfg.d_model, cfg.vocab), d,
                                     ("embed", "vocab"))
    return specs


def embed_apply(p, tokens: jax.Array, cfg) -> jax.Array:
    return jnp.take(p["tok"], tokens, axis=0).astype(cfg.cdtype)


def unembed_apply(p, x: jax.Array, cfg) -> jax.Array:
    w = p["tok"].T.astype(cfg.cdtype) if cfg.tie_embeddings else p["lm_head"]
    # ops.linear dispatches on the leaf type (dense / sparse-bf16 / int8 /
    # packed4); never swallow kernel errors behind a silent dense fallback
    logits = ops.linear(x, w, out_dtype=jnp.float32)
    if logits.shape[-1] != cfg.vocab:
        # a head padded to whole lane tiles (pad_output_head): its zero
        # columns are no tokens, so they never reach the sampler
        logits = logits[..., :cfg.vocab]
    return logits


HEAD_ALIGN = 128      # lane width: the dense kernel's output tile


def pad_output_head(params, cfg):
    """``params`` with an untied output head whose vocabulary is not a
    whole number of ``HEAD_ALIGN`` columns padded with zero columns to one.
    The dense kernel pads its weight to whole tiles on every call, which on
    a decode step copies the whole head; padded once here, it never does
    (``unembed_apply`` slices the logits back to ``cfg.vocab``)."""
    w = None if cfg.tie_embeddings else params["embed"]["lm_head"]
    if not isinstance(w, jax.Array) or w.shape[-1] % HEAD_ALIGN == 0:
        return params
    pad = -w.shape[-1] % HEAD_ALIGN
    head = jnp.pad(w, ((0, 0), (0, pad)))
    return {**params, "embed": {**params["embed"], "lm_head": head}}


# ---------------------------------------------------------------------------
# SwiGLU MLP
# ---------------------------------------------------------------------------

def mlp_specs(cfg, d_in: Optional[int] = None,
              d_ff: Optional[int] = None) -> Dict[str, ParamSpec]:
    d_in = d_in or cfg.d_model
    d_ff = d_ff or cfg.d_ff
    dt = cfg.pdtype
    return {
        "w_gate": ParamSpec((d_in, d_ff), dt, ("embed", "ffn")),
        "w_up": ParamSpec((d_in, d_ff), dt, ("embed", "ffn")),
        "w_down": ParamSpec((d_ff, d_in), dt, ("ffn", "embed")),
    }


def mlp_apply(p, x: jax.Array, ctx=None) -> jax.Array:
    h = jax.nn.silu(ops.linear(x, p["w_gate"])) * ops.linear(x, p["w_up"])
    if ctx is not None:
        h = ctx.constrain(h, ("batch", "seq", "ffn"))
    return ops.linear(h, p["w_down"])


def norm_spec(cfg, d: Optional[int] = None) -> ParamSpec:
    return ParamSpec((d or cfg.d_model,), jnp.float32, ("embed",),
                     init="ones")
