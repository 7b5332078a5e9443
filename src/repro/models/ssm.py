"""Recurrent mixers: Mamba (Jamba's 7-of-8 layers) and RWKV-6 "Finch".

Both are expressed as chunked ``lax.scan`` over time with
``jax.checkpoint`` on the inner chunk, so the backward pass stores one
carry per ``cfg.scan_chunk`` steps instead of per step (this is what makes
train_4k fit; see EXPERIMENTS.md §Dry-run).  Decode is the single-step
recurrence — O(1) state, which is why these archs run the long_500k cell.

All projections route through ``ops.linear`` and are therefore
sparse-format capable (the paper's technique applies to every linear here;
for RWKV decode the model is *nothing but* these GEMVs — see DESIGN.md §5).
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.kernels import ops
from .module import ParamSpec
from .layers import rms_norm


def _chunked_scan(step, carry, xs_t, chunk: int, remat: bool = True):
    """scan over leading time axis of xs_t in remat'd chunks."""
    t = jax.tree_util.tree_leaves(xs_t)[0].shape[0]
    if t % chunk != 0 or t <= chunk:
        return lax.scan(step, carry, xs_t)
    n = t // chunk
    xs_c = jax.tree_util.tree_map(
        lambda a: a.reshape(n, chunk, *a.shape[1:]), xs_t)

    def chunk_step(c, xs):
        return lax.scan(step, c, xs)

    if remat:
        chunk_step = jax.checkpoint(chunk_step,
                                    prevent_cse=False)
    carry, ys = lax.scan(chunk_step, carry, xs_c)
    ys = jax.tree_util.tree_map(
        lambda a: a.reshape(n * chunk, *a.shape[2:]), ys)
    return carry, ys


# ===========================================================================
# Mamba (selective SSM), as used by Jamba
# ===========================================================================

def mamba_specs(cfg) -> Dict[str, ParamSpec]:
    d, di, n, dc = cfg.d_model, cfg.d_inner, cfg.d_state, cfg.d_conv
    rank = max(d // 16, 8)
    dt = cfg.pdtype
    return {
        "w_in": ParamSpec((d, 2 * di), dt, ("embed", "ssm_inner")),
        "conv_w": ParamSpec((dc, di), jnp.float32, (None, "ssm_inner"),
                            init="small"),
        "conv_b": ParamSpec((di,), jnp.float32, ("ssm_inner",), init="zeros"),
        "w_bcdt": ParamSpec((di, rank + 2 * n), dt, ("ssm_inner", None)),
        "dt_w": ParamSpec((rank, di), jnp.float32, (None, "ssm_inner"),
                          init="small"),
        "dt_b": ParamSpec((di,), jnp.float32, ("ssm_inner",), init="zeros"),
        "a_log": ParamSpec((di, n), jnp.float32, ("ssm_inner", "state"),
                           init="zeros"),
        "d_skip": ParamSpec((di,), jnp.float32, ("ssm_inner",), init="ones"),
        "w_out": ParamSpec((di, d), dt, ("ssm_inner", "embed")),
    }


def _mamba_conv_train(x: jax.Array, w: jax.Array, b: jax.Array) -> jax.Array:
    """Causal depthwise conv over seq: x [B,S,di], w [dc,di]."""
    dc = w.shape[0]
    out = x * w[dc - 1]
    for i in range(1, dc):
        shifted = jnp.pad(x, ((0, 0), (i, 0), (0, 0)))[:, :-i]
        out = out + shifted * w[dc - 1 - i]
    return out + b


def _mamba_step(carry, xs, a, d_skip):
    """h' = dA h + dB x; y = C.h + D x.  Shapes: h [B,di,N]."""
    h = carry
    xc_t, dt_t, b_t, c_t = xs          # [B,di], [B,di], [B,N], [B,N]
    da = jnp.exp(dt_t[..., None] * a)                       # [B,di,N]
    db = dt_t[..., None] * b_t[:, None, :]                  # [B,di,N]
    h = da * h + db * xc_t[..., None]
    y = jnp.einsum("bdn,bn->bd", h, c_t) + d_skip * xc_t
    return h, y


def mamba_apply(p, x: jax.Array, cfg, ctx, return_state: bool = False):
    """Train/prefill path. x [B, S, d] -> [B, S, d]."""
    b, s, d = x.shape
    di, n = cfg.d_inner, cfg.d_state
    rank = p["dt_w"].shape[0]
    xz = ops.linear(x, p["w_in"])
    x_in, z = jnp.split(xz, 2, axis=-1)                      # [B,S,di]
    xc = jax.nn.silu(_mamba_conv_train(
        x_in.astype(jnp.float32), p["conv_w"], p["conv_b"]))
    bcdt = ops.linear(xc.astype(x.dtype), p["w_bcdt"]).astype(jnp.float32)
    dt_lo, b_ssm, c_ssm = jnp.split(bcdt, [rank, rank + n], axis=-1)
    dt = jax.nn.softplus(jnp.einsum("bsr,rd->bsd", dt_lo, p["dt_w"])
                         + p["dt_b"])                        # [B,S,di]
    a = -jnp.exp(p["a_log"])                                 # [di,N]

    to_t = lambda v: jnp.moveaxis(v, 1, 0)                   # time-major
    xs_t = (to_t(xc), to_t(dt), to_t(b_ssm), to_t(c_ssm))
    h0 = jnp.zeros((b, di, n), jnp.float32)
    step = lambda c, xs: _mamba_step(c, xs, a, p["d_skip"])
    h_fin, ys = _chunked_scan(step, h0, xs_t, cfg.scan_chunk, cfg.remat)
    y = jnp.moveaxis(ys, 0, 1)                               # [B,S,di]
    y = (y * jax.nn.silu(z.astype(jnp.float32))).astype(x.dtype)
    out = ops.linear(y, p["w_out"])
    if return_state:
        dc = cfg.d_conv
        conv = x_in.astype(jnp.float32)[:, -(dc - 1):]
        return out, {"conv": conv, "ssm": h_fin}
    return out


def mamba_init_state(cfg, batch: int, dtype=jnp.float32):
    return {
        "conv": jnp.zeros((batch, cfg.d_conv - 1, cfg.d_inner), dtype),
        "ssm": jnp.zeros((batch, cfg.d_inner, cfg.d_state), dtype),
    }


def mamba_decode(p, x_t: jax.Array, state, cfg) -> Tuple[jax.Array, Any]:
    """One-token step. x_t [B, d]."""
    b, d = x_t.shape
    di, n = cfg.d_inner, cfg.d_state
    rank = p["dt_w"].shape[0]
    xz = ops.linear(x_t, p["w_in"])
    x_in, z = jnp.split(xz, 2, axis=-1)                      # [B,di]
    # fixed-width conv window shift (static shapes; ssm is not pooled)
    window = jnp.concatenate(  # jitlint: disable=hot-path-op
        [state["conv"], x_in.astype(jnp.float32)[:, None]], axis=1)
    xc = jnp.einsum("bcd,cd->bd", window, p["conv_w"]) + p["conv_b"]
    xc = jax.nn.silu(xc)
    bcdt = ops.linear(xc.astype(x_t.dtype), p["w_bcdt"]).astype(jnp.float32)
    dt_lo, b_ssm, c_ssm = jnp.split(bcdt, [rank, rank + n], axis=-1)
    dt = jax.nn.softplus(dt_lo @ p["dt_w"] + p["dt_b"])
    a = -jnp.exp(p["a_log"])
    h, y = _mamba_step(state["ssm"], (xc, dt, b_ssm, c_ssm), a, p["d_skip"])
    y = (y * jax.nn.silu(z.astype(jnp.float32))).astype(x_t.dtype)
    new_state = {"conv": window[:, 1:], "ssm": h}
    return ops.linear(y, p["w_out"]), new_state


# ===========================================================================
# RWKV-6 "Finch" (data-dependent decay)
# ===========================================================================

def rwkv_specs(cfg) -> Dict[str, ParamSpec]:
    d, f = cfg.d_model, cfg.d_ff
    dh = cfg.rwkv_head_dim
    h = d // dh
    dt = cfg.pdtype
    lora = 64 if d >= 1024 else 16
    return {
        # time-mix (attention analogue)
        "mu_r": ParamSpec((d,), jnp.float32, ("embed",), init="small"),
        "mu_k": ParamSpec((d,), jnp.float32, ("embed",), init="small"),
        "mu_v": ParamSpec((d,), jnp.float32, ("embed",), init="small"),
        "mu_w": ParamSpec((d,), jnp.float32, ("embed",), init="small"),
        "mu_g": ParamSpec((d,), jnp.float32, ("embed",), init="small"),
        "w_r": ParamSpec((d, d), dt, ("embed", "heads")),
        "w_k": ParamSpec((d, d), dt, ("embed", "heads")),
        "w_v": ParamSpec((d, d), dt, ("embed", "heads")),
        "w_g": ParamSpec((d, d), dt, ("embed", "heads")),
        "w_o": ParamSpec((d, d), dt, ("heads", "embed")),
        # data-dependent decay lora (the Finch hallmark)
        "decay_w0": ParamSpec((d,), jnp.float32, ("embed",), init="zeros"),
        "decay_a": ParamSpec((d, lora), jnp.float32, ("embed", None),
                             init="small"),
        "decay_b": ParamSpec((lora, d), jnp.float32, (None, "embed"),
                             init="small"),
        "bonus_u": ParamSpec((h, dh), jnp.float32, ("heads", None),
                             init="small"),
        "ln_x": ParamSpec((d,), jnp.float32, ("embed",), init="ones"),
        # channel-mix (FFN analogue)
        "mu_ck": ParamSpec((d,), jnp.float32, ("embed",), init="small"),
        "mu_cr": ParamSpec((d,), jnp.float32, ("embed",), init="small"),
        "w_ck": ParamSpec((d, f), dt, ("embed", "ffn")),
        "w_cv": ParamSpec((f, d), dt, ("ffn", "embed")),
        "w_cr": ParamSpec((d, d), dt, ("embed", "embed")),
    }


def _shift(x: jax.Array) -> jax.Array:
    """Token shift: previous timestep (zeros at t=0). x [B,S,d]."""
    return jnp.pad(x, ((0, 0), (1, 0), (0, 0)))[:, :-1]


def _lerp(x, xs, mu):
    return x + (xs - x) * mu


def _rwkv_step(carry, xs, u):
    """WKV recurrence per head.  state [B,H,dh,dh] (i=key dim, j=val dim)."""
    state = carry
    r_t, k_t, v_t, w_t = xs      # [B,H,dh] each
    kv = k_t[..., :, None] * v_t[..., None, :]               # [B,H,dh,dh]
    y = jnp.einsum("bhi,bhij->bhj", r_t, u[..., :, None] * kv + state)
    state = w_t[..., :, None] * state + kv
    return state, y


def rwkv_time_mix(p, x: jax.Array, cfg, ctx, return_state: bool = False):
    b, s, d = x.shape
    dh = cfg.rwkv_head_dim
    h = d // dh
    xf = x.astype(jnp.float32)
    xs = _shift(xf)
    r = ops.linear(_lerp(xf, xs, p["mu_r"]).astype(x.dtype), p["w_r"])
    k = ops.linear(_lerp(xf, xs, p["mu_k"]).astype(x.dtype), p["w_k"])
    v = ops.linear(_lerp(xf, xs, p["mu_v"]).astype(x.dtype), p["w_v"])
    g = ops.linear(_lerp(xf, xs, p["mu_g"]).astype(x.dtype), p["w_g"])
    xw = _lerp(xf, xs, p["mu_w"])
    w = jnp.exp(-jnp.exp(
        p["decay_w0"] + jnp.tanh(xw @ p["decay_a"]) @ p["decay_b"]))

    heads = lambda t: t.astype(jnp.float32).reshape(b, s, h, dh)
    to_t = lambda t: jnp.moveaxis(heads(t), 1, 0)            # [S,B,H,dh]
    xs_t = (to_t(r), to_t(k), to_t(v), to_t(w))
    state0 = jnp.zeros((b, h, dh, dh), jnp.float32)
    step = lambda c, xx: _rwkv_step(c, xx, p["bonus_u"])
    wkv_fin, ys = _chunked_scan(step, state0, xs_t, cfg.scan_chunk, cfg.remat)
    y = jnp.moveaxis(ys, 0, 1).reshape(b, s, d)              # [B,S,d]
    y = rms_norm(y.astype(x.dtype), p["ln_x"], cfg.rms_eps)
    y = (y.astype(jnp.float32) * jax.nn.silu(g.astype(jnp.float32))
         ).astype(x.dtype)
    out = ops.linear(y, p["w_o"])
    if return_state:
        return out, {"wkv": wkv_fin, "tm_x": xf[:, -1]}
    return out


def rwkv_channel_mix(p, x: jax.Array, cfg) -> jax.Array:
    xf = x.astype(jnp.float32)
    xs = _shift(xf)
    xk = _lerp(xf, xs, p["mu_ck"]).astype(x.dtype)
    xr = _lerp(xf, xs, p["mu_cr"]).astype(x.dtype)
    k = jnp.square(jax.nn.relu(ops.linear(xk, p["w_ck"])
                               .astype(jnp.float32))).astype(x.dtype)
    return jax.nn.sigmoid(ops.linear(xr, p["w_cr"]).astype(jnp.float32)
                          ).astype(x.dtype) * ops.linear(k, p["w_cv"])


def rwkv_init_state(cfg, batch: int):
    d = cfg.d_model
    dh = cfg.rwkv_head_dim
    h = d // dh
    return {
        "wkv": jnp.zeros((batch, h, dh, dh), jnp.float32),
        "tm_x": jnp.zeros((batch, d), jnp.float32),
        "cm_x": jnp.zeros((batch, d), jnp.float32),
    }


def rwkv_time_mix_decode(p, x_t: jax.Array, state, cfg
                         ) -> Tuple[jax.Array, Any]:
    b, d = x_t.shape
    dh = cfg.rwkv_head_dim
    h = d // dh
    xf = x_t.astype(jnp.float32)
    xs = state["tm_x"]
    r = ops.linear(_lerp(xf, xs, p["mu_r"]).astype(x_t.dtype), p["w_r"])
    k = ops.linear(_lerp(xf, xs, p["mu_k"]).astype(x_t.dtype), p["w_k"])
    v = ops.linear(_lerp(xf, xs, p["mu_v"]).astype(x_t.dtype), p["w_v"])
    g = ops.linear(_lerp(xf, xs, p["mu_g"]).astype(x_t.dtype), p["w_g"])
    xw = _lerp(xf, xs, p["mu_w"])
    w = jnp.exp(-jnp.exp(
        p["decay_w0"] + jnp.tanh(xw @ p["decay_a"]) @ p["decay_b"]))
    hd = lambda t: t.astype(jnp.float32).reshape(b, h, dh)
    new_wkv, y = _rwkv_step(state["wkv"], (hd(r), hd(k), hd(v), hd(w)),
                            p["bonus_u"])
    y = rms_norm(y.reshape(b, d).astype(x_t.dtype), p["ln_x"], cfg.rms_eps)
    y = (y.astype(jnp.float32) * jax.nn.silu(g.astype(jnp.float32))
         ).astype(x_t.dtype)
    out = ops.linear(y, p["w_o"])
    return out, {**state, "wkv": new_wkv, "tm_x": xf}


def rwkv_channel_mix_decode(p, x_t: jax.Array, state, cfg
                            ) -> Tuple[jax.Array, Any]:
    xf = x_t.astype(jnp.float32)
    xs = state["cm_x"]
    xk = _lerp(xf, xs, p["mu_ck"]).astype(x_t.dtype)
    xr = _lerp(xf, xs, p["mu_cr"]).astype(x_t.dtype)
    k = jnp.square(jax.nn.relu(ops.linear(xk, p["w_ck"])
                               .astype(jnp.float32))).astype(x_t.dtype)
    out = jax.nn.sigmoid(ops.linear(xr, p["w_cr"]).astype(jnp.float32)
                         ).astype(x_t.dtype) * ops.linear(k, p["w_cv"])
    return out, {**state, "cm_x": xf}
