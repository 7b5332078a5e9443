"""Plain float32 reference of the served model, and the comparison that
decides ``correct``.

It imports nothing of the program and takes nothing it made: the weights
come again from the seed (``weights.py``), and the configuration's
declared compression is applied here in plain ``jax.numpy``:

* weights: every linear ``[K, N]`` keeps, in each ``weight_block`` block
  (cut to the dimension where that is smaller), its ``(1 - sparsity)``
  share of largest magnitudes, ties going to the lower row-major index in
  the block;
* KV: the keys and values of a ``kv_block``-token block of one request are
  pruned together over all KV heads, dropping the magnitudes below the
  one at rank ``round(sparsity * size)``; each head's share of the block
  keeps at most ``capacity`` entries (its largest) where
  ``capacity = min(ceil128(round(density * kv_block * hd * slack)),
  kv_block * hd)``.  Position ``i`` attends to the pruned keys and values
  of the positions frozen before it (``counts.frozen``) and to the exact
  ones of the rest, up to itself.

The model: RMSNorm, grouped-query attention with RoPE (half rotation) and,
where the configuration says so, per-head RMSNorm of queries and keys;
SwiGLU MLP; final RMSNorm; tied or separate output head.  All in float32
at ``highest`` matmul precision.

``max_gap`` runs it over prompts with the tokens a run served and returns
the widest gap by which a served token's logit lies below the reference's
best at its position, and the mean of those gaps.  With ``control=True``
it also runs the same model with every linear's weights and inputs rounded
to float8 (e4m3, scaled per output channel and per row) and returns the
same two numbers for the tokens the float8 model puts first.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import counts, weights

PAD = 512           # sequences are padded to a multiple of this
SCORE_BYTES = 2.5e9  # attention scores of one layer held at once
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


# ---------------------------------------------------------------------------
# the declared compression
# ---------------------------------------------------------------------------

def fit_block(dim: int, pref: int) -> int:
    return pref if dim >= pref else -(-dim // 8) * 8


def keep_largest(a: jax.Array, keep: int) -> jax.Array:
    """Mask of the ``keep`` largest entries of each row of ``a [R, L]``
    (non-negative), ties going to the lower index: the threshold is found
    by bisection on the float32 bit pattern (which orders non-negative
    floats), and the entries equal to it are taken in index order."""
    bits = jax.lax.bitcast_convert_type(a.astype(jnp.float32), jnp.int32)

    def step(_, lohi):
        lo, hi = lohi                     # count(bits >= lo) >= keep > hi's
        mid = lo + (hi - lo) // 2
        ok = jnp.sum(bits >= mid[:, None], axis=1) >= keep
        return jnp.where(ok, mid, lo), jnp.where(ok, hi, mid)

    r = a.shape[0]
    lo = jnp.zeros((r,), jnp.int32)
    hi = jnp.full((r,), 0x7F800001, jnp.int32)
    thr, _ = jax.lax.fori_loop(0, 32, step, (lo, hi))
    above = bits > thr[:, None]
    tie = bits == thr[:, None]
    room = keep - jnp.sum(above, axis=1, keepdims=True)
    return above | (tie & (jnp.cumsum(tie, axis=1) <= room))


def prune_weight(w: jax.Array, sparsity: float, block) -> jax.Array:
    """Zero all but each block's largest ``(1 - sparsity)`` magnitudes."""
    k, n = w.shape
    bk, bn = fit_block(k, block[0]), fit_block(n, block[1])
    if k % bk or n % bn:
        raise ValueError(f"weight {w.shape} is not a whole number of "
                         f"({bk}, {bn}) blocks")
    keep = max(int(round((1.0 - sparsity) * bk * bn)), 1)
    kb, nb = k // bk, n // bn
    blocks = w.reshape(kb, bk, nb, bn).transpose(0, 2, 1, 3)
    mask = keep_largest(jnp.abs(blocks).reshape(kb * nb, bk * bn), keep)
    mask = mask.reshape(kb, nb, bk, bn).transpose(0, 2, 1, 3).reshape(k, n)
    return jnp.where(mask, w, 0)


def kv_capacity(density: float, bs: int, hd: int, slack: float) -> int:
    if density >= 1.0:
        return bs * hd
    return min(-(-int(round(density * bs * hd * slack)) // 128) * 128,
               bs * hd)


def prune_kv(x: jax.Array, sparsity: float, bs: int, cap: int) -> jax.Array:
    """``x [S, Hkv, hd]`` (S a multiple of ``bs``) -> pruned per block."""
    s, hkv, hd = x.shape
    nb = s // bs
    xb = x.reshape(nb, bs, hkv, hd).transpose(0, 2, 1, 3)     # [nb,H,bs,hd]
    a = jnp.abs(xb)
    size = hkv * bs * hd
    r = min(max(int(round(sparsity * size)), 0), size - 1)
    thr = jnp.sort(a.reshape(nb, size), axis=1)[:, r]
    mask = a >= thr[:, None, None, None]
    score = jnp.where(mask, a, -jnp.inf).reshape(nb, hkv, bs * hd)
    idx = jax.lax.top_k(score, cap)[1]
    sel = jnp.zeros(score.shape, bool).at[
        jnp.arange(nb)[:, None, None], jnp.arange(hkv)[None, :, None],
        idx].set(True)
    mask = mask & sel.reshape(mask.shape)
    out = jnp.where(mask, xb, 0)
    return out.transpose(0, 2, 1, 3).reshape(s, hkv, hd)


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------

def rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rope(x, pos, theta):
    """``x [S, H, hd]``: rotate the two halves by ``pos * theta^(-2j/hd)``."""
    hd = x.shape[-1]
    freq = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos.astype(jnp.float32)[:, None] * freq
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def f8(x, axis):
    """Round to float8 e4m3 with a scale per slice along ``axis``."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    scale = jnp.where(scale > 0, scale, 1.0)
    return (x / scale).astype(F8).astype(jnp.float32) * scale


def matmul(x, w, low):
    if low:
        return f8(x, -1) @ f8(w, 0)
    return x @ w


def layer_weights(key, c: Dict, l) -> Dict[str, jax.Array]:
    """Layer ``l``'s weights as served: linears pruned, all float32."""
    f = c["format"]
    w = weights.layer(key, c, l)
    out = {}
    for name, (shape, kind) in weights.layer_shapes(c).items():
        x = w[name].astype(jnp.float32)
        if kind == "linear":
            x = prune_weight(x, f["weight_sparsity"], f["weight_block"])
        out[name] = x
    return out


def layer_forward(w, x, pos, frozen, c: Dict, low: bool):
    """One transformer block over ``x [S, d]``; ``frozen [S]`` is how many
    leading positions query ``i`` sees in compressed form."""
    m = weights.dims(c)
    f = c["format"]
    h, hkv, hd = m["h"], m["hkv"], m["hd"]
    eps = c["rms_norm_eps"]
    s = x.shape[0]
    a = rms(x, w["attn_norm"], eps)
    q = matmul(a, w["wq"], low).reshape(s, h, hd)
    k = matmul(a, w["wk"], low).reshape(s, hkv, hd)
    v = matmul(a, w["wv"], low).reshape(s, hkv, hd)
    if c.get("qk_norm"):
        q = rms(q, w["q_norm"], eps)
        k = rms(k, w["k_norm"], eps)
    q = rope(q, pos, c["rope_theta"])
    k = rope(k, pos, c["rope_theta"])
    bs = f["kv_block"]
    slack = f["kv_capacity_slack"]
    kp = prune_kv(k, f["kv_k_sparsity"], bs,
                  kv_capacity(1 - f["kv_k_sparsity"], bs, hd, slack))
    vp = prune_kv(v, f["kv_v_sparsity"], bs,
                  kv_capacity(1 - f["kv_v_sparsity"], bs, hd, slack))
    g = h // hkv
    rep = lambda t: jnp.repeat(t, g, axis=1)                  # noqa: E731
    j = jnp.arange(s)
    old = j[None, :] < frozen[:, None]                        # [S, S]
    own = (~old) & (j[None, :] <= j[:, None])
    sc = 1.0 / math.sqrt(hd)
    s_d = jnp.einsum("ihd,jhd->hij", q, rep(k)) * sc
    s_p = jnp.einsum("ihd,jhd->hij", q, rep(kp)) * sc
    sco = jnp.where(old, s_p, jnp.where(own, s_d, -jnp.inf))
    p = jax.nn.softmax(sco, axis=-1)
    o = (jnp.einsum("hij,jhd->ihd", jnp.where(old, p, 0), rep(vp))
         + jnp.einsum("hij,jhd->ihd", jnp.where(own, p, 0), rep(v)))
    x = x + matmul(o.reshape(s, h * hd), w["wo"], low)
    b = rms(x, w["mlp_norm"], eps)
    up = jax.nn.silu(matmul(b, w["w_gate"], low)) * matmul(b, w["w_up"], low)
    return x + matmul(up, w["w_down"], low)


def head_gaps(x, g, tokens, lo, hi, ctl, c: Dict, control: bool):
    """Gaps at positions ``lo <= i < hi``: the reference's best logit minus
    its logit of ``tokens[i + 1]`` or, with ``control``, of the token that
    the float8 model (hidden states ``ctl``) puts first."""
    eps = c["rms_norm_eps"]
    head = (g["embed"].astype(jnp.float32).T
            if c.get("tie_word_embeddings")
            else g["lm_head"].astype(jnp.float32))
    logits = rms(x, g["final_norm"], eps) @ head
    best = jnp.max(logits, -1)
    if not control:
        nxt = jnp.concatenate([tokens[1:], tokens[:1]])
    else:
        nxt = jnp.argmax(matmul(rms(ctl, g["final_norm"], eps), head, True),
                         -1)
    chosen = jnp.take_along_axis(logits, nxt[:, None], -1)[:, 0]
    i = jnp.arange(x.shape[0])
    return jnp.where((i >= lo) & (i < hi), best - chosen, 0.0)


# ---------------------------------------------------------------------------
# the comparison
# ---------------------------------------------------------------------------

def max_gap(c: Dict, seed: int,
            requests: Sequence[Tuple[Sequence[int], Sequence[int]]],
            control: bool = False) -> Dict[str, float]:
    """``requests``: (prompt ids, served ids) pairs.  Returns the widest
    gap over every served token (``gap``), their mean (``mean_gap``), the
    largest of the requests' own means (``request_mean_gap``), the number
    of tokens compared (``tokens``) and, with ``control``, the float8
    model's three (``control_gap``, ``control_mean_gap``,
    ``control_request_mean_gap``)."""
    reqs = [(np.asarray(p, np.int32), np.asarray(t, np.int32))
            for p, t in requests if len(t)]
    key = weights.root_key(seed)
    with jax.default_matmul_precision("highest"):
        return _max_gap(c, key, reqs, control)


def _batches(reqs, c: Dict):
    """Groups of request indices that run together: the same padded length
    ``S``, and as many as keep the attention scores of one layer under
    ``SCORE_BYTES``.  A group is topped up with copies of its first entry
    to a power of two, so that few batch shapes compile."""
    h = weights.dims(c)["h"]
    by_len: Dict[int, List[int]] = {}
    for r, (prompt, served) in enumerate(reqs):
        pad = -(-(len(prompt) + len(served)) // PAD) * PAD
        by_len.setdefault(pad, []).append(r)
    out = []
    for pad, idx in sorted(by_len.items()):
        b = max(1, int(SCORE_BYTES // (4 * 4 * h * pad * pad)))
        b = min(b, 1 << (len(idx) - 1).bit_length())
        for i in range(0, len(idx), b):
            part = idx[i:i + b]
            out.append((pad, part + [part[0]] * (b - len(part)), len(part)))
    return out


def _max_gap(c, key, reqs, control):
    n_layers = c["num_hidden_layers"]
    gen = jax.jit(lambda k, l: layer_weights(k, c, l))
    rows = lambda f: jax.jit(jax.vmap(f, in_axes=(None, 0, None, 0)))  # noqa
    fwd = rows(partial(layer_forward, c=c, low=False))
    fwd_low = rows(partial(layer_forward, c=c, low=True))
    glob = jax.jit(lambda k: weights.globals_(k, c))(key)
    gaps = {ctl: jax.jit(jax.vmap(partial(head_gaps, c=c, control=ctl),
                                  in_axes=(0, None, 0, 0, 0, 0)))
            for ctl in (False, True)}
    batches = []
    for pad, idx, real in _batches(reqs, c):
        tokens = np.zeros((len(idx), pad), np.int32)
        frozen = np.zeros((len(idx), pad), np.int32)
        lo = np.zeros(len(idx), np.int32)
        hi = np.zeros(len(idx), np.int32)
        for j, r in enumerate(idx[:real]):
            prompt, served = reqs[r]
            n = len(prompt) + len(served)
            tokens[j, :n] = np.concatenate([prompt, served])
            frozen[j] = [counts.frozen(i, len(prompt), c) for i in range(pad)]
            lo[j], hi[j] = len(prompt) - 1, n - 1
        for j in range(real, len(idx)):
            tokens[j], frozen[j] = tokens[0], frozen[0]
        tokens = jnp.asarray(tokens)
        x = glob["embed"][tokens].astype(jnp.float32)
        batches.append({"tokens": tokens, "frozen": jnp.asarray(frozen),
                        "real": real,
                        "pos": jnp.arange(pad), "lo": jnp.asarray(lo),
                        "hi": jnp.asarray(hi), "x": x,
                        "low": x if control else None})
    for l in range(n_layers):
        w = gen(key, l)
        for b in batches:
            b["x"] = fwd(w, b["x"], b["pos"], b["frozen"])
            if control:
                b["low"] = fwd_low(w, b["low"], b["pos"], b["frozen"])
        del w
    acc = {ctl: {"gap": 0.0, "total": 0.0, "request_mean_gap": 0.0}
           for ctl in (False, True)}
    n = 0
    for b in batches:
        counted = np.asarray(b["hi"] - b["lo"])[:b["real"]]
        n += int(counted.sum())
        for ctl in (False, True) if control else (False,):
            g = gaps[ctl](b["x"], glob, b["tokens"], b["lo"], b["hi"],
                          b["low"] if ctl else b["x"])
            per_row = np.asarray(jnp.sum(g, -1))[:b["real"]]
            a = acc[ctl]
            a["gap"] = max(a["gap"], float(jnp.max(g)))
            a["total"] += float(per_row.sum())
            a["request_mean_gap"] = max(
                a["request_mean_gap"],
                float(np.max(per_row / np.maximum(counted, 1))))
    out = {"tokens": n}
    for ctl in (False, True) if control else (False,):
        a, pre = acc[ctl], "control_" if ctl else ""
        out[pre + "gap"] = a["gap"]
        out[pre + "mean_gap"] = a["total"] / max(n, 1)
        out[pre + "request_mean_gap"] = a["request_mean_gap"]
    return out


def as_control(res: Dict[str, float]) -> Dict[str, float]:
    """The control's readings of a ``max_gap(..., control=True)`` result
    under the program's keys: the control put in the program's place."""
    return {k: res.get("control_" + k, v) for k, v in res.items()
            if not k.startswith("control_")}
