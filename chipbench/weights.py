"""Seeded weights of a configuration, in plain per-layer terms.

The benchmark owns its weights: both the program under test (through
``system.program_params``) and the reference (``reference.py``) take them
from here, the reference one layer at a time.  Dense, before pruning:
the program prunes them with its own conversion, and the reference applies
the configuration's declared pruning rule itself.

Layer ``l``'s leaves come from ``fold_in(fold_in(seed key, l), leaf index)``
so one layer can be made without the others.  Each value is an integer
draw -- the sum of four uniform random bytes less its mean, 0 +- 147.8 on
[-510, 510] -- times one constant, so every step is exact or a single
correctly rounded product: the same bits come out whatever compiles it,
one layer at a time or all layers under ``vmap``.  Linears get standard
deviation ``1/sqrt(fan_in)`` (bf16), the embedding 0.02 (bf16), norm
scales ``1 + z/2048`` (float32, about 1 +- 0.07).
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

GLOBAL_SALT = 1 << 20
Z_STD = (4 * (256 ** 2 - 1) / 12) ** 0.5       # std of the byte sum


def root_key(seed: int) -> jax.Array:
    """A key that keeps all 64 bits of ``seed``."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    k = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(k, (seed >> 32) & 0xFFFFFFFF)


def dims(c: Dict) -> Dict[str, int]:
    d = c["hidden_size"]
    h = c["num_attention_heads"]
    return {"d": d, "h": h, "hkv": c["num_key_value_heads"],
            "hd": c.get("head_dim") or d // h,
            "ff": c["intermediate_size"], "vocab": c["vocab_size"],
            "layers": c["num_hidden_layers"]}


def layer_shapes(c: Dict) -> Dict[str, Tuple[Tuple[int, ...], str]]:
    """Per-layer leaf -> (shape, kind); kind is ``linear`` or ``norm``."""
    m = dims(c)
    d, h, hkv, hd, ff = m["d"], m["h"], m["hkv"], m["hd"], m["ff"]
    out = {"attn_norm": ((d,), "norm"),
           "wq": ((d, h * hd), "linear"),
           "wk": ((d, hkv * hd), "linear"),
           "wv": ((d, hkv * hd), "linear"),
           "wo": ((h * hd, d), "linear")}
    if c.get("qk_norm"):
        out["q_norm"] = ((hd,), "norm")
        out["k_norm"] = ((hd,), "norm")
    out.update({"mlp_norm": ((d,), "norm"),
                "w_gate": ((d, ff), "linear"),
                "w_up": ((d, ff), "linear"),
                "w_down": ((ff, d), "linear")})
    return out


def _z(key, shape) -> jax.Array:
    """Integer draws as float32: sum of four random bytes minus 510."""
    b = jax.random.bits(key, shape, jnp.uint32)
    s = ((b & 255) + ((b >> 8) & 255) + ((b >> 16) & 255) + (b >> 24))
    return (s.astype(jnp.int32) - 510).astype(jnp.float32)


def _leaf(key, shape, kind):
    z = _z(key, shape)
    if kind == "norm":
        return 1.0 + z * (1.0 / 2048)
    std = 0.02 if kind == "embed" else shape[0] ** -0.5
    return (z * (std / Z_STD)).astype(jnp.bfloat16)


def layer(key: jax.Array, c: Dict, l) -> Dict[str, jax.Array]:
    """Layer ``l``'s dense leaves: bf16 linears ``[in, out]``, f32 norm
    scales.  ``l`` may be traced."""
    k = jax.random.fold_in(key, l)
    return {name: _leaf(jax.random.fold_in(k, i), shape, kind)
            for i, (name, (shape, kind)) in enumerate(layer_shapes(c).items())}


def globals_(key: jax.Array, c: Dict) -> Dict[str, jax.Array]:
    """Embedding ``[vocab, d]`` (bf16), final norm (f32) and, when the
    embedding is not tied, the output head ``[d, vocab]`` (bf16)."""
    m = dims(c)
    k = jax.random.fold_in(key, GLOBAL_SALT)
    out = {"embed": _leaf(jax.random.fold_in(k, 0), (m["vocab"], m["d"]),
                          "embed"),
           "final_norm": _leaf(jax.random.fold_in(k, 1), (m["d"],), "norm")}
    if not c.get("tie_word_embeddings"):
        out["lm_head"] = _leaf(jax.random.fold_in(k, 2),
                               (m["d"], m["vocab"]), "linear")
    return out
