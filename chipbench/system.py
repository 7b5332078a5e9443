"""The system under test, as the benchmark drives it.

This is the one module that imports the program (``repro``): it turns a
configuration file into the program's config, hands the program the
benchmark's seeded weights to convert to its sparse format, and builds the
served entry -- ``ContinuousEngine`` with the paged pool, chunked prefill
and overlapped ticks, as ``python -m repro.launch.serve --paged
--prefill-chunk 128`` runs it.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from . import weights

# benchmark leaf -> the program's parameter path
LAYER_PATHS = {
    "attn_norm": ("blocks", "l0", "ln1"),
    "wq": ("blocks", "l0", "mixer", "wq"),
    "wk": ("blocks", "l0", "mixer", "wk"),
    "wv": ("blocks", "l0", "mixer", "wv"),
    "wo": ("blocks", "l0", "mixer", "wo"),
    "q_norm": ("blocks", "l0", "mixer", "q_norm"),
    "k_norm": ("blocks", "l0", "mixer", "k_norm"),
    "mlp_norm": ("blocks", "l0", "ln2"),
    "w_gate": ("blocks", "l0", "ffn", "w_gate"),
    "w_up": ("blocks", "l0", "ffn", "w_up"),
    "w_down": ("blocks", "l0", "ffn", "w_down"),
}
CHUNK_BYTES = 0.5e9       # dense bytes made and converted at a time
GLOBAL_PATHS = {
    "embed": ("embed", "tok"),
    "lm_head": ("embed", "lm_head"),
    "final_norm": ("final_norm",),
}


def program_config(c: Dict):
    """The program's ``ArchConfig`` with the file's sizes and format."""
    from repro.configs import get_config
    f = c["format"]
    m = weights.dims(c)
    return dataclasses.replace(
        get_config(c["program_arch"]),
        n_layers=m["layers"], d_model=m["d"], n_heads=m["h"],
        n_kv=m["hkv"], d_ff=m["ff"], vocab=m["vocab"], head_dim=m["hd"],
        qk_norm=bool(c.get("qk_norm")), rope_theta=float(c["rope_theta"]),
        tie_embeddings=bool(c.get("tie_word_embeddings")), tp_pad=1,
        param_dtype=f["dtype"], compute_dtype=f["dtype"],
        sparsity=f["weight_sparsity"], sparse_policy=f["weight_policy"],
        kv_k_sparsity=f["kv_k_sparsity"], kv_v_sparsity=f["kv_v_sparsity"],
        kv_tail=f["kv_tail"])


def _get(tree, path):
    for p in path:
        tree = tree[p]
    return tree


def _nest(path, leaf):
    for p in reversed(path):
        leaf = {p: leaf}
    return leaf


def _put(tree, path, leaf):
    for p in path[:-1]:
        tree = tree.setdefault(p, {})
    tree[path[-1]] = leaf


def program_params(c: Dict, cfg, seed: int,
                   chunk_bytes: float = CHUNK_BYTES):
    """The benchmark's weights in the program's layout, its linears
    converted by the program's own ``convert_concrete``.  A stacked leaf is
    made and converted in equal chunks of layers of at most
    ``chunk_bytes`` of dense weights: the dense tree of a 3.8B model and
    the conversion's temporaries would not fit one chip together."""
    from repro.distributed import NULL_CTX
    from repro.distributed.convert_plan import convert_concrete
    from repro.models import lm

    key = weights.root_key(seed)
    specs = lm.model_specs(cfg)
    want = jax.eval_shape(lambda: lm.init_params(cfg, jax.random.PRNGKey(0)))
    n = c["num_hidden_layers"]
    params: Dict[str, Any] = {}

    def check(path, shape, dtype):
        ref = _get(want, path)
        if (tuple(shape), dtype) != (ref.shape, ref.dtype):
            raise ValueError(f"{'/'.join(path)}: benchmark leaf {shape} "
                             f"{dtype}, program wants {ref.shape} "
                             f"{ref.dtype}")

    for name, (shape, kind) in weights.layer_shapes(c).items():
        path = LAYER_PATHS[name]
        per_layer = 2 * math.prod(shape)
        n_chunks = -(-n // max(1, min(n, int(chunk_bytes // per_layer))))
        step = -(-n // n_chunks)    # chunks as even as the layers allow
        make = jax.jit(lambda k, l0, m, name=name: jax.vmap(
            lambda l: weights.layer(k, c, l)[name])(l0 + jnp.arange(m)),
            static_argnums=2)
        parts = []
        for l0 in range(0, n, step):
            m = min(step, n - l0)
            dense = make(key, l0, m)
            check(path, (n,) + dense.shape[1:], dense.dtype)
            if kind == "linear":
                spec = dataclasses.replace(_get(specs, path),
                                           shape=dense.shape)
                dense = _get(convert_concrete(_nest(path, dense),
                                              _nest(path, spec), cfg,
                                              NULL_CTX, mode="bf16"), path)
            parts.append(jax.block_until_ready(dense))
        _put(params, path, parts[0] if len(parts) == 1 else
             jax.tree_util.tree_map(lambda *xs: jnp.concatenate(xs),
                                    *parts))
        del parts
    for name, leaf in jax.jit(lambda k: weights.globals_(k, c))(key).items():
        check(GLOBAL_PATHS[name], leaf.shape, leaf.dtype)
        _put(params, GLOBAL_PATHS[name], leaf)
    made = {tuple(k.key for k in p) for p, _ in
            jax.tree_util.tree_flatten_with_path(
                params, is_leaf=lambda x: not isinstance(x, dict))[0]}
    missing = [p for p, _ in jax.tree_util.tree_flatten_with_path(want)[0]
               if tuple(k.key for k in p) not in made]
    if missing:
        raise ValueError(f"the program wants leaves the benchmark does not "
                         f"make: {missing}")
    return jax.block_until_ready(params)


def enable_compile_cache() -> str:
    """The program's persistent compile cache; returns its directory."""
    from repro.launch import runtime
    return runtime.enable_compile_cache()


def peak_bytes_in_use(device) -> Optional[int]:
    from repro.launch import runtime
    return runtime.peak_bytes_in_use(device)


def make_engine(params, cfg, c: Dict, max_tokens: int):
    """The served entry, and its telemetry sink."""
    from repro.obs import Observability
    from repro.serving import ContinuousEngine
    dep, f = c["deployment"], c["format"]
    obs = Observability()
    eng = ContinuousEngine(
        params, cfg, slots=dep["slots"], max_tokens=max_tokens,
        prefill_chunk=dep["prefill_chunk"], paged=True, overlap=True,
        capacity_slack=f["kv_capacity_slack"], obs=obs)
    if eng.pool.bs != f["kv_block"]:
        raise ValueError(f"the pool froze {eng.pool.bs}-token blocks, the "
                         f"configuration declares {f['kv_block']}")
    return eng, obs


def submit(eng, prompt, max_new_tokens: int) -> int:
    from repro.serving import SamplingParams
    return eng.submit(prompt, SamplingParams(max_new_tokens=max_new_tokens))


def obs_counts(obs) -> Tuple[float, float]:
    """(engine ticks, prefill chunks) so far, from the program's counters."""
    snap = obs.snapshot()
    chunks = snap.get("repro_prefill_chunk_seconds", {}).get("count", 0)
    return float(snap.get("repro_engine_ticks_total", 0)), float(chunks)

