"""Subprocess worker: the mesh-sharded serving parity suite.

Runs on a forced 8-host-device platform (set before jax import, so the
parent test process can stay single-device) and prints one JSON record:

* ``engine`` — greedy token streams of the unsharded ``ContinuousEngine``
  vs ``ContinuousEngine(mesh=...)`` on dp-only (8x1) and dp x tp (4x2)
  meshes, across a lockstep wave AND a staggered admissions/evictions
  wave (refreezes included), plus each sharded engine's trace counts
  before/after the second wave (the zero-retrace bar);
* ``spec`` — the same parity bar for the draft–verify engine under the
  4x2 mesh (one jitted verify panel + on-device rollback, sharded);
* ``pool`` — a refreeze + rollback round-trip on mesh-sharded pool state
  vs the same transitions unsharded (observable state equality);
* ``kernels`` — the Pallas kernels (interpret mode) on 50% sparse weights
  and a paged pool with chunked prefill and overlapped ticks: a 2x2-mesh
  engine vs the unsharded engine, with the pooled state and the attention
  kernel's operands split over all four devices.
"""
import os
import sys

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..",
                                "src"))

import dataclasses
import json

import numpy as np
import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.distributed import serving_sharding
from repro.launch.mesh import make_mesh
from repro.models import lm
from repro.serving import (CachePool, ContinuousEngine, SamplingParams,
                           SpecConfig, stable_trace_counts)


def _setup():
    cfg = get_config("qwen3-0.6b").reduced()
    # f32 so sharded-vs-unsharded token identity isolates placement from
    # bf16 reduction-order noise (like the sharded-train worker)
    cfg = dataclasses.replace(cfg, kv_k_sparsity=0.0, kv_v_sparsity=0.0,
                              kv_tail=16, compute_dtype="float32",
                              param_dtype="float32")
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    toks = np.random.default_rng(0).integers(0, cfg.vocab, (4, 16))
    return cfg, params, jnp.asarray(toks, jnp.int32)


def _waves(eng, toks):
    """Lockstep wave + staggered wave (admissions, evictions, unaligned
    prompts, > kv_tail generations -> refreezes)."""
    out1 = np.asarray(eng.generate_batch(toks, SamplingParams(
        max_new_tokens=24))).tolist()
    rids = [eng.submit(np.asarray(toks[i % 4][:7 + 3 * i]),
                       SamplingParams(max_new_tokens=20 - 2 * i))
            for i in range(6)]
    res = eng.run()
    out2 = [list(res[r].token_ids) for r in rids]
    return out1, out2


def run_engine(cfg, params, toks):
    base = ContinuousEngine(params, cfg, slots=4, max_tokens=96, bs=16)
    b1, b2 = _waves(base, toks)
    rec = {"meshes": {}}
    for label, shape in (("dp8", (8, 1)), ("dp4tp2", (4, 2))):
        mesh = make_mesh(shape, ("data", "model"))
        eng = ContinuousEngine(params, cfg, slots=4, max_tokens=96, bs=16,
                               mesh=mesh)
        o1, o2 = _waves(eng, toks)
        warm = eng.trace_counts()
        o1b, o2b = _waves(eng, toks)    # repeat both waves: must not trace
        after = eng.trace_counts()
        rec["meshes"][label] = {
            "tokens_match": (o1 == b1 and o2 == b2
                             and o1b == b1 and o2b == b2),
            "warm": warm, "after": after,
            "stable": stable_trace_counts(after) == stable_trace_counts(warm),
            "decode_traces": after["decode"],
        }
    return rec


def run_spec(cfg, params, toks):
    base = ContinuousEngine(params, cfg, slots=4, max_tokens=96, bs=16)
    b1, b2 = _waves(base, toks)
    mesh = make_mesh((4, 2), ("data", "model"))
    eng = ContinuousEngine(params, cfg, slots=4, max_tokens=96, bs=16,
                           mesh=mesh, spec=SpecConfig(k=3))
    o1, o2 = _waves(eng, toks)
    warm = eng.trace_counts()
    o1b, _ = _waves(eng, toks)
    after = eng.trace_counts()
    return {
        "tokens_match": o1 == b1 and o2 == b2 and o1b == b1,
        "verify_traces": after.get("verify"),
        "stable": stable_trace_counts(after) == stable_trace_counts(warm),
        "hist_tail": int(eng.spec_hist[1:].sum()),
    }


def _visible(state, pool):
    """Observable (length-gated) pool state, JSON-comparable digest."""
    out = {"pos": np.asarray(state["pos"]).tolist(),
           "prefix_blocks": np.asarray(state["prefix_blocks"]).tolist(),
           "tail_len": np.asarray(state["tail_len"]).tolist()}
    tl = np.asarray(state["tail_len"])
    for name, leaf in state["layers"].items():
        kv = leaf["kv"]
        live = (np.arange(pool.tail)[None, None, None, :, None]
                < tl[None, :, None, None, None])
        for key in ("k_tail", "v_tail"):
            out[f"{name}/{key}"] = float(
                np.abs(np.where(live, np.asarray(kv[key], np.float64), 0.0)
                       ).sum())
        for key in ("k_bitmap", "k_values", "v_bitmap", "v_values"):
            out[f"{name}/{key}"] = float(
                np.abs(np.asarray(kv[key], np.float64)).sum())
    return out


def run_pool(cfg, params, toks):
    """append -> rollback -> re-append -> refreeze, sharded vs unsharded."""
    pool = CachePool.build(cfg, slots=4, max_tokens=64, bs=16)
    mesh = make_mesh((4, 2), ("data", "model"))
    ctx = serving_sharding.serving_ctx(mesh, cfg)
    axes = pool.state_axes()
    rng = np.random.default_rng(3)
    p = lm.period_len(cfg)
    t = pool.tail
    shape = (cfg.n_layers // p, pool.slots, cfg.n_kv, t, cfg.hd)
    panels = {f"l{j}": {"k": jnp.asarray(rng.normal(size=shape), cfg.cdtype),
                        "v": jnp.asarray(rng.normal(size=shape), cfg.cdtype)}
              for j in range(p)}

    def transitions(state, shardings=None):
        kw = lambda in_s: ({} if shardings is None else
                           {"in_shardings": in_s,
                            "out_shardings": shardings[0]})
        if shardings is None:
            st_sh = pan_sh = vec_sh = None
        else:
            st_sh, pan_sh, vec_sh = shardings
        append = jax.jit(pool.append_many, **kw((st_sh, pan_sh, vec_sh)))
        roll = jax.jit(pool.rollback, **kw((st_sh, vec_sh)))
        refreeze = jax.jit(pool.refreeze, **kw((st_sh,)))
        st = append(state, panels, jnp.asarray([t, t, t, t], jnp.int32))
        st = roll(st, jnp.asarray([5, 0, 2, t], jnp.int32))
        st = append(st, panels, jnp.asarray([5, 0, 2, t], jnp.int32))
        return refreeze(st)

    plain = transitions(pool.init_state())

    st0 = serving_sharding.shard_state(ctx, pool.init_state(), axes)
    st_sh = serving_sharding.state_shardings(ctx, st0, axes)
    sharded = transitions(st0)
    # and once more with pinned in/out shardings (the engine's jit mode)
    rep = serving_sharding.replicated(ctx)
    pan_sh = jax.tree_util.tree_map(lambda _: rep, panels)
    vec_sh = serving_sharding.vec_sharding(ctx, pool.slots)
    pinned = transitions(st0, (st_sh, pan_sh, vec_sh))

    va, vb, vc = (_visible(s, pool) for s in (plain, sharded, pinned))
    return {"roundtrip_match": va == vb == vc,
            "prefix_blocks": va["prefix_blocks"],
            "tail_len": va["tail_len"]}


def run_kernels(cfg, params, toks):
    """Greedy tokens of the 2x2-mesh engine vs one device, both through
    the Pallas kernels, on the path ``chip_smoke.py --four-chips`` runs."""
    from repro.distributed import NULL_CTX
    from repro.distributed.convert_plan import convert_concrete
    from repro.kernels import ops
    from repro.launch.mesh import make_test_mesh
    sparse = convert_concrete(params, lm.model_specs(cfg), cfg, NULL_CTX)
    prompts = [np.asarray(toks[i][:10 + 9 * i]) for i in range(4)]

    def serve(mesh):
        eng = ContinuousEngine(sparse, cfg, slots=4, max_tokens=96, bs=16,
                               prefill_chunk=16, paged=True, overlap=True,
                               mesh=mesh)
        rids = [eng.submit(p, SamplingParams(max_new_tokens=24))
                for p in prompts]
        res = eng.run()
        return eng, [list(res[r].token_ids) for r in rids]

    with ops.backend("interpret"):
        _, base = serve(None)
        eng, toks_mesh = serve(make_test_mesh(2, 2))
        fn, args = eng.entry_points()["decode"]
        jaxpr = str(jax.make_jaxpr(fn)(*args))
    spans = sorted({len(leaf.sharding.device_set)
                    for leaf in jax.tree_util.tree_leaves(eng.state)})
    return {"tokens_match": toks_mesh == base,
            "n_tokens": sum(map(len, base)),
            "state_spans": spans,
            "decode_traces": eng.trace_counts()["decode"],
            "kernel_on_slots_and_heads": "PartitionSpec('data', 'model', None, None)"
            in jaxpr}


def main():
    cfg, params, toks = _setup()
    which = sys.argv[1] if len(sys.argv) > 1 else "all"
    rec = {"devices": jax.device_count()}
    if which in ("all", "engine"):
        rec["engine"] = run_engine(cfg, params, toks)
    if which in ("all", "spec"):
        rec["spec"] = run_spec(cfg, params, toks)
    if which in ("all", "pool"):
        rec["pool"] = run_pool(cfg, params, toks)
    if which in ("all", "kernels"):
        rec["kernels"] = run_kernels(cfg, params, toks)
    print(json.dumps(rec))


if __name__ == "__main__":
    main()
