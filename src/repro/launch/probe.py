import os
os.environ["XLA_FLAGS"] = (os.environ.get("REPRO_XLA_EXTRA", "") +
                           " --xla_force_host_platform_device_count="
                           + os.environ.get("REPRO_DEVICES", "512"))
# ^ MUST precede any jax import (same contract as dryrun.py).

__doc__ = """Per-period compiled probes for the roofline analysis.

``compiled.cost_analysis()`` counts while-loop bodies ONCE (verified in
EXPERIMENTS.md §Methodology), so whole-program numbers undercount scanned
layer stacks by ~n_periods.  The roofline therefore decomposes:

    total ≈ whole_program + (n_periods - 1) x period_probe + corrections

where ``period_probe`` lowers + compiles EXACTLY one period of the model
(fwd for prefill/decode, fwd+vjp for train) under the same mesh/shardings,
and ``corrections`` are closed-form terms for compute that hides inside
*inner* scans even in the probe (SSM recurrences over seq; blocked-flash
attention at 32k) — see benchmarks/roofline.py.

Outputs experiments/probes/<cell>.json.
"""

import argparse
import json
import time
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCH_IDS, SHAPES, applicable_shapes, get_config
from repro.distributed import (ShardCtx, default_rules, tree_param_specs,
                               to_named)
from repro.distributed.convert_plan import convert_abstract
from repro.models import lm
from repro.models import module as mod
from repro.launch.mesh import make_production_mesh
from repro.launch.dryrun import collective_bytes


def _period_specs(cfg):
    p = lm.period_len(cfg)
    kinds = [lm.layer_kind(cfg, j) for j in range(p)]
    cross = cfg.family == "encdec"
    return {f"l{j}": lm._block_specs(cfg, kinds[j], cross=cross)
            for j in range(p)}, kinds


def build_period_probe(cfg, ctx, shape, mode: str = "paper"):
    """One-period step function + abstract args + shardings."""
    specs, kinds = _period_specs(cfg)
    params = mod.abstract(specs)
    train = shape.kind == "train"
    decode = shape.kind == "decode"
    if decode and mode in ("paper", "int8"):
        params = convert_abstract(params, specs, cfg, ctx,
                                  mode="bf16" if mode == "paper" else "int8")
    p_shard = to_named(ctx, tree_param_specs(ctx, specs, params))

    b = shape.global_batch
    d = cfg.d_model
    sds = jax.ShapeDtypeStruct

    if decode:
        cache_full = lm.init_cache(cfg, b, shape.seq_len,
                                   mode="dense" if mode == "dense" else "sparse",
                                   abstract=True)
        cache = jax.tree_util.tree_map(
            lambda s: sds(s.shape[1:], s.dtype)
            if s.shape and s.shape[0] == cfg.n_layers // lm.period_len(cfg)
            else s,
            cache_full["layers"],
            is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))
        from repro.launch.dryrun import cache_shardings

        def strip(ns):
            spec = ns.spec
            return NamedSharding(ctx.mesh, P(*spec[1:])) \
                if len(spec) == len(ns.spec) and len(spec) > 0 else ns
        c_shard_full = cache_shardings(ctx, cache_full["layers"], cfg)
        c_shard = jax.tree_util.tree_map(
            lambda ns: NamedSharding(ctx.mesh, P(*ns.spec[1:]))
            if len(ns.spec) > 0 else ns, c_shard_full,
            is_leaf=lambda x: isinstance(x, NamedSharding))
        x_t = sds((b, d), cfg.cdtype)
        x_shard = NamedSharding(ctx.mesh, ctx.spec(("batch", None), (b, d)))
        pos = sds((), jnp.int32)
        cross_kv = None
        if cfg.family == "encdec":
            kv = sds((b, cfg.n_kv, shape.seq_len, cfg.hd), cfg.cdtype)
            cross_kv = {"k": kv, "v": kv}

        def fn(pp, cc, x, position):
            for j, kind in enumerate(kinds):
                ck = None
                x, cc[f"l{j}"] = lm._sublayer_decode(
                    x, pp[f"l{j}"], cc[f"l{j}"], kind, cfg, ctx, position,
                    ck)
            return x, cc

        jfn = jax.jit(fn, in_shardings=(p_shard, c_shard, x_shard, None))
        return jfn, (params, cache, x_t, pos), None

    s = shape.seq_len
    x = sds((b, s, d), cfg.cdtype)
    x_shard = NamedSharding(ctx.mesh,
                            ctx.spec(("batch", "seq", None), (b, s, d)))
    positions = jnp.arange(s)
    memory = None

    def fwd(pp, xx):
        for j, kind in enumerate(kinds):
            xx = lm._sublayer(xx, pp[f"l{j}"], kind, cfg, ctx, positions,
                              memory, "masked")
        return xx

    if train:
        def fn(pp, xx, dy):
            y, vjp = jax.vjp(fwd, pp, xx)
            return vjp(dy)
        jfn = jax.jit(fn, in_shardings=(p_shard, x_shard, x_shard))
        jfwd = jax.jit(fwd, in_shardings=(p_shard, x_shard))
        return jfn, (params, x, x), (jfwd, (params, x))
    jfn = jax.jit(fwd, in_shardings=(p_shard, x_shard))
    return jfn, (params, x), None


def run_probe(arch: str, shape_name: str, multi_pod: bool = False,
              mode: str = "paper", out_dir: str = "experiments/probes",
              tag: str = "", opts: str = "") -> Dict[str, Any]:
    from repro.launch.dryrun import apply_opts
    cfg = apply_opts(get_config(arch), opts)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    rules = default_rules(multi_pod, cfg)
    if shape.kind == "decode" and not cfg.serve_fsdp:
        rules["embed"] = None
    ctx = ShardCtx(mesh, rules)
    t0 = time.time()
    fn, args, fwd_probe = build_period_probe(cfg, ctx, shape, mode)
    with jax.set_mesh(mesh):
        lowered = fn.lower(*args)
        compiled = lowered.compile()
    cost = compiled.cost_analysis()
    coll = collective_bytes(compiled.as_text())
    fwd_cost = None
    if fwd_probe is not None:
        jfwd, fargs = fwd_probe
        with jax.set_mesh(mesh):
            fwd_cost = jfwd.lower(*fargs).compile().cost_analysis()
    rec = {
        "arch": arch, "shape": shape_name, "mode": mode,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "kind": "period_probe",
        "n_periods": cfg.n_layers // lm.period_len(cfg),
        "flops": float(cost.get("flops", -1)),
        "bytes_accessed": float(cost.get("bytes accessed", -1)),
        "collective_bytes": coll,
        "elapsed_s": round(time.time() - t0, 1),
    }
    if fwd_cost is not None:
        rec["flops_fwd"] = float(fwd_cost.get("flops", -1))
        rec["bytes_fwd"] = float(fwd_cost.get("bytes accessed", -1))
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        name = f"{arch}_{shape_name}_{rec['mesh']}_{mode}"
        if tag:
            name += f"_{tag}"
        with open(os.path.join(out_dir, name + ".json"), "w") as f:
            json.dump(rec, f, indent=1)
    return rec


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", choices=list(SHAPES), default=None)
    ap.add_argument("--multipod", action="store_true")
    ap.add_argument("--mode", choices=["paper", "int8", "dense"],
                    default="paper")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="experiments/probes")
    ap.add_argument("--opt", default="")
    ap.add_argument("--tag", default="")
    args = ap.parse_args(argv)
    cells = []
    if args.all:
        for arch in ARCH_IDS:
            for sh in applicable_shapes(get_config(arch)):
                cells.append((arch, sh))
    else:
        cells.append((args.arch, args.shape))
    for arch, sh in cells:
        print(f"=== probe {arch} x {sh} mode={args.mode} "
              f"opts={args.opt} ===", flush=True)
        try:
            rec = run_probe(arch, sh, args.multipod, args.mode, args.out,
                            args.tag, opts=args.opt)
            print(json.dumps(rec), flush=True)
        except Exception as e:
            print(f"PROBE FAILED: {type(e).__name__}: {e}", flush=True)


if __name__ == "__main__":
    main()
