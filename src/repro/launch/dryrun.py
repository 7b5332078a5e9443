import os
os.environ["XLA_FLAGS"] = (os.environ.get("REPRO_XLA_EXTRA", "") +
                           " --xla_force_host_platform_device_count="
                           + os.environ.get("REPRO_DEVICES", "512"))
# ^ MUST precede any jax import: jax locks the device count on first init.

__doc__ = """Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this:
  * builds abstract params (dense for train; paper-sparse for decode),
    abstract optimizer state / KV caches, and ShapeDtypeStruct inputs;
  * jit-lowers the step function with explicit in/out shardings over the
    production mesh (16x16 single pod / 2x16x16 multi-pod);
  * ``.compile()``s — proving the sharding/collective schedule is coherent;
  * records ``memory_analysis()`` (fits-or-not per device),
    ``cost_analysis()`` (FLOPs / bytes for §Roofline), and the collective
    operand bytes parsed from the optimized HLO.

Results land in ``experiments/dryrun/<cell>.json`` for the roofline tooling.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-0.6b \
      --shape decode_32k [--multipod] [--mode paper|dense] [--out DIR]
  PYTHONPATH=src python -m repro.launch.dryrun --all
"""

import argparse
import json
import re
import sys
import time
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import (ARCH_IDS, SHAPES, ArchConfig, ShapeConfig,
                           applicable_shapes, get_config)
from repro.distributed import (ShardCtx, default_rules, tree_param_specs,
                               to_named)
from repro.distributed.convert_plan import convert_abstract
from repro.models import lm
from repro.models import module as mod
from repro.optim import OptConfig, abstract_opt_state
from repro.train import make_train_step
from .mesh import make_production_mesh

COLLECTIVE_RE = re.compile(
    r"\b(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")
SHAPE_RE = re.compile(r"(f64|f32|bf16|f16|s64|s32|u32|s16|u16|s8|u8|pred)"
                      r"\[([0-9,]*)\]")
DTYPE_BYTES = {"f64": 8, "s64": 8, "f32": 4, "s32": 4, "u32": 4, "bf16": 2,
               "f16": 2, "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1}


# ---------------------------------------------------------------------------
# input specs
# ---------------------------------------------------------------------------

def input_specs(cfg: ArchConfig, shape: ShapeConfig) -> Dict[str, Any]:
    """ShapeDtypeStruct stand-ins for every model input of this cell."""
    b, s = shape.global_batch, shape.seq_len
    sds = jax.ShapeDtypeStruct
    if shape.kind == "decode":
        return {"tokens": sds((b, 1), jnp.int32)}
    batch: Dict[str, Any] = {
        "tokens": sds((b, s), jnp.int32),
        "labels": sds((b, s), jnp.int32),
        "mask": sds((b, s), jnp.float32),
    }
    if cfg.family == "encdec":
        batch["src_embeds"] = sds((b, s, cfg.d_model), jnp.bfloat16)
    elif cfg.frontend:
        f = cfg.frontend_tokens
        batch["tokens"] = sds((b, s - f), jnp.int32)
        batch["labels"] = sds((b, s - f), jnp.int32)
        batch["mask"] = sds((b, s - f), jnp.float32)
        batch["frontend_embeds"] = sds((b, f, cfg.d_model), jnp.bfloat16)
    if shape.kind == "prefill":
        batch = {k: batch[k] for k in batch if k not in ("labels", "mask")}
    return batch


def batch_shardings(ctx: ShardCtx, batch: Dict[str, Any]) -> Dict[str, Any]:
    def one(leaf):
        axes = ["batch"] + [None] * (len(leaf.shape) - 1)
        return NamedSharding(ctx.mesh, ctx.spec(axes, leaf.shape))
    return jax.tree_util.tree_map(one, batch)


def cache_shardings(ctx: ShardCtx, cache: Any, cfg) -> Any:
    """Shard the decode cache: sparse-prefix block axes over data ('ctx'),
    batch dims over dp, kv-head dims over model where divisible.

    Type-driven (custom pytree nodes don't expose field names in paths):
    all cache leaves carry a leading stacked-period dim.
    """
    from repro.core.sparse_format import BlockSparseWeight
    from repro.core.sparse_kv import SparseKVCache
    from repro.models.attention import DenseKVCache
    mesh = ctx.mesh
    N = lambda axes, shp: NamedSharding(mesh, ctx.spec(axes, shp))

    def sparse_w(sw: BlockSparseWeight) -> BlockSparseWeight:
        if sw.bitmap.ndim == 6:   # stacked structured [P,B,Hkv,Sb,1,W]
            axes = (None, "batch", "kv_heads", "ctx", None, None)
        else:                     # stacked flat [P,(B*Hkv*Sb),1,W]
            axes = (None, "ctx", None, None)
        s3 = N(axes, sw.bitmap.shape)
        return BlockSparseWeight(
            bitmap=s3, values=N(axes, sw.values.shape),
            scale=None if sw.scale is None else NamedSharding(mesh, P()),
            shape=sw.shape, block=sw.block, packed4=sw.packed4)

    def tail(t):
        return N((None, "batch", "kv_heads", None, None), t.shape)

    def one(leaf):
        if isinstance(leaf, SparseKVCache):
            return SparseKVCache(
                k_sp=sparse_w(leaf.k_sp), v_sp=sparse_w(leaf.v_sp),
                k_tail=tail(leaf.k_tail), v_tail=tail(leaf.v_tail),
                tail_len=NamedSharding(mesh, P()))
        if isinstance(leaf, DenseKVCache):
            kv = N((None, "batch", "kv_heads", "ctx", None), leaf.k.shape)
            return DenseKVCache(kv, kv, NamedSharding(mesh, P()))
        # plain array leaf (recurrent state, cross kv, pos counter)
        shp = leaf.shape
        if len(shp) == 0:
            return NamedSharding(mesh, P())
        if len(shp) == 5:     # stacked dense/cross KV [P,B,Hkv,S,hd]
            return N((None, "batch", "kv_heads", "ctx", None), shp)
        axes = (None, "batch") + (None,) * (len(shp) - 2)
        return N(axes[: len(shp)], shp)

    return jax.tree_util.tree_map(
        one, cache,
        is_leaf=lambda x: isinstance(x, (SparseKVCache, DenseKVCache)))


# ---------------------------------------------------------------------------
# step builders
# ---------------------------------------------------------------------------

def build_train(cfg, ctx, shape):
    specs = lm.model_specs(cfg)
    params = mod.abstract(specs)
    opt = abstract_opt_state(params)
    batch = input_specs(cfg, shape)
    pspecs = tree_param_specs(ctx, specs, params)
    p_shard = to_named(ctx, pspecs)
    from repro.distributed.sharding import zero1_specs
    o_shard = {
        "step": NamedSharding(ctx.mesh, P()),
        "master": to_named(ctx, zero1_specs(pspecs, params, cfg, ctx)),
        "m": to_named(ctx, zero1_specs(pspecs, params, cfg, ctx)),
        "v": to_named(ctx, zero1_specs(pspecs, params, cfg, ctx)),
    }
    b_shard = batch_shardings(ctx, batch)
    step = make_train_step(cfg, ctx, OptConfig())
    met = {"loss": NamedSharding(ctx.mesh, P()),
           "lr": NamedSharding(ctx.mesh, P()),
           "grad_norm": NamedSharding(ctx.mesh, P())}
    fn = jax.jit(step, in_shardings=(p_shard, o_shard, b_shard),
                 out_shardings=(p_shard, o_shard, met))
    return fn, (params, opt, batch)


def build_prefill(cfg, ctx, shape):
    specs = lm.model_specs(cfg)
    params = mod.abstract(specs)
    batch = input_specs(cfg, shape)
    p_shard = to_named(ctx, tree_param_specs(ctx, specs, params))
    b_shard = batch_shardings(ctx, batch)
    fn = jax.jit(lambda p, b: lm.forward_prefill(p, b, cfg, ctx),
                 in_shardings=(p_shard, b_shard))
    return fn, (params, batch)


def build_decode(cfg, ctx, shape, mode: str = "paper"):
    specs = lm.model_specs(cfg)
    params = mod.abstract(specs)
    if mode in ("paper", "int8"):
        params = convert_abstract(params, specs, cfg, ctx,
                                  mode="bf16" if mode == "paper" else "int8")
    cache = lm.init_cache(cfg, shape.global_batch, shape.seq_len,
                          mode="dense" if mode == "dense" else "sparse",
                          abstract=True)
    tokens = input_specs(cfg, shape)["tokens"]
    p_shard = to_named(ctx, tree_param_specs(ctx, specs, params))
    c_shard = cache_shardings(ctx, cache, cfg)
    t_shard = NamedSharding(ctx.mesh, ctx.spec(("batch", None),
                                               tokens.shape))
    logit_shard = NamedSharding(ctx.mesh, ctx.spec(
        ("batch", "vocab"), (shape.global_batch, cfg.vocab)))
    fn = jax.jit(lambda p, c, t: lm.forward_decode(p, c, t, cfg, ctx),
                 in_shardings=(p_shard, c_shard, t_shard),
                 out_shardings=(logit_shard, c_shard))
    return fn, (params, cache, tokens)


# ---------------------------------------------------------------------------
# analysis
# ---------------------------------------------------------------------------

def collective_bytes(hlo: str) -> Dict[str, float]:
    """Per-collective *result* bytes from the optimized (partitioned) HLO.

    Post-optimization operands are %refs without shapes, so we account each
    collective by its per-device result shape (LHS).  The roofline layer
    applies op-specific wire factors (all-reduce moves ~2x its result in a
    ring; all-gather's result ≈ bytes received).  `-start` async forms are
    counted once; `-done` carries the same shape and is skipped.
    """
    out: Dict[str, float] = {}
    for line in hlo.splitlines():
        m = COLLECTIVE_RE.search(line)
        if not m or "= " not in line or "-done" in line:
            continue
        op = m.group(1)
        lhs = line.split("= ", 1)[1] if " = " in line else line
        sm = SHAPE_RE.search(lhs)
        if not sm:
            continue
        dt, dims = sm.group(1), sm.group(2)
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        nbytes = n * DTYPE_BYTES[dt]
        out[op] = out.get(op, 0) + nbytes
        out["total"] = out.get("total", 0) + nbytes
        out[op + "_count"] = out.get(op + "_count", 0) + 1
    return out


OPTS = {
    # §Perf optimization knobs (see EXPERIMENTS.md §Perf):
    "cp": {"cp_decode": True},            # context-parallel decode attention
    "ep": {"ep_moe": True},               # expert-parallel MoE
    "tpweights": {"serve_fsdp": False},   # serving weights TP-resident
    "triangular": {"attn_impl": "triangular"},  # causal-optimal flash
    "flashtrain": {"full_attn_max": 2048,       # blocked flash at 4k train
                   "attn_impl": "triangular"},
    "nosp": {"seq_shard": False},
    "sp": {"seq_shard": True},
    "nofsdp": {"fsdp": False},
    "noremat": {"remat": False},
}


def apply_opts(cfg, opts: str):
    import dataclasses as _dc
    for o in [o for o in (opts or "").split(",") if o]:
        cfg = _dc.replace(cfg, **OPTS[o])
    return cfg


def run_cell(arch: str, shape_name: str, multi_pod: bool = False,
             mode: str = "paper", out_dir: str = "experiments/dryrun",
             opts: str = "", tag: str = "") -> Dict[str, Any]:
    cfg = apply_opts(get_config(arch), opts)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    rules = default_rules(multi_pod, cfg)
    if shape.kind == "decode" and not cfg.serve_fsdp:
        rules["embed"] = None               # weights stay TP-resident
    ctx = ShardCtx(mesh, rules)

    t0 = time.time()
    if shape.kind == "train":
        fn, args = build_train(cfg, ctx, shape)
    elif shape.kind == "prefill":
        fn, args = build_prefill(cfg, ctx, shape)
    else:
        fn, args = build_decode(cfg, ctx, shape, mode)

    with jax.set_mesh(mesh):
        lowered = fn.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    coll = collective_bytes(hlo)

    mem_rec = {}
    for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                 "temp_size_in_bytes", "generated_code_size_in_bytes",
                 "alias_size_in_bytes", "peak_memory_in_bytes"):
        v = getattr(mem, attr, None)
        if v is not None:
            mem_rec[attr] = int(v)

    rec = {
        "arch": arch, "shape": shape_name, "mode": mode, "opts": opts,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "n_devices": int(np.prod(mesh.devices.shape)),
        "flops": float(cost.get("flops", -1)) if cost else -1.0,
        "bytes_accessed": float(cost.get("bytes accessed", -1))
        if cost else -1.0,
        "collective_bytes": coll,
        "memory": mem_rec,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "hlo_bytes": len(hlo),
    }
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
    ap.add_argument("--arch", choices=ARCH_IDS + ["llama3-8b"], default=None)
    ap.add_argument("--shape", choices=list(SHAPES), default=None)
    ap.add_argument("--multipod", action="store_true")
    ap.add_argument("--mode", choices=["paper", "int8", "dense"],
                    default="paper")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--opt", default="", help="comma list of OPTS keys")
    ap.add_argument("--tag", default="")
    args = ap.parse_args(argv)

    cells = []
    if args.all:
        for arch in ARCH_IDS:
            for sh in applicable_shapes(get_config(arch)):
                cells.append((arch, sh, args.multipod, args.mode))
    else:
        assert args.arch and args.shape
        cells.append((args.arch, args.shape, args.multipod, args.mode))

    failures = 0
    for arch, sh, mp, mode in cells:
        print(f"=== {arch} x {sh} mesh={'2x16x16' if mp else '16x16'} "
              f"mode={mode} opts={args.opt} ===", flush=True)
        try:
            rec = run_cell(arch, sh, mp, mode, args.out, opts=args.opt,
                           tag=args.tag)
            print(json.dumps(rec, indent=1), flush=True)
        except Exception as e:
            failures += 1
            import traceback
            print(f"CELL FAILED: {type(e).__name__}: {e}", flush=True)
            traceback.print_exc()
    if failures:
        print(f"{failures} cell(s) FAILED", flush=True)
        raise SystemExit(1)


if __name__ == "__main__":
    main()
