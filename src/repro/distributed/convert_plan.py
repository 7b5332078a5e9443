"""Mesh-aware sparse-conversion planning.

The paper packs weights offline for a fixed thread count; our analogue packs
for a fixed mesh: each eligible 2D weight gets a block shape + block-count
padding so its packed block axes shard exactly like the dense axes they
replace (DESIGN.md §2, §6).

* if the sharded dense axis has >= mesh_size blocks, pad the block count up
  to a multiple (waste <= mesh/Nb, e.g. +2.3% for deepseek's d_ff=22016);
* otherwise the tensor replicates on that axis (small tensors — cheap).

3D expert-stacked weights stay dense under tensor-parallel meshes (their
block order is expert-major, which TP chunking would misinterpret); they go
sparse under expert-parallel sharding (§Perf) or single-shard serving.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.sparse_format import (DEFAULT_BLOCK, BlockSparseWeight,
                                      packed_spec, balanced_capacity, pack,
                                      pack_nibbles, repack_capacity)
from repro.core.convert import default_predicate, _path_str
from repro.core.pruning import make_mask
from repro.core.quant import quantize_weight_int8, quantize_weight_int4
from repro.models import module as mod
from .sharding import ShardCtx, mesh_axis_size


def _to_int4(sw: BlockSparseWeight) -> BlockSparseWeight:
    """int8-valued packed weight -> nibble-packed int4.  The capacity is
    grown to a multiple of 256 so each nibble plane fills whole 128-lane
    rows of the int4 kernel's value tile."""
    if sw.capacity % 256:
        sw = repack_capacity(sw, -(-sw.capacity // 256) * 256)
    return BlockSparseWeight(sw.bitmap, pack_nibbles(sw.values), sw.scale,
                             sw.shape, sw.block, packed4=True)


def _fit_block(dim: int, pref: int) -> int:
    """Shrink the preferred block edge for small tensors (no padding blowup);
    keep multiples of 8 so bitmaps stay word-aligned."""
    if dim >= pref:
        return pref
    return max(-(-dim // 8) * 8, 8)


def _plan_leaf(spec: mod.ParamSpec, ctx: ShardCtx, block=DEFAULT_BLOCK
               ) -> Tuple[Tuple[int, int], Tuple[int, int]]:
    """-> (block, pad_to_blocks) for one (possibly layer-stacked) 2D weight."""
    k, n = spec.shape[-2:]
    block = (_fit_block(k, block[0]), _fit_block(n, block[1]))
    bk, bn = block
    axes = (spec.axes or (None,) * len(spec.shape))[-2:]
    kb = -(-k // bk)
    nb = -(-n // bn)
    pk = mesh_axis_size(ctx.mesh, ctx.rules.get(axes[0]))
    pn = mesh_axis_size(ctx.mesh, ctx.rules.get(axes[1]))
    pad_k = pk if (pk > 1 and kb >= pk) else 1
    pad_n = pn if (pn > 1 and nb >= pn) else 1
    return block, (pad_k, pad_n)


def _is_sparsifiable(path: str, spec) -> bool:
    """2D weights, or layer-stacked 2D weights (leading 'layers' axis).
    Expert-stacked (axis 'experts') weights stay dense under TP (see above)."""
    if not mod.is_spec(spec):
        return False
    if not default_predicate(
            path, jax.ShapeDtypeStruct(spec.shape, spec.dtype)):
        return False
    if len(spec.shape) == 2:
        return True
    axes = spec.axes or ()
    return len(spec.shape) == 3 and len(axes) == 3 and axes[0] == "layers"


def convert_abstract(params_abs: Any, spec_tree: Any, cfg, ctx: ShardCtx,
                     mode: str = "bf16", block=DEFAULT_BLOCK) -> Any:
    """ShapeDtypeStruct params -> tree with abstract BlockSparseWeight leaves
    (zero allocation; used by the dry-run)."""
    density = 1.0 - cfg.sparsity
    flat_s = jax.tree_util.tree_flatten_with_path(
        spec_tree, is_leaf=mod.is_spec)[0]
    treedef = jax.tree_util.tree_structure(spec_tree, is_leaf=mod.is_spec)
    flat_p = treedef.flatten_up_to(params_abs)
    out = []
    for (path, spec), leaf in zip(flat_s, flat_p):
        p = _path_str(path)
        if _is_sparsifiable(p, spec):
            blk, pad = _plan_leaf(spec, ctx, block)
            dtype = jnp.int8 if mode in ("int8", "int4") else jnp.bfloat16
            lead = tuple(spec.shape[:-2])
            ps = packed_spec(*spec.shape[-2:], density, blk, dtype,
                             pad, with_scale=(mode in ("int8", "int4")),
                             lead=lead)
            if mode == "int4":
                half = jax.ShapeDtypeStruct(
                    ps.values.shape[:-1] + (ps.values.shape[-1] // 2,),
                    jnp.uint8)
                ps = BlockSparseWeight(ps.bitmap, half, ps.scale, ps.shape,
                                       ps.block, packed4=True)
            out.append(ps)
        else:
            out.append(leaf)
    return jax.tree_util.tree_unflatten(treedef, out)


@partial(jax.jit, static_argnames=("sparsity", "policy", "blk", "cap",
                                   "pad", "mode"))
def _prune_and_pack(leaf, *, sparsity, policy, blk, cap, pad, mode):
    """Prune and pack one 2-D weight, or a layer-stacked one per layer.
    Jitted so that each leaf geometry is one compiled program: run eagerly,
    every primitive of the pruning and packing is dispatched (and on a TPU
    compiled) on its own."""
    def pack_one(w2):
        mask = make_mask(w2, sparsity, policy, blk)
        if mode in ("int8", "int4"):
            quant = quantize_weight_int8 if mode == "int8" \
                else quantize_weight_int4
            q, scale = quant(jnp.where(mask, w2, 0))
            sw = pack(q, mask, blk, capacity=cap,
                      pad_to_blocks=pad, scale=scale)
            return _to_int4(sw) if mode == "int4" else sw
        return pack(w2.astype(jnp.bfloat16), mask, blk,
                    capacity=cap, pad_to_blocks=pad)

    return jax.vmap(pack_one)(leaf) if leaf.ndim == 3 else pack_one(leaf)


def convert_concrete(params: Any, spec_tree: Any, cfg, ctx: ShardCtx,
                     mode: str = "bf16", block=DEFAULT_BLOCK) -> Any:
    """Real pruning + packing with the same mesh-aware plan (tests/serving)."""
    density = 1.0 - cfg.sparsity
    flat_s = jax.tree_util.tree_flatten_with_path(
        spec_tree, is_leaf=mod.is_spec)[0]
    treedef = jax.tree_util.tree_structure(spec_tree, is_leaf=mod.is_spec)
    flat_p = treedef.flatten_up_to(params)
    out = []
    for (path, spec), leaf in zip(flat_s, flat_p):
        p = _path_str(path)
        if _is_sparsifiable(p, spec):
            blk, pad = _plan_leaf(spec, ctx, block)
            out.append(_prune_and_pack(
                leaf, sparsity=cfg.sparsity, policy=cfg.sparse_policy,
                blk=blk, cap=balanced_capacity(density, blk), pad=pad,
                mode=mode))
        else:
            out.append(leaf)
    return jax.tree_util.tree_unflatten(treedef, out)
