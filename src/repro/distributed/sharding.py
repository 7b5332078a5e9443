"""Logical-axis sharding rules and the model-visible shard context.

Logical axes used by param specs and activation constraints:

  batch     -> (pod, data)      activations' batch dim
  slots     -> (pod, data)      serving cache-pool slot dim (the pooled
               state's batch axis — see distributed/serving_sharding.py)
  seq       -> model (iff cfg.seq_shard; Megatron sequence sharding of the
               residual stream between attention/MLP blocks)
  ctx       -> data             KV-cache / recurrent-state sequence dim for
               context-parallel long-context decode
  embed     -> data+pod iff cfg.fsdp (ZeRO-3-style weight sharding), else None
  heads, kv_heads, ffn, vocab, expert_in -> model   (tensor parallel)
  experts   -> None baseline (see EP variant in §Perf)
  layers    -> None

Every mapping degrades to ``None`` (replication) when the dim size does not
divide the mesh axis — e.g. kv_heads=8 on model=16 — so any (arch x mesh)
combination lowers.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from repro.core.sparse_format import BlockSparseWeight
from repro.models import module as mod


def mesh_axis_size(mesh: Optional[Mesh], axis) -> int:
    if mesh is None or axis is None:
        return 1
    if isinstance(axis, (tuple, list)):
        n = 1
        for a in axis:
            n *= mesh.shape[a]
        return n
    return mesh.shape[axis]


@dataclasses.dataclass
class ShardCtx:
    """Model-visible sharding context. ``mesh=None`` -> single-device no-op."""
    mesh: Optional[Mesh] = None
    rules: Dict[str, Any] = dataclasses.field(default_factory=dict)

    # -- activation constraints ------------------------------------------
    def spec(self, axes: Sequence[Optional[str]], sizes: Sequence[int] = None
             ) -> PartitionSpec:
        used: set = set()
        out = []
        for i, ax in enumerate(axes):
            mesh_ax = self.rules.get(ax) if ax is not None else None
            if mesh_ax is None:
                out.append(None)
                continue
            flat = tuple(mesh_ax) if isinstance(mesh_ax, (tuple, list)) \
                else (mesh_ax,)
            keep = tuple(a for a in flat if a not in used)
            if sizes is not None and keep:
                n = 1
                for a in keep:
                    n *= self.mesh.shape[a]
                if sizes[i] % n != 0:
                    keep = ()
            used.update(keep)
            out.append(None if not keep else
                       (keep if len(keep) > 1 else keep[0]))
        return PartitionSpec(*out)

    def constrain(self, x: jax.Array, axes: Sequence[Optional[str]]):
        if self.mesh is None or x is None:
            return x
        s = self.spec(axes, x.shape)
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, s))

    @property
    def tp_axis(self) -> Optional[str]:
        return self.rules.get("ffn")

    @property
    def dp_axes(self):
        return self.rules.get("batch")

    def axis_size(self, logical: str) -> int:
        return mesh_axis_size(self.mesh, self.rules.get(logical))


NULL_CTX = ShardCtx()


def default_rules(multi_pod: bool, cfg=None) -> Dict[str, Any]:
    dp = ("pod", "data") if multi_pod else ("data",)
    rules: Dict[str, Any] = {
        "batch": dp,
        # serving: cache-pool slots are the batch dim of the pooled state —
        # slots over the data axes, kv heads (below) over the model axis
        # gives multi-chip continuous batching (distributed/serving_sharding)
        "slots": dp,
        "ctx": dp + ("model",),   # KV/cache blocks spread over ALL chips
        "heads": "model",
        "kv_heads": "model",
        "ffn": "model",
        "vocab": "model",
        "expert_in": "model",
        "experts": None,
        "layers": None,
        "seq": None,
        "embed": None,
        "ssm_inner": "model",
        "state": None,
    }
    if cfg is not None:
        if cfg.seq_shard:
            rules["seq"] = "model"
        if cfg.fsdp:
            rules["embed"] = dp
        if getattr(cfg, "ep_moe", False):
            # expert-parallel: store expert weights already in the EP layout
            # (experts over DP) so the shard_map consumes them reshard-free
            rules["experts"] = dp
    return rules


# ---------------------------------------------------------------------------
# param shardings (dense ParamSpec trees and converted sparse trees)
# ---------------------------------------------------------------------------

def _sparse_leaf_spec(ctx: ShardCtx, sw: BlockSparseWeight,
                      k_ax: Optional[str], n_ax: Optional[str]
                      ) -> BlockSparseWeight:
    """PartitionSpecs for a BlockSparseWeight: block axes inherit the dense
    tensor's logical axes; leading stacked dims and the packed trailing dim
    are unsharded."""
    lead = (None,) * (sw.bitmap.ndim - 3)
    kb, nb = sw.bitmap.shape[-3:-1]
    s2 = ctx.spec(lead + (k_ax, n_ax, None),
                  sw.lead_shape + (kb, nb, 1))
    scale_spec = None
    if sw.scale is not None:
        scale_spec = PartitionSpec(*(lead + (s2[len(lead) + 1],)))
    return BlockSparseWeight(
        bitmap=s2, values=s2, scale=scale_spec,
        shape=sw.shape, block=sw.block, packed4=sw.packed4)


def tree_param_specs(ctx: ShardCtx, spec_tree: Any, params_tree: Any) -> Any:
    """PartitionSpec tree for a (possibly sparse-converted) params tree.

    ``spec_tree`` carries the logical axes (ParamSpec leaves); where the
    params tree has a BlockSparseWeight, block axes inherit the last two
    logical axes of the original spec.
    """
    def one(ps: mod.ParamSpec, leaf):
        if isinstance(leaf, BlockSparseWeight):
            axes = ps.axes or (None,) * len(ps.shape)
            return _sparse_leaf_spec(ctx, leaf, axes[-2], axes[-1])
        return ctx.spec(ps.axes or (None,) * leaf.ndim, leaf.shape)

    return jax.tree_util.tree_map(
        one, spec_tree, params_tree,
        is_leaf=lambda x: mod.is_spec(x) or isinstance(x, BlockSparseWeight))


def zero1_specs(pspec_tree: Any, params_tree: Any, cfg, ctx: ShardCtx) -> Any:
    """ZeRO-1: optimizer-state specs = param specs + data-parallel sharding
    on the first unsharded, dp-divisible dim.  Shrinks fp32 master+moments by
    the dp degree (the difference between 67B fitting a pod or not)."""
    dp = ctx.rules.get("batch")
    dp = tuple(dp) if isinstance(dp, (tuple, list)) else ((dp,) if dp else ())
    dp = tuple(a for a in dp if a is not None)
    dp_size = 1
    for a in dp:
        dp_size *= ctx.mesh.shape[a]

    def one(spec: PartitionSpec, leaf):
        if not getattr(cfg, "zero1", False) or not dp or leaf.ndim == 0:
            return spec
        dims = list(spec) + [None] * (leaf.ndim - len(spec))
        used = set()
        for d in dims:
            for a in (d if isinstance(d, tuple) else (d,)):
                if a is not None:
                    used.add(a)
        free = tuple(a for a in dp if a not in used)
        if not free:
            return spec
        n = 1
        for a in free:
            n *= ctx.mesh.shape[a]
        for i, d in enumerate(dims):
            if d is None and leaf.shape[i] % n == 0 and leaf.shape[i] >= n:
                dims[i] = free if len(free) > 1 else free[0]
                break
        return PartitionSpec(*dims)

    return jax.tree_util.tree_map(
        one, pspec_tree, params_tree,
        is_leaf=lambda x: isinstance(x, PartitionSpec))


def to_named(ctx: ShardCtx, spec_tree: Any) -> Any:
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(ctx.mesh, s),
        spec_tree,
        is_leaf=lambda x: isinstance(x, PartitionSpec))
