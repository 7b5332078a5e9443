"""Jit'd dispatch wrappers over the Pallas kernels and their XLA fallbacks.

Backend policy (``set_backend`` / ``backend()`` context):
  * ``"tpu"``        — real Pallas lowering (requires TPU devices).
  * ``"interpret"``  — Pallas interpret mode: the kernel bodies execute in
                        Python on CPU; used to *validate* the kernels here.
  * ``"xla"``        — pure-jnp reference semantics (the CPU default; on a
                        TPU it is the on-chip reference the kernels are
                        checked against, e.g. by ``chip_smoke.py``).

All entry points accept arbitrary leading batch dims on ``x``.
"""
from __future__ import annotations

import contextlib
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.sparse_format import BlockSparseWeight
from repro.core.quant import quantize_act_int8
from . import ref
from .dense_matmul import dense_matmul_pallas
from .sparse_matmul import sparse_matmul_pallas
from .sparse_matmul_int8 import sparse_matmul_int8_pallas
from .sparse_gemv import sparse_gemv_pallas
from .sparse_attention import (sparse_decode_attention_pallas,
                               sparse_decode_attention_fused_pallas)


def _platform_backend() -> str:
    """The backend a fresh process starts on: the real Pallas kernels on a
    TPU, the jnp reference on the CPU.  Any other platform has neither and
    is refused rather than silently served by the reference."""
    platform = jax.default_backend()
    if platform == "tpu":
        return "tpu"
    if platform == "cpu":
        return "xla"
    raise RuntimeError(f"no kernel backend for platform {platform!r}")


_BACKEND = _platform_backend()


def set_backend(name: str) -> None:
    global _BACKEND
    if name not in ("tpu", "interpret", "xla"):
        raise ValueError(f"unknown backend {name!r}")
    _BACKEND = name


def get_backend() -> str:
    return _BACKEND


@contextlib.contextmanager
def backend(name: str):
    prev = get_backend()
    set_backend(name)
    try:
        yield
    finally:
        set_backend(prev)


# Sharding context the Pallas kernels run under, set while a mesh-sharded
# engine traces its steps.  XLA cannot partition a Mosaic kernel, so under
# a mesh each kernel call is a ``shard_map`` whose operands keep the
# serving placement (``distributed/serving_sharding``): attention splits
# slots over the data axes and KV heads over the model axis, so the pooled
# KV state is never gathered; a linear splits its rows over the data axes
# against its weight, which the serving engine replicates.
_SHARD = None

# logical axes of the attention kernels' [B, Hkv, ...] operands
_SLOT_HEAD = ("slots", "kv_heads", None, None)


@contextlib.contextmanager
def kernel_mesh(ctx):
    """Trace-time context: run every Pallas kernel call on the mesh of the
    ``ShardCtx`` ``ctx``, with operands placed by its logical-axis rules."""
    global _SHARD
    prev, _SHARD = _SHARD, ctx
    try:
        yield
    finally:
        _SHARD = prev


def _run(kernel, args, axes, out_axes, dims, **static):
    """Call a Pallas kernel entry; under :func:`kernel_mesh` as a
    ``shard_map``.  ``axes`` holds one logical-axes tuple per operand
    (``()`` keeps it whole on every device) and ``out_axes`` one per result;
    ``dims`` gives each logical axis's global size, and an axis whose size
    does not divide its mesh axes stays whole."""
    if _SHARD is None:
        return kernel(*args, **static)
    spec = lambda ax: _SHARD.spec(ax, [dims.get(a, 1) for a in ax])  # noqa: E731
    out_specs = (tuple(map(spec, out_axes)) if isinstance(out_axes, list)
                 else spec(out_axes))
    return jax.shard_map(partial(kernel, **static), mesh=_SHARD.mesh,
                         in_specs=tuple(map(spec, axes)),
                         out_specs=out_specs, check_vma=False)(*args)


def _run_linear(kernel, x2, *operands, **static):
    """A linear kernel on ``x2 [M, K]`` (plus per-row int8 scales when
    ``operands`` has two entries): rows follow the batch placement, the
    weight is whole on every device."""
    axes = [("batch", None)] + [("batch",)] * (len(operands) - 1) + [()]
    return _run(kernel, (x2, *operands), axes, ("batch", None),
                {"batch": x2.shape[0]}, **static)


def _pallas() -> Optional[bool]:
    """None -> use XLA ref; True -> interpret pallas; False -> real pallas."""
    if _BACKEND == "xla":
        return None
    return _BACKEND == "interpret"


def _flatten_leading(x):
    lead = x.shape[:-1]
    return x.reshape(-1, x.shape[-1]), lead


# ---------------------------------------------------------------------------
# matmuls
# ---------------------------------------------------------------------------

def dense_matmul(x: jax.Array, w: jax.Array, out_dtype=None) -> jax.Array:
    x2, lead = _flatten_leading(x)
    interp = _pallas()
    if interp is None:
        out = ref.dense_matmul_ref(x2, w, out_dtype)
    else:
        out = _run_linear(dense_matmul_pallas, x2, w, out_dtype=out_dtype,
                          interpret=interp)
    return out.reshape(*lead, w.shape[-1])


def sparse_matmul(x: jax.Array, sw: BlockSparseWeight,
                  out_dtype=None) -> jax.Array:
    x2, lead = _flatten_leading(x)
    interp = _pallas()
    if interp is None:
        out = ref.sparse_matmul_ref(x2, sw, out_dtype)
    elif x2.shape[0] <= 8:
        out = _run_linear(sparse_gemv_pallas, x2, sw, out_dtype=out_dtype,
                          interpret=interp)
    else:
        out = _run_linear(sparse_matmul_pallas, x2, sw, out_dtype=out_dtype,
                          interpret=interp)
    return out.reshape(*lead, out.shape[-1])


def sparse_matmul_int8(x: jax.Array, sw: BlockSparseWeight,
                       out_dtype=None) -> jax.Array:
    out_dtype = out_dtype or x.dtype
    x2, lead = _flatten_leading(x)
    interp = _pallas()
    if interp is None:
        out = ref.sparse_matmul_int8_ref(x2, sw, out_dtype)
    else:
        xq, sx = quantize_act_int8(x2)
        if sw.packed4:
            from .sparse_matmul_int4 import sparse_matmul_int4_pallas
            out = _run_linear(sparse_matmul_int4_pallas, xq, sx, sw,
                              out_dtype=out_dtype, interpret=interp)
        else:
            out = _run_linear(sparse_matmul_int8_pallas, xq, sx, sw,
                              out_dtype=out_dtype, interpret=interp)
    return out.reshape(*lead, out.shape[-1])


def linear(x: jax.Array, w, out_dtype=None) -> jax.Array:
    """Apply a linear layer whose weight is dense, sparse-bf16, or sparse-int8.

    This is the run-time face of the paper's "automatically replace all
    linear layers" feature: callers never branch on the storage format.
    """
    if isinstance(w, BlockSparseWeight):
        if w.packed4 or w.values.dtype == jnp.int8:
            return sparse_matmul_int8(x, w, out_dtype)
        return sparse_matmul(x, w, out_dtype)
    return dense_matmul(x, w, out_dtype)


# ---------------------------------------------------------------------------
# sparse-KV decode attention
# ---------------------------------------------------------------------------

def sparse_decode_attention(q: jax.Array,
                            k_sp: BlockSparseWeight,
                            v_sp: BlockSparseWeight,
                            hkv: int,
                            sm_scale: float,
                            k_tail: Optional[jax.Array] = None,
                            v_tail: Optional[jax.Array] = None,
                            tail_len: Optional[jax.Array] = None,
                            prefix_len: Optional[jax.Array] = None
                            ) -> jax.Array:
    """Decode attention over a compressed frozen prefix + dense tail.

    q: ``[B, Hq, D]`` (one decode tick) or ``[B, Q, Hq, D]`` (a *query
    panel* — the unified serving forward; ``Q > 1`` requires a tail).  A
    ``Q == 1`` panel is squeezed onto the single-query dispatch — decode
    through the panel forward is bit-identical to the 3-D entry.  k_sp/
    v_sp packed from the [B*Hkv*S, D] cache view with block (bs, D);
    k_tail/v_tail: [B, Hkv, T, D].

    ``tail_len``/``prefix_len`` may be scalar (uniform batch) or per-slot
    ``[B]`` int32 (pooled continuous-batching cache).  ``prefix_len`` must
    be a whole number of (bs,)-token blocks; on the Pallas path it becomes a
    per-slot valid-block count the kernel skips past.  For a query panel,
    ``tail_len`` counts the tail tokens visible to panel query 0 (its own
    appended K/V included) and query ``j`` sees ``tail_len + j`` — the
    intra-window causal mask of the draft–verify step.

    When a tail is passed, ONE fused ``pallas_call`` (or, on the XLA
    backend, one grouped-GQA softmax over the concatenated sequence)
    produces the final output: there is no XLA-side tail attention, no lse
    merge, and no ``jnp.repeat`` head materialization on the per-token hot
    path — the K+1-query verify panel rides the exact same kernel with a
    wider query block.  The two-pass partial+merge semantics survive only
    in ``repro.distributed.cp_attention``, where per-shard partials must
    cross chips before the merge.
    """
    interp = _pallas()
    has_tail = k_tail is not None and k_tail.shape[2] > 0
    if q.ndim == 4 and q.shape[1] == 1:
        # a 1-wide panel IS a decode tick: squeeze onto the single-query
        # dispatch so the unified panel forward at Q==1 stays bit-identical
        # to the pre-unification decode path on every backend.
        o = sparse_decode_attention(q[:, 0], k_sp, v_sp, hkv, sm_scale,
                                    k_tail, v_tail, tail_len, prefix_len)
        return o[:, None]
    panel = q.ndim == 4
    if panel:
        if not has_tail:
            raise ValueError("query panels append into (and need) a dense tail")
    if interp is None:
        if panel:
            return ref.sparse_decode_attention_panel_ref(
                q, k_sp, v_sp, sm_scale, k_tail, v_tail, tail_len,
                prefix_len)
        if has_tail:
            return ref.sparse_decode_attention_fused_ref(
                q, k_sp, v_sp, sm_scale, k_tail, v_tail, tail_len,
                prefix_len)
        return ref.sparse_decode_attention_ref(
            q, k_sp, v_sp, sm_scale, None, None, None, prefix_len)

    if panel:
        b, qn, hq, d = q.shape
    else:
        b, hq, d = q.shape
        qn = 1
    g = hq // hkv
    bs = k_sp.block[0]
    if k_sp.block[1] != d:
        raise ValueError(f"KV block width {k_sp.block[1]} must equal head dim {d}")
    words = k_sp.bitmap.shape[-1]
    if k_sp.bitmap.ndim == 5:       # structured [B, Hkv, Sb, 1, X]
        sb = k_sp.bitmap.shape[2]
    else:
        sb = k_sp.bitmap.shape[0] // (b * hkv)
    if panel:
        # query-major rows within each GQA group: row // g = panel index
        qg = (q.reshape(b, qn, hkv, g, d).transpose(0, 2, 1, 3, 4)
              .reshape(b, hkv, qn * g, d))
    else:
        qg = q.reshape(b, hkv, g, d)
    kbm = k_sp.bitmap.reshape(b, hkv, sb, words)
    kvv = k_sp.values.reshape(b, hkv, sb, k_sp.capacity)
    vbm = v_sp.bitmap.reshape(b, hkv, sb, words)
    vvv = v_sp.values.reshape(b, hkv, sb, v_sp.capacity)
    n_blocks = None
    if prefix_len is not None:
        n_blocks = jnp.broadcast_to(
            jnp.asarray(prefix_len, jnp.int32) // bs, (b,))

    if has_tail:
        t = k_tail.shape[2]
        tl = jnp.broadcast_to(jnp.asarray(
            tail_len if tail_len is not None else t, jnp.int32), (b,))
        # pad the ring to whole (bs,)-token panels; padding is masked by tl
        pad = -t % bs
        if pad:
            k_tail = jnp.pad(k_tail, ((0, 0), (0, 0), (0, pad), (0, 0)))
            v_tail = jnp.pad(v_tail, ((0, 0), (0, 0), (0, pad), (0, 0)))
        o = _run(sparse_decode_attention_fused_pallas,
                 (qg, kbm, kvv, vbm, vvv, k_tail, v_tail, n_blocks, tl),
                 [_SLOT_HEAD] * 7 + [("slots",)] * 2, _SLOT_HEAD,
                 {"slots": b, "kv_heads": hkv},
                 bs=bs, sm_scale=sm_scale, interpret=interp, group=g)
        if panel:
            return (o.reshape(b, hkv, qn, g, d).transpose(0, 2, 1, 3, 4)
                    .reshape(b, qn, hq, d).astype(q.dtype))
    else:
        o, _ = _run(sparse_decode_attention_pallas,
                    (qg, kbm, kvv, vbm, vvv, n_blocks),
                    [_SLOT_HEAD] * 5 + [("slots",)], [_SLOT_HEAD] * 2,
                    {"slots": b, "kv_heads": hkv},
                    bs=bs, sm_scale=sm_scale, interpret=interp)
        if prefix_len is not None:
            # a fully-skipped prefix leaves the accumulator untouched
            empty_p = jnp.broadcast_to(jnp.atleast_1d(
                jnp.asarray(prefix_len)) <= 0, (b,))
            o = jnp.where(empty_p[:, None, None, None], 0.0, o)
    return o.reshape(b, hq, d).astype(q.dtype)


def sparse_decode_attention_paged(q: jax.Array,
                                  k_bitmap: jax.Array, k_values: jax.Array,
                                  v_bitmap: jax.Array, v_values: jax.Array,
                                  table: jax.Array,
                                  layer: jax.Array,
                                  hkv: int,
                                  sm_scale: float,
                                  bs: int,
                                  k_tail: jax.Array,
                                  v_tail: jax.Array,
                                  tail_len: Optional[jax.Array] = None,
                                  prefix_len: Optional[jax.Array] = None
                                  ) -> jax.Array:
    """Paged twin of :func:`sparse_decode_attention`: the compressed prefix
    lives ONCE in a pool-global arena and each slot reaches it through its
    block-table row.

    q as in :func:`sparse_decode_attention` (``[B, Hq, D]`` tick or
    ``[B, Q, Hq, D]`` panel); ``k_bitmap`` uint32 / ``k_values`` (same for
    v) the shared arena of EVERY layer as the pool stores it,
    ``[P, n_phys, Hkv, rows, 128]`` tiles; ``layer`` the int32 period
    index to attend in; ``table`` int32 ``[B, Sb]`` physical block ids
    (entries past ``prefix_len // bs`` are dead but must stay in range).
    Tail ring and length semantics are identical to the flat entry —
    paging touches only where prefix blocks are FETCHED from, never what
    they mean.

    XLA backend: gather each slot's logical prefix out of the layer's
    arena and reuse the flat reference semantics verbatim (the defining
    oracle).  Pallas backend: the fused kernel takes the layer and the
    table as scalar-prefetch operands and its prefix phase loads the tile
    ``(layer, table[slot, i], head)`` where it lies — no per-layer slice
    or relayout of the arena, and the shared blocks are streamed per slot
    but STORED once, which is where the memory-bound decode wins.
    """
    interp = _pallas()
    d = q.shape[-1]
    if interp is None:
        k_sp = ref.gather_paged_prefix(table, layer, k_bitmap, k_values, bs, d)
        v_sp = ref.gather_paged_prefix(table, layer, v_bitmap, v_values, bs, d)
        return sparse_decode_attention(q, k_sp, v_sp, hkv, sm_scale,
                                       k_tail, v_tail, tail_len, prefix_len)
    if q.ndim == 4 and q.shape[1] == 1:
        # Q == 1 panel IS a decode tick (see sparse_decode_attention)
        o = sparse_decode_attention_paged(
            q[:, 0], k_bitmap, k_values, v_bitmap, v_values, table, layer,
            hkv, sm_scale, bs, k_tail, v_tail, tail_len, prefix_len)
        return o[:, None]
    panel = q.ndim == 4
    if panel:
        b, qn, hq, _ = q.shape
        qg = (q.reshape(b, qn, hkv, hq // hkv, d).transpose(0, 2, 1, 3, 4)
              .reshape(b, hkv, qn * (hq // hkv), d))
    else:
        b, hq, _ = q.shape
        qn = 1
        qg = q.reshape(b, hkv, hq // hkv, d)
    g = hq // hkv
    n_blocks = None
    if prefix_len is not None:
        n_blocks = jnp.broadcast_to(
            jnp.asarray(prefix_len, jnp.int32) // bs, (b,))
    t = k_tail.shape[2]
    tl = jnp.broadcast_to(jnp.asarray(
        tail_len if tail_len is not None else t, jnp.int32), (b,))
    pad = -t % bs
    if pad:
        k_tail = jnp.pad(k_tail, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v_tail = jnp.pad(v_tail, ((0, 0), (0, 0), (0, pad), (0, 0)))
    # physical blocks reach every slot: only the KV heads split the arena
    arena = (None, None, "kv_heads", None, None)
    o = _run(sparse_decode_attention_fused_pallas,
             (qg, k_bitmap, k_values, v_bitmap, v_values, k_tail, v_tail,
              n_blocks, tl, table, jnp.asarray(layer, jnp.int32)),
             [_SLOT_HEAD] + [arena] * 4 + [_SLOT_HEAD] * 2
             + [("slots",)] * 2 + [("slots", None), ()], _SLOT_HEAD,
             {"slots": b, "kv_heads": hkv},
             bs=bs, sm_scale=sm_scale, interpret=interp, group=g)
    if panel:
        return (o.reshape(b, hkv, qn, g, d).transpose(0, 2, 1, 3, 4)
                .reshape(b, qn, hq, d).astype(q.dtype))
    return o.reshape(b, hq, d).astype(q.dtype)
