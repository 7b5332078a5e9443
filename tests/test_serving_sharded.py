"""Mesh-sharded serving: the multi-chip parity bar.

``ContinuousEngine(mesh=...)`` shards the WHOLE serving state — slots over
the data axes, KV heads over the model axis
(``repro.distributed.serving_sharding``) — and pins every jitted step with
``in_shardings``/``out_shardings``.  The acceptance bar, asserted on a
forced 8-host-device platform (subprocess worker, so this file runs under
any parent device count):

* greedy token streams on dp-only (8x1) and dp x tp (4x2) meshes are
  IDENTICAL to the unsharded engine, across lockstep and staggered
  admission/eviction waves with refreezes;
* re-running the waves adds ZERO retraces (``stable_trace_counts``);
* the draft–verify engine (jitted verify panel + on-device rollback)
  passes the same bar under the 4x2 mesh;
* a refreeze + rollback round-trip on sharded pool state — plain jits and
  shardings-pinned jits — matches the unsharded transitions on the
  observable state.

Sharding-spec *derivation* (no devices needed) is tested in-process below.
"""
import json
import os
import subprocess
import sys

import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from repro.serving import CachePool, sampling

WORKER = os.path.join(os.path.dirname(__file__), "workers",
                      "sharded_serving_worker.py")


def run_worker(which, timeout=900):
    out = subprocess.run([sys.executable, WORKER, which],
                         capture_output=True, text=True, timeout=timeout)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.slow
def test_sharded_engine_token_identity_and_zero_retraces():
    rec = run_worker("engine")
    for label, row in rec["engine"]["meshes"].items():
        assert row["tokens_match"], (label, row)
        assert row["stable"], (label, row["warm"], row["after"])
        assert row["decode_traces"] == 1, (label, row)


@pytest.mark.slow
def test_sharded_spec_engine_parity():
    rec = run_worker("spec")["spec"]
    assert rec["tokens_match"], rec
    assert rec["verify_traces"] == 1 and rec["stable"], rec
    # speculation must actually accept drafts under sharding — an engine
    # degraded to one-token ticks would keep tokens_match green
    assert rec["hist_tail"] > 0, rec


@pytest.mark.slow
def test_sharded_pool_refreeze_rollback_roundtrip():
    rec = run_worker("pool")["pool"]
    assert rec["roundtrip_match"], rec
    assert rec["prefix_blocks"] == [1, 1, 1, 1]
    assert rec["tail_len"] == [0, 0, 0, 0]


def test_sharded_engine_pallas_kernels_parity():
    """The Pallas kernels (interpret mode) under a 2x2 mesh serve the
    unsharded engine's greedy tokens, with the pooled state and the
    attention kernel's operands split over slots and KV heads."""
    rec = run_worker("kernels")["kernels"]
    assert rec["tokens_match"] and rec["n_tokens"] == 96, rec
    assert rec["state_spans"] == [4], rec
    assert rec["kernel_on_slots_and_heads"], rec
    assert rec["decode_traces"] == 1, rec


# ---------------------------------------------------------------------------
# spec derivation units (no devices needed — FakeMesh like test_sharding)
# ---------------------------------------------------------------------------

class FakeMesh:
    shape = {"data": 4, "model": 2}
    axis_names = ("data", "model")


def _pool():
    cfg = get_config("qwen3-0.6b").reduced()
    return CachePool.build(cfg, slots=4, max_tokens=64, bs=16)


def test_state_axes_cover_every_leaf():
    """The pool + lane axes pytrees must mirror the state pytree leaf for
    leaf (a missing leaf would silently replicate new storage)."""
    import jax
    pool = _pool()
    state = {**jax.eval_shape(pool.init_state),
             "sample": jax.eval_shape(lambda: sampling.init_lanes(4))}
    axes = {**pool.state_axes(), "sample": sampling.lane_axes()}
    is_axes = lambda x: isinstance(x, tuple) and all(
        a is None or isinstance(a, str) for a in x)
    sa = jax.tree_util.tree_structure(
        jax.tree_util.tree_map(lambda _: 0, axes, is_leaf=is_axes))
    ss = jax.tree_util.tree_structure(
        jax.tree_util.tree_map(lambda _: 0, state))
    assert sa == ss
    for ax, leaf in zip(
            jax.tree_util.tree_leaves(axes, is_leaf=is_axes),
            jax.tree_util.tree_leaves(state)):
        assert len(ax) == len(leaf.shape), (ax, leaf.shape)


def test_serving_specs_slots_and_heads():
    """Slots land on data, KV heads on model; non-dividing dims replicate."""
    from repro.distributed.sharding import ShardCtx, default_rules
    cfg = get_config("qwen3-0.6b").reduced()        # n_kv = 2
    ctx = ShardCtx(FakeMesh(), default_rules(False, cfg))
    # pooled cache leaf [P, slots, Hkv, Sb, X]: slots->data, Hkv->model
    assert ctx.spec((None, "slots", "kv_heads", None, None),
                    (2, 4, 2, 4, 64)) == P(None, "data", "model", None, None)
    # 3 slots don't divide data=4 -> replicate; Hkv=1 doesn't divide model
    assert ctx.spec((None, "slots", "kv_heads", None, None),
                    (2, 3, 1, 4, 64)) == P(None, None, None, None, None)
    # lane vectors: slots over data
    assert ctx.spec(("slots",), (4,)) == P("data")
    assert ctx.spec(("slots", None), (4, 2)) == P("data", None)
