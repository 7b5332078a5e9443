#!/usr/bin/env python3
"""Chip smoke test: serve qwen3-0.6b at full width on a TPU through the
Pallas kernels, and check what comes out against the jnp reference.

    python chip_smoke.py                # one chip (the default check)
    python chip_smoke.py --four-chips   # the 2x2 mesh path on four chips

Everything runs in this one process: a chip belongs to one process at a
time, so no phase starts a child.  The default run:

1. requires a TPU: prints ``platform``/``device_kind``/count and exits 1
   (printing no result) unless JAX runs on a TPU and the kernel dispatch
   (``repro.kernels.ops``) is on its ``"tpu"`` backend;
2. builds qwen3-0.6b at its published widths (28 layers, d_model 1024,
   16/8 heads, head_dim 128, vocab 151936) with random weights from
   ``--seed``, converted to 50% sparse bf16 (``convert_concrete``);
3. serves ``REQUESTS`` greedy requests on as many slots through
   ``ContinuousEngine`` exactly as ``python -m repro.launch.serve --paged
   --prefill-chunk 128`` does (stream mode, overlapped ticks, paged pool),
   twice: the first wave compiles, the second (a prefix-cache hit) must
   repeat its tokens and gives the decode rate;
4. serves the same requests for their first ``REF_STEPS`` tokens through
   the same engine on the jnp reference backend (``ops.backend("xla")``) on
   the same chip, with every weight decompressed once by
   ``sparse_format.unpack`` (bit-exact to the packed leaves), and compares.
   The reference rebuilds the compressed KV prefix with element gathers
   every step, seconds per step at this width, so it is kept short.

``--four-chips`` instead serves the same requests for their first
``MESH_STEPS`` tokens on a ``2,2`` mesh (``ContinuousEngine(mesh=...)``, as
``serve --mesh 2,2``; the same pool geometry as the default run) and
compares them with a one-chip engine in the same process; the mesh decode
step must call the Pallas kernels and the pooled state must span all four
devices.  The short waves keep the call inside one compile of each
program: a refreeze under a mesh is covered by
``tests/test_serving_sharded.py``.

The comparison rule: generated tokens must agree position by position.
Where they first differ, the disagreement is a near tie — reported, not
failed — if the reference's chosen-token logprob exceeds the logprob the
kernel path gave its own choice by less than ``TOL`` nats: that difference
is the reference's logit gap between the two tokens, up to kernel noise.
Otherwise the run fails.  On the common prefix every chosen-token logprob
must agree within ``TOL``.  The last line of standard output is the JSON
result; any failed phase raises, exits non-zero, and prints no result.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

# chosen-token logprob tolerance in nats: bf16 weights and activations over
# 28 layers, f32 accumulation in both paths, different summation orders
TOL = 0.1
ARCH = "qwen3-0.6b"
CHUNK = 128                # prefill chunk = KV block = kv_tail
PROMPT_LENS = (256, 384)   # 2-3 frozen blocks: one prefill shape to compile
STEPS = 136                # fills the 128-token tail: one refreeze per slot
REF_STEPS = 8              # tokens the jnp reference generates per request
REQUESTS = 8               # greedy requests, one slot each
MESH_STEPS = 24            # tokens per request in the --four-chips waves


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def make_prompts(vocab: int, n: int, seed: int):
    import numpy as np
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, PROMPT_LENS[i % len(PROMPT_LENS)])
            .astype(np.int32) for i in range(n)]


def serve_wave(eng, prompts, steps=STEPS):
    """Submit one greedy request per prompt, run to completion; returns
    (outputs in submission order, wall seconds)."""
    from repro.serving import SamplingParams
    t0 = time.perf_counter()
    rids = [eng.submit(p, SamplingParams(max_new_tokens=steps))
            for p in prompts]
    out = eng.run()
    return [out[r] for r in rids], time.perf_counter() - t0


def make_engine(params, cfg, prompts, mesh=None, steps=STEPS):
    from repro.serving import ContinuousEngine
    return ContinuousEngine(
        params, cfg, slots=len(prompts),
        max_tokens=max(len(p) for p in prompts) + steps + cfg.kv_tail,
        prefill_chunk=CHUNK, paged=True, overlap=True, mesh=mesh)


def decode_has_kernel(eng) -> bool:
    """Whether the engine's compiled decode step calls a Mosaic kernel (the
    lowering reuses the trace the served wave made)."""
    fn, args = eng.entry_points()["decode"]
    return "tpu_custom_call" in fn.lower(*args).as_text()


def check_outputs(name, outs, eng):
    reasons = {o.finish_reason for o in outs}
    if not reasons <= {"length", "stop"}:
        raise RuntimeError(f"{name}: abnormal finish reasons {reasons}")
    traces = eng.trace_counts()
    if traces["decode"] != 1 or traces.get("decode_chain", 1) != 1:
        raise RuntimeError(f"{name}: decode retraced: {traces}")
    for o in outs:
        lp = o.logprobs
        if len(lp) != len(o.token_ids) or not all(
                x is not None and x == x and x <= 0.0 for x in lp):
            raise RuntimeError(f"{name}: request {o.request_id} has bad "
                               f"logprobs {lp}")
    log(f"{name}: {len(outs)} requests, finish {sorted(reasons)}, "
        f"jit traces {traces}")


def compare(name, test, ref, steps=STEPS):
    """Apply the module docstring's rule to the first ``steps`` tokens
    (``ref`` was asked for that many); returns (positions compared,
    max |logprob diff|, near ties)."""
    compared, worst, ties = 0, 0.0, []
    for i, (a, b) in enumerate(zip(test, ref)):
        for j, (x, y) in enumerate(zip(a.token_ids, b.token_ids)):
            if x != y:
                gap = b.logprobs[j] - a.logprobs[j]
                if gap >= TOL:
                    raise RuntimeError(
                        f"{name}: request {i} token {j}: {x} vs reference "
                        f"{y}, which the reference prefers by {gap:.4f} "
                        f"nats (>= {TOL})")
                ties.append((i, j, round(gap, 4)))
                break
            diff = abs(a.logprobs[j] - b.logprobs[j])
            if diff > TOL:
                raise RuntimeError(
                    f"{name}: request {i} token {j}: logprob "
                    f"{a.logprobs[j]:.4f} vs reference {b.logprobs[j]:.4f}")
            worst = max(worst, diff)
            compared += 1
        else:
            if len(b.token_ids) != min(len(a.token_ids), steps):
                raise RuntimeError(f"{name}: request {i} length "
                                   f"{len(a.token_ids)} vs {len(b.token_ids)}")
    log(f"{name}: {compared} tokens agree, max |logprob diff| {worst:.6f} "
        f"(tolerance {TOL}); near ties (request, position, gap) {ties}")
    if sum(j == 0 for _, j, _ in ties) == len(test):
        raise RuntimeError(f"{name}: every request is a near tie at its "
                           "first token: the comparison saw nothing")
    return compared, worst, ties


def one_chip(cfg, params, prompts):
    import jax
    from repro.core.sparse_format import BlockSparseWeight, unpack
    from repro.kernels import ops
    from repro.launch.runtime import peak_bytes_in_use

    eng = make_engine(params, cfg, prompts)
    first, t_first = serve_wave(eng, prompts)
    check_outputs("kernels", first, eng)
    if not decode_has_kernel(eng):
        raise RuntimeError("the decode step holds no Pallas kernel")
    warm, t_warm = serve_wave(eng, prompts)
    if [o.token_ids for o in warm] != [o.token_ids for o in first]:
        raise RuntimeError("the prefix-cache wave changed the tokens")
    n_tok = sum(len(o.token_ids) for o in warm)
    log(f"first wave {t_first:.2f} s (compiles included); warm wave "
        f"{t_warm:.2f} s; compile ~ {t_first - t_warm:.2f} s")
    log(f"decode {n_tok / t_warm:.1f} tok/s over {n_tok} tokens "
        "(smoke reading, not a metric)")

    t0 = time.perf_counter()
    is_sw = lambda x: isinstance(x, BlockSparseWeight)  # noqa: E731
    dense = jax.block_until_ready(jax.jit(lambda p: jax.tree_util.tree_map(
        lambda x: unpack(x) if is_sw(x) else x, p, is_leaf=is_sw))(params))
    log(f"weights decompressed for the reference in "
        f"{time.perf_counter() - t0:.2f} s")
    with ops.backend("xla"):
        ref_eng = make_engine(dense, cfg, prompts, steps=REF_STEPS)
        ref, t_ref = serve_wave(ref_eng, prompts, steps=REF_STEPS)
        check_outputs("reference", ref, ref_eng)
        ref_has_kernel = decode_has_kernel(ref_eng)
    if ref_has_kernel:
        raise RuntimeError("the reference decode step holds a Pallas kernel")
    log(f"reference wave {t_ref:.2f} s for {REF_STEPS} tokens per request "
        "(compiles included)")
    compare("kernels vs jnp reference", first, ref, steps=REF_STEPS)
    log(f"peak_bytes_in_use {peak_bytes_in_use()}")


def four_chips(cfg, params, prompts):
    import jax
    from repro.launch.mesh import make_mesh
    from repro.launch.runtime import peak_bytes_in_use

    if len(jax.devices()) < 4:
        raise RuntimeError(f"--four-chips needs 4 devices, have "
                           f"{len(jax.devices())}")
    single = make_engine(params, cfg, prompts)
    ref, t_ref = serve_wave(single, prompts, steps=MESH_STEPS)
    check_outputs("one chip", ref, single)
    log(f"one-chip wave {t_ref:.2f} s (compiles included; smoke reading, "
        "not a metric)")
    mesh = make_mesh((2, 2), ("data", "model"))
    eng = make_engine(params, cfg, prompts, mesh=mesh)
    outs, t_mesh = serve_wave(eng, prompts, steps=MESH_STEPS)
    check_outputs("mesh 2x2", outs, eng)
    log(f"mesh wave {t_mesh:.2f} s (compiles included; smoke reading, "
        "not a metric)")
    if not decode_has_kernel(eng):
        raise RuntimeError("the mesh decode step holds no Pallas kernel")
    leaves = jax.tree_util.tree_leaves(eng.state)
    spans = {len(leaf.sharding.device_set) for leaf in leaves}
    if spans != {4}:
        raise RuntimeError(f"pooled state leaves span {spans} devices")
    split = sum(leaf.sharding.shard_shape(leaf.shape) != leaf.shape
                for leaf in leaves)
    if not split:
        raise RuntimeError("every pooled state leaf is replicated")
    log(f"pooled state: {len(leaves)} leaves, each on all 4 devices, "
        f"{split} of them split across devices")
    compare("mesh 2x2 vs one chip", outs, ref, steps=MESH_STEPS)
    log("peak_bytes_in_use per device "
        f"{[peak_bytes_in_use(d) for d in jax.devices()]}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="serve on a 2x2 mesh and compare with one chip")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    from repro.kernels import ops
    from repro.launch.runtime import enable_compile_cache
    from repro.launch.serve import load_model

    dev = jax.devices()[0]
    count = len(jax.devices())
    log(f"platform {dev.platform}, device_kind {dev.device_kind}, "
        f"count {count}")
    if dev.platform != "tpu" or ops.get_backend() != "tpu":
        log(f"needs a TPU with the Pallas kernel backend; found platform "
            f"{dev.platform}, backend {ops.get_backend()}")
        return 1
    log(f"compile cache {enable_compile_cache()}")

    t0 = time.perf_counter()
    cfg, params = load_model(ARCH, sparsity=0.5, seed=args.seed)
    jax.block_until_ready(params)
    log(f"{ARCH}: {cfg.n_layers} layers, d_model {cfg.d_model}, heads "
        f"{cfg.n_heads}/{cfg.n_kv}, head_dim {cfg.hd}, vocab {cfg.vocab}; "
        f"weights ready in {time.perf_counter() - t0:.1f} s")
    prompts = make_prompts(cfg.vocab, REQUESTS, args.seed)
    (four_chips if args.four_chips else one_chip)(cfg, params, prompts)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": count}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
