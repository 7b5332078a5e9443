"""Serving launcher: sparse-weight + sparse-KV decode over streamed requests.

Demonstrates the paper's full inference path at serving scale: init (or
load) a model, convert linear layers to the compressed sparse format, then
either

* **stream mode (default)** — drive the continuous-batching
  ``ContinuousEngine``: a Poisson-ish stream of requests with mixed prompt
  and output lengths flows through the pooled sparse-KV cache (chunked
  prefill interleaved with decode, slot recycling, zero decode retraces);
* ``--one-shot`` — the legacy static-batch ``Engine`` (prefill the whole
  batch, decode lockstep), kept as the baseline.

Stream mode runs with **overlapped (double-buffered) ticks** by default:
tick t+1 is dispatched into JAX's async stream before tick t's tokens
are synced to host, hiding the host/dispatch gap behind device compute.
``--no-overlap`` restores the serial loop — it is the token-identity
oracle (greedy and seeded output are identical either way).  ``--server``
swaps the synthetic request wave for an asyncio HTTP frontend with
per-request NDJSON streaming (``repro.serving.frontend``).

``--mesh DP,TP`` serves the stream on a device mesh: the pooled state
shards slots over the data axis and KV heads over the model axis
(``repro.distributed.serving_sharding``) with token-identical greedy
output; ``--spec-k K`` adds draft–verify speculation (``--spec-adaptive``
for per-slot adaptive draft windows).

Fault-tolerant serving knobs: ``--max-queue`` (bounded admission with
load shedding), ``--deadline`` / ``--ttft-deadline`` (per-request
wall-clock budgets), ``--degrade-queue`` (drop spec drafting under
pressure), and ``--snapshot-dir`` (with ``--paged``: restore the prefix
cache on start, snapshot it when the stream drains — a restarted server
resumes at full cache-hit rate).

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-0.6b --reduced \
      --requests 8 --slots 4 --prompt-len 64 --steps 16 --sparsity 0.5
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-0.6b --reduced \
      --requests 8 --slots 8 --mesh 4,2
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.data import DataConfig, host_batch
from repro.distributed import ShardCtx, NULL_CTX, default_rules
from repro.distributed.convert_plan import convert_concrete
from repro.kernels import ops
from repro.launch.runtime import enable_compile_cache, peak_bytes_in_use
from repro.models import lm
from repro.serving import (Engine, ContinuousEngine, SamplingParams,
                           SpecConfig, stable_trace_counts)


def load_model(arch: str, *, reduced: bool = False, sparsity: float = 0.5,
               dense: bool = False, int8: bool = False, seed: int = 0):
    """Config + random weights from ``seed``, converted to the compressed
    sparse format unless ``dense`` -> ``(cfg, params)``."""
    cfg = get_config(arch)
    if reduced:
        cfg = cfg.reduced()
    cfg = dataclasses.replace(cfg, sparsity=sparsity)
    params = lm.init_params(cfg, jax.random.PRNGKey(seed))
    if not dense:
        specs = lm.model_specs(cfg)
        params = convert_concrete(params, specs, cfg, NULL_CTX,
                                  mode="int8" if int8 else "bf16")
        from repro.core import sparsity_report
        rep = sparsity_report(params)
        tot_d = sum(r["dense_bytes"] for r in rep.values())
        tot_c = sum(r["compressed_bytes"] for r in rep.values())
        print(f"[serve] sparse-converted {len(rep)} weights: "
              f"{tot_d/1e6:.1f}MB -> {tot_c/1e6:.1f}MB "
              f"({tot_c/tot_d:.3f}x)")
    return cfg, params


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--steps", type=int, default=16,
                    help="max_new_tokens per request")
    ap.add_argument("--sparsity", type=float, default=0.5)
    ap.add_argument("--int8", action="store_true")
    ap.add_argument("--dense", action="store_true",
                    help="baseline: dense weights + dense KV")
    ap.add_argument("--one-shot", action="store_true",
                    help="legacy static-batch engine instead of the "
                         "continuous-batching stream")
    ap.add_argument("--requests", type=int, default=0,
                    help="stream mode: number of requests (default: batch)")
    ap.add_argument("--slots", type=int, default=0,
                    help="stream mode: cache-pool slots (default: batch)")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="stream mode: prompt tokens prefilled per tick")
    ap.add_argument("--spec-k", type=int, default=0,
                    help="stream mode: speculative decoding — verify up "
                         "to K n-gram draft tokens per slot per tick "
                         "(0 = off; greedy output is token-identical "
                         "either way)")
    ap.add_argument("--spec-adaptive", action="store_true",
                    help="with --spec-k: per-slot adaptive draft windows "
                         "(each slot's acceptance rate scales its K)")
    ap.add_argument("--paged", action="store_true",
                    help="stream mode: paged shared-prefix pool — "
                         "compressed blocks live once in a pool-global "
                         "arena behind per-slot block tables; prompts "
                         "sharing a block-aligned prefix store and "
                         "prefill it once (needs --prefill-chunk for "
                         "prefix-cache hits)")
    ap.add_argument("--phys-blocks", type=int, default=0,
                    help="with --paged: physical blocks in the shared "
                         "arena (default: slots * max_blocks — the flat "
                         "pool's footprint)")
    ap.add_argument("--mesh", default="",
                    help="stream mode: serve the pooled engine on a "
                         "DPxTP device mesh, e.g. --mesh 4,2 — slots "
                         "shard over the data axis, KV heads over the "
                         "model axis; greedy output is token-identical "
                         "to the unsharded engine")
    ap.add_argument("--no-overlap", action="store_true",
                    help="stream mode: disable the double-buffered tick "
                         "pipeline (overlap is ON by default — tick t+1 "
                         "dispatches before tick t's tokens sync; "
                         "--no-overlap is the serial token-identity "
                         "oracle)")
    ap.add_argument("--server", action="store_true",
                    help="stream mode: instead of driving a synthetic "
                         "request stream, serve an asyncio HTTP frontend "
                         "— POST /v1/generate streams newline-delimited "
                         "JSON token frames, POST /v1/cancel aborts, "
                         "GET /healthz probes, POST /v1/shutdown drains "
                         "the pipeline and exits (snapshotting first "
                         "under --snapshot-dir)")
    ap.add_argument("--host", default="127.0.0.1",
                    help="with --server: bind address")
    ap.add_argument("--port", type=int, default=8731,
                    help="with --server: port (0 = pick a free one)")
    ap.add_argument("--audit", action="store_true",
                    help="stream mode: retrace audit — serve one warmup "
                         "request, snapshot stable_trace_counts(), then "
                         "fail (nonzero exit) if any jitted entry point "
                         "retraces during the real stream")
    ap.add_argument("--snapshot-dir", default="",
                    help="stream mode, with --paged: warm-restart "
                         "snapshots — restore the prefix cache (arena + "
                         "trie + allocator) from the newest snapshot on "
                         "start, and snapshot once the stream drains, so "
                         "a restarted server resumes at full cache-hit "
                         "rate")
    ap.add_argument("--max-queue", type=int, default=0,
                    help="stream mode: bounded admission queue — submits "
                         "past the bound are shed immediately "
                         "(finish_reason='shed'); 0 = unbounded")
    ap.add_argument("--deadline", type=float, default=0.0,
                    help="stream mode: per-request total wall-clock "
                         "deadline in seconds (finish_reason='timeout' "
                         "past it); 0 = none")
    ap.add_argument("--ttft-deadline", type=float, default=0.0,
                    help="stream mode: per-request first-token deadline "
                         "in seconds; 0 = none")
    ap.add_argument("--degrade-queue", type=int, default=0,
                    help="stream mode, with --spec-k: drop speculative "
                         "drafting to 0 while the queue holds at least "
                         "this many requests (pressure relief); 0 = off")
    # observability (stream mode): all host-side, all jit-invisible —
    # the engine feeds repro.obs at its tick-boundary sync point only
    ap.add_argument("--metrics-port", type=int, default=-1,
                    help="stream mode: serve Prometheus text exposition "
                         "on http://127.0.0.1:PORT/metrics from a "
                         "background thread (0 = pick a free port; "
                         "-1 = off)")
    ap.add_argument("--trace-file", default="",
                    help="stream mode: write request-lifecycle spans "
                         "(submit/queued/prefill/decode/finish, fault "
                         "firings, snapshot save/load) as a Chrome "
                         "trace-event JSON — load it in chrome://tracing "
                         "or https://ui.perfetto.dev")
    ap.add_argument("--log-json", action="store_true",
                    help="stream mode: one structured JSON line per "
                         "finished request (id, finish_reason, ttft, "
                         "tpot, queue/prefill/decode breakdown) instead "
                         "of the free-form result prints")
    ap.add_argument("--profile-dir", default="",
                    help="stream mode: wrap the serving stream in "
                         "jax.profiler.trace(DIR) — inspect the XLA/"
                         "device timeline in TensorBoard or Perfetto")
    ap.add_argument("--report-every", type=float, default=0.0,
                    help="stream mode: print a one-line metrics report "
                         "(ticks, tok/s rolling median, queue, shed/"
                         "timeout) every N seconds of serving; 0 = off")
    # sampling (0 temperature = greedy; each request gets its own seed)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--top-p", type=float, default=1.0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if args.spec_adaptive and not args.spec_k:
        ap.error("--spec-adaptive requires --spec-k >= 1")
    if args.one_shot and (args.metrics_port >= 0 or args.trace_file
                          or args.log_json or args.profile_dir
                          or args.report_every):
        ap.error("observability flags (--metrics-port/--trace-file/"
                 "--log-json/--profile-dir/--report-every) are "
                 "stream-mode only")
    if args.audit and args.one_shot:
        ap.error("--audit is stream-mode only (the one-shot engine has no "
                 "warmup/steady-state split to audit)")
    if args.snapshot_dir and not args.paged:
        ap.error("--snapshot-dir needs --paged (only the shared-prefix "
                 "arena + trie persist across restarts)")
    if args.degrade_queue and not args.spec_k:
        ap.error("--degrade-queue needs --spec-k (it degrades by dropping "
                 "the draft window)")
    if args.server and args.one_shot:
        ap.error("--server is stream-mode only (the one-shot engine has "
                 "no scheduler to serve requests through)")
    if args.server and args.audit:
        ap.error("--server and --audit are mutually exclusive (--audit "
                 "drives its own synthetic warmup + stream; run the "
                 "retrace audit without --server)")

    enable_compile_cache()
    dev = jax.devices()[0]
    print(f"[serve] device: {dev.platform} {dev.device_kind} "
          f"x{len(jax.devices())}; kernel backend {ops.get_backend()}")
    cfg, params = load_model(args.arch, reduced=args.reduced,
                             sparsity=args.sparsity, dense=args.dense,
                             int8=args.int8)

    n_req = args.requests or args.batch
    dc = DataConfig(vocab=cfg.vocab, seq_len=args.prompt_len,
                    global_batch=max(n_req, args.batch))
    prompts = jnp.asarray(host_batch(dc, 0)["tokens"])

    one_shot = args.one_shot
    if not one_shot:
        try:
            lm._attn_kinds(cfg)
        except ValueError:
            print(f"[serve] {cfg.family}/frontend={bool(cfg.frontend)} has "
                  "no continuous-batching path yet; falling back to the "
                  "one-shot engine (see --one-shot)")
            if args.audit:
                raise SystemExit("[serve] --audit needs the "
                                 "continuous-batching path")
            one_shot = True
    if one_shot:
        batch = {"tokens": prompts[:args.batch]}
        if cfg.family == "encdec":
            batch["src_embeds"] = jnp.zeros(
                (args.batch, args.prompt_len, cfg.d_model), jnp.float32)
        if cfg.frontend:
            batch["frontend_embeds"] = jnp.zeros(
                (args.batch, cfg.frontend_tokens, cfg.d_model), jnp.float32)
        eng = Engine(params, cfg,
                     kv_mode="dense" if args.dense else "sparse")
        sp = SamplingParams(temperature=args.temperature, top_k=args.top_k,
                            top_p=args.top_p, seed=args.seed,
                            max_new_tokens=args.steps)
        t0 = time.time()
        toks, _ = eng.generate(batch, sp)
        dt = time.time() - t0
        print(f"[serve] one-shot: {args.steps} tokens x {args.batch} reqs "
              f"in {dt:.2f}s ({args.steps*args.batch/dt:.1f} tok/s)")
        print("[serve] sample:", np.asarray(toks)[0][:16])
        return 0

    # request-stream mode: mixed lengths through the pooled engine
    if args.dense:
        # dense-KV baseline: zero KV sparsity makes the pooled compression
        # a bit-exact round trip at full per-block capacity
        cfg = dataclasses.replace(cfg, kv_k_sparsity=0.0, kv_v_sparsity=0.0)
    slots = args.slots or args.batch
    mesh = None
    if args.mesh:
        from repro.launch.mesh import make_mesh
        try:
            dp, tp = (int(x) for x in args.mesh.split(","))
        except ValueError:
            raise SystemExit(
                f"[serve] --mesh wants DP,TP (e.g. --mesh 4,2), got "
                f"{args.mesh!r}")
        if dp * tp > len(jax.devices()):
            raise SystemExit(
                f"[serve] --mesh {args.mesh} needs {dp * tp} devices, have "
                f"{len(jax.devices())} (hint: "
                f"XLA_FLAGS=--xla_force_host_platform_device_count={dp*tp})")
        mesh = make_mesh((dp, tp), ("data", "model"))
        print(f"[serve] mesh {dp}x{tp} (data x model): {slots} slots over "
              f"data, {cfg.n_kv} KV heads over model")
    obs = None
    metrics_server = None
    if args.metrics_port >= 0 or args.trace_file or args.report_every:
        from repro.obs import MetricsServer, Observability
        obs = Observability(trace_path=args.trace_file or None,
                            report_every=args.report_every)
        if args.metrics_port >= 0:
            metrics_server = MetricsServer(obs.registry,
                                           port=args.metrics_port).start()
            print(f"[serve] metrics: {metrics_server.url}")
    eng = ContinuousEngine(
        params, cfg, slots=slots,
        max_tokens=args.prompt_len + args.steps + cfg.kv_tail,
        prefill_chunk=args.prefill_chunk or None,
        spec=SpecConfig(k=args.spec_k, adaptive=args.spec_adaptive)
        if args.spec_k else None,
        mesh=mesh, paged=args.paged, phys_blocks=args.phys_blocks,
        max_queue=args.max_queue, degrade_queue=args.degrade_queue,
        obs=obs, overlap=not args.no_overlap)
    if args.paged:
        print(f"[serve] paged pool: {eng.pool.n_phys} physical blocks of "
              f"{eng.pool.bs} tokens behind {slots}x{eng.pool.max_blocks} "
              f"block tables")
    if args.snapshot_dir:
        try:
            n = eng.load_snapshot(args.snapshot_dir)
            print(f"[serve] warm restart: restored {n} prefix pages from "
                  f"{args.snapshot_dir} (trie holds {len(eng._trie)} "
                  f"blocks — matching prompts skip their prefill)")
        except ValueError as e:
            print(f"[serve] cold start: {e}")
    if mesh is not None:
        from repro.distributed import serving_sharding
        place = serving_sharding.describe(eng.ctx, eng.state, eng.state_axes)
        kv_key = next(k for k in place if k.endswith("k_values"))
        print(f"[serve] placement: pos={place['pos']} "
              f"kv={ {kv_key: place[kv_key]} }")
    if args.server:
        from repro.serving import ServerFrontend

        def on_shutdown():
            if args.snapshot_dir:
                step = eng.save_snapshot(args.snapshot_dir)
                print(f"[serve] snapshot: step {step} -> "
                      f"{args.snapshot_dir} ({len(eng._trie)} prefix "
                      "blocks persisted)")
            if obs is not None:
                obs.close()
            if metrics_server is not None:
                metrics_server.close()

        front = ServerFrontend(eng, host=args.host, port=args.port,
                               on_shutdown=on_shutdown)

        def ready(port):
            print(f"[serve] server: http://{args.host}:{port} — "
                  "POST /v1/generate {'prompt': [ids...]} streams NDJSON "
                  "token frames; GET /healthz; POST /v1/cancel; "
                  "POST /v1/shutdown", flush=True)

        try:
            front.run(ready)
        except KeyboardInterrupt:
            pass
        print(f"[serve] server drained after {front.loop_thread.ticks} "
              f"ticks, {front.requests_served} requests; jit traces: "
              f"{eng.trace_counts()}")
        return 0

    baseline = None
    if args.audit:
        # warmup: one request touches every entry point (submit/prefill/
        # decode/refreeze/release; verify when --spec-k), populating the
        # jit caches — steady-state serving must not add a single trace
        sp0 = SamplingParams(temperature=args.temperature, top_k=args.top_k,
                             top_p=args.top_p, seed=args.seed,
                             max_new_tokens=max(args.steps, 2))
        eng.submit(np.asarray(prompts[0][:args.prompt_len]), sp0)
        eng.run()
        baseline = stable_trace_counts(eng.trace_counts())
        print(f"[serve] audit: warmup traces {baseline}")
        # what each warmed program holds on the device (to obs, if on)
        print(f"[serve] audit: program bytes {eng.program_memory()}")
    on_token = None
    if args.log_json:
        import json as _json

        def on_token(o):
            """One structured line per *finished* request (streaming
            snapshots pass through silently)."""
            if not o.finished:
                return
            m = o.metrics
            print(_json.dumps({
                "event": "request", "id": o.request_id,
                "finish_reason": o.finish_reason,
                "prompt_tokens": len(o.prompt_token_ids),
                "tokens": len(o.token_ids),
                "ttft_s": m.ttft, "tpot_s": m.tpot,
                "queue_s": m.queue_time, "prefill_s": m.prefill_time,
                "decode_s": m.decode_time, "e2e_s": m.e2e_latency,
            }))

    import contextlib
    profile_ctx = (jax.profiler.trace(args.profile_dir)
                   if args.profile_dir else contextlib.nullcontext())
    rng = np.random.default_rng(0)
    t0 = time.time()
    rids = []
    with profile_ctx:
        for i in range(n_req):
            plen = int(rng.integers(max(args.prompt_len // 2, 1),
                                    args.prompt_len + 1))
            steps = int(rng.integers(max(args.steps // 2, 1),
                                     args.steps + 1))
            sp = SamplingParams(
                temperature=args.temperature, top_k=args.top_k,
                top_p=args.top_p, seed=args.seed + i,
                max_new_tokens=steps,
                deadline_s=args.deadline or None,
                ttft_deadline_s=args.ttft_deadline or None)
            rids.append(eng.submit(np.asarray(prompts[i][:plen]), sp,
                                   on_token=on_token))
        out = eng.run()
    dt = time.time() - t0
    total = sum(len(o.token_ids) for o in out.values())
    reasons = [o.finish_reason for o in out.values()]
    abnormal = {r: reasons.count(r) for r in ("shed", "timeout", "cancelled")
                if reasons.count(r)}
    if args.log_json:
        import json as _json
        print(_json.dumps({
            "event": "summary", "requests": n_req, "tokens": total,
            "wall_s": dt, "tok_s": total / dt if dt > 0 else None,
            "slots": slots,
            "finish_reasons": {r: reasons.count(r) for r in set(reasons)},
        }))
    else:
        print(f"[serve] stream: {n_req} requests, {total} tokens in "
              f"{dt:.2f}s ({total/dt:.1f} tok/s) on {slots} slots")
        print(f"[serve] jit traces: {eng.trace_counts()}")
        ttfts = [o.metrics.ttft for o in out.values()
                 if o.metrics.ttft is not None]
        lats = [o.metrics.e2e_latency for o in out.values()
                if o.metrics.e2e_latency is not None]
        if ttfts:
            print(f"[serve] ttft p50={np.median(ttfts)*1e3:.0f}ms "
                  f"max={max(ttfts)*1e3:.0f}ms; "
                  f"e2e p50={np.median(lats)*1e3:.0f}ms; "
                  f"finish: { {o.finish_reason for o in out.values()} }")
        fc = {k: v for k, v in eng.fault_counters.items() if v}
        if abnormal or fc:
            print(f"[serve] lifecycle: {abnormal or 'all normal'}; "
                  f"counters {fc}")
        if args.paged:
            print(f"[serve] paged: prefix trie holds {len(eng._trie)} "
                  f"blocks; {eng._alloc.free_blocks()}/{eng.pool.n_phys} "
                  "reclaimable")
        print("[serve] sample:", list(out[rids[0]].token_ids[:16]))
        lps = [lp for o in out.values() for lp in o.logprobs
               if lp is not None]
        print(f"[serve] mean chosen-token logprob: {np.mean(lps):.3f} "
              f"({len(lps)} tokens)")
        peak = peak_bytes_in_use()
        if peak is not None:
            print(f"[serve] device peak_bytes_in_use: {peak}")
    if obs is not None:
        if not args.log_json:
            print(obs.report_line())
        obs.close()
        if args.trace_file:
            print(f"[serve] trace: {args.trace_file} "
                  f"({obs.trace.events_written} events — load in "
                  "chrome://tracing or ui.perfetto.dev)")
    if metrics_server is not None:
        metrics_server.close()
    if args.profile_dir:
        print(f"[serve] profile: {args.profile_dir} (tensorboard "
              "--logdir or Perfetto)")
    if args.spec_k:
        apt = [o.metrics.accepted_per_tick for o in out.values()
               if o.metrics.accepted_per_tick is not None]
        mean = f"{np.mean(apt):.2f}" if apt else "n/a (no decode ticks)"
        print(f"[serve] spec: accepted-draft histogram "
              f"{eng.spec_hist.tolist()} (index = drafts accepted/tick); "
              f"mean tokens/tick {mean}")
        if eng.adaptive_hist is not None:
            print(f"[serve] spec: adaptive proposal histogram "
                  f"{eng.adaptive_hist.tolist()} "
                  f"(index = drafts proposed/tick)")
    if args.snapshot_dir:
        step = eng.save_snapshot(args.snapshot_dir)
        print(f"[serve] snapshot: step {step} -> {args.snapshot_dir} "
              f"({len(eng._trie)} prefix blocks persisted)")
    if args.audit:
        final = stable_trace_counts(eng.trace_counts())
        drift = {k: (baseline.get(k, 0), v) for k, v in final.items()
                 if v != baseline.get(k, 0)}
        if drift:
            print(f"[serve] audit: RETRACE DRIFT (warmup -> exit): {drift}")
            return 1
        print(f"[serve] audit: zero retraces after warmup ({final})")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
