#!/usr/bin/env python3
"""Run one cell of the on-chip benchmark once.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

The cell (an entry of ``BENCHMARK.json``) names a configuration and a
traffic mix; ``spec.py`` finds their files.  One run, all in this process:

1. set-up (``setup_s``, from process start to the window): the
   configuration's weights from ``--seed``, converted by the program; the
   served engine; a warm-up request that decodes until its tail ring
   folds (and one per prompt level the first wave does not send), so
   every program the window runs is compiled (or loaded from the
   persistent cache) before it; the mix's first wave, until every client
   has its first token;
2. the window: ``--seconds`` of closed-loop serving.  ``tokens_per_s`` is
   every token delivered in it over its length; ``itl_p95_ms`` the 95th
   percentile of every gap between two tokens of one request whose later
   token is delivered in it, on the host clock at delivery.  The window
   closes ``--seconds`` after it opens; the tick running then finishes,
   and its tokens, delivered after the close, are not counted;
3. with ``--trace 1``, the window's first ``TRACE_SECONDS`` run under the
   profiler, and the per-layer metrics are read from that trace and from
   the program's counters over the whole window;
4. the check: the program's state is freed and ``reference.py`` compares a
   seeded sample of the served requests (the longest always among them)
   token by token; ``correct`` holds when no request failed and every
   number named in ``chipbench/limits/<cell>.json`` is within its limit.

The last line of standard output is one JSON object; the numbers compared
also end standard error.  Without a TPU (or with fewer chips than the cell
asks for) it prints no result and exits 2; any failure exits 1.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import traceback  # noqa: E402
import types  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import numpy as np  # noqa: E402

from chipbench import counts, spec, trace_reduce, traffic  # noqa: E402

TRACE_SECONDS = 4.0     # traced share of the window in a --trace 1 run
CHECK_REQUESTS = 48     # served requests the reference compares, at most
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"
# a limits file's check names -> the reference's result keys
RESULT_KEYS = {"mean_logit_gap": "mean_gap", "widest_logit_gap": "gap",
               "compared_tokens": "tokens"}


class NoChip(RuntimeError):
    pass


def log(msg: str) -> None:
    print(f"[chipbench {time.perf_counter() - T_START:7.1f}s] {msg}",
          file=sys.stderr, flush=True)


def require_chips(n: int):
    """The devices, or ``NoChip`` unless JAX runs on ``n`` TPUs or more."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < n:
        raise NoChip(f"needs {n} TPU chip(s); JAX has {len(devs)} "
                     f"{devs[0].platform} device(s)")
    return devs


def enable_compile_cache() -> str:
    """The program's persistent compilation cache
    (``$JAX_COMPILATION_CACHE_DIR`` where set, else the checkout's fixed
    ``.jax_cache``), keeping every program however fast it compiled, so
    that only a checkout's first run compiles.  Returns the directory."""
    import jax
    from chipbench import system
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return system.enable_compile_cache()


def memory(devs) -> str:
    s = devs[0].memory_stats() or {}
    return (f"device memory in use {s.get('bytes_in_use')} bytes, "
            f"peak {s.get('peak_bytes_in_use')}")


class Compiles:
    """Programs compiled or loaded from the persistent cache in this
    process (JAX's backend-compile events), their seconds, and how many of
    them the cache held."""

    def __init__(self):
        import jax
        self.n = self.hits = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._timed)
        jax.monitoring.register_event_listener(self._event)

    def _timed(self, event, duration, **_):
        if event == BACKEND_COMPILE:
            self.n += 1
            self.seconds += duration

    def _event(self, event, **_):
        if event == CACHE_HIT:
            self.hits += 1

    def __str__(self):
        return (f"{self.n} programs compiled or loaded in "
                f"{self.seconds:.1f} s, {self.hits} from the cache")


class Record:
    __slots__ = ("prompt", "client", "tokens", "times", "finish")

    def __init__(self, prompt, client):
        self.prompt = prompt
        self.client = client
        self.tokens: list = []
        self.times: list = []
        self.finish = None


class Served:
    """The closed loop around the engine: submits, ticks, and stamps every
    delivered token with the host clock."""

    def __init__(self, eng, stream):
        import jax
        self._ann = jax.profiler.TraceAnnotation
        self.eng = eng
        self.stream = stream
        self.recs = {}
        self.sent = 0

    def submit(self, req, client):
        from chipbench import system
        with self._ann(trace_reduce.SPAN_SUBMIT):
            rid = system.submit(self.eng, req.prompt, req.max_new_tokens)
        self.recs[rid] = Record(req.prompt, client)
        return rid

    def absorb(self, events, now):
        """Stamp ``events``; returns (records that got tokens, as
        (record, new token count), finished records)."""
        got, done = [], []
        for out in events:
            rec = self.recs[out.request_id]
            new = out.token_ids[len(rec.tokens):]
            if new:
                rec.tokens.extend(int(t) for t in new)
                rec.times.extend([now] * len(new))
                got.append((rec, len(new)))
            if out.finished and rec.finish is None:
                rec.finish = out.finish_reason
                done.append(rec)
        return got, done

    def step(self):
        with self._ann(trace_reduce.SPAN_STEP):
            events = self.eng.step()
        now = time.perf_counter()
        got, done = self.absorb(events, now)
        for rec in done:
            if rec.client is not None:
                self.submit(self.stream.next(), rec.client)
                self.sent += 1
        return now, got


def run_window(served, seconds, trace_dir, c, compiles):
    """Serve for ``seconds``; returns the window's host-side numbers."""
    import jax
    win = types.SimpleNamespace(tokens=0, gaps=[], steps=0, traced=[],
                                tick_s=[])
    n0 = compiles.n
    traced = trace_dir is not None
    if traced:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        span = jax.profiler.TraceAnnotation(trace_reduce.SPAN_WINDOW)
        span.__enter__()
    try:
        t0 = now = time.perf_counter()
        end = t0 + seconds
        while now < end:
            last = now
            now, got = served.step()
            win.steps += 1
            win.tick_s.append(now - last)
            if now > end:
                break       # delivered after the close
            decode = []
            for rec, n in got:
                win.tokens += n
                first = len(rec.tokens) - n
                for i in range(max(first, 1), len(rec.tokens)):
                    win.gaps.append(rec.times[i] - rec.times[i - 1])
                if first:
                    ctx = len(rec.prompt) + first
                    decode.append((ctx, counts.frozen(ctx - 1,
                                                      len(rec.prompt), c)))
            if traced:
                win.traced.append(decode)
                if now - t0 >= TRACE_SECONDS:
                    span.__exit__(None, None, None)
                    jax.profiler.stop_trace()
                    traced = False
        win.window_s = seconds
        win.overrun_s = now - end
        win.t0 = t0
        win.compiles = compiles.n - n0
    finally:
        if traced:
            span.__exit__(None, None, None)
            jax.profiler.stop_trace()
    return win


def p95(values):
    return statistics.quantiles(values, n=20, method="inclusive")[-1]


def sample(recs, seed):
    """The requests the reference compares: the longest served one and a
    seeded draw of the rest, ``CHECK_REQUESTS`` at most."""
    served = [r for r in recs if r.tokens]
    if not served:
        return []
    longest = max(range(len(served)), key=lambda i: len(served[i].tokens))
    rest = [i for i in range(len(served)) if i != longest]
    rng = np.random.default_rng([seed, 7])
    pick = rng.permutation(rest)[:CHECK_REQUESTS - 1].tolist()
    return [(served[i].prompt, served[i].tokens)
            for i in [longest] + sorted(pick)]


def device_info(devs, chips):
    from chipbench import system
    peaks = [p for p in map(system.peak_bytes_in_use, devs[:chips])
             if p is not None]
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs),
            "memory_peak_bytes": max(peaks) if peaks else None}


def judge(limits, res, failed):
    """``(correct, checks)`` of the reference's result ``res``: every
    number the limits file names within its limit, and no failed
    request."""
    checks = {}
    for name, lim in limits["checks"].items():
        value = res[RESULT_KEYS[name]]
        (how, bound), = lim.items()
        ok = value <= bound if how == "at_most" else value >= bound
        checks[name] = {"value": value, "limit": bound, "ok": ok}
    return all(v["ok"] for v in checks.values()) and failed == 0, checks


def serve_cell(args, root):
    """Set-up, window and metrics of one run; the program's state is
    freed on return.  Returns what the check and the result line need."""
    devs = require_chips(1)     # before anything else touches the chip
    log(f"compile cache {enable_compile_cache()}")
    from chipbench import system

    bench = spec.benchmark(root)
    cell = spec.cell(bench, args.workload)
    require_chips(cell["chips"])
    c = spec.config(bench, cell["config"], root)
    mix = spec.traffic(cell["traffic"], root)
    limits = spec.limits(cell["name"], root)
    compiles = Compiles()

    cfg = system.program_config(c)
    params = system.program_params(c, cfg, args.seed)
    log(f"weights made and converted; {memory(devs)}")
    stream = traffic.Stream(mix, args.seed, c["vocab_size"],
                            c["deployment"]["slots"])
    eng, obs = system.make_engine(params, cfg, c,
                                  stream.max_context() + cfg.kv_tail)
    served = Served(eng, stream)
    log(f"engine built; {memory(devs)}")

    warm = [served.submit(r, None) for r in
            stream.warmup(c["format"]["kv_tail"], c["format"]["kv_block"])]
    while not all(served.recs[r].finish for r in warm):
        served.step()
    served.absorb(eng.quiesce(), time.perf_counter())
    for r in warm:
        del served.recs[r]
    log(f"warm-up served {len(warm)} requests; {memory(devs)}")
    for client in range(stream.clients):
        served.submit(stream.next(), client)
        served.sent += 1
    while not all(r.tokens for r in served.recs.values()
                  if r.finish is None):
        served.step()

    log(f"first wave of {stream.clients} requests decoding; "
        f"{memory(devs)}")
    ticks0, chunks0 = system.obs_counts(obs)
    tmp = tempfile.mkdtemp() if args.trace else None
    try:
        log(f"set-up: {compiles}")
        win = run_window(served, args.seconds, tmp, c, compiles)
        setup_s = win.t0 - T_START
        ticks1, chunks1 = system.obs_counts(obs)
        served.absorb(eng.quiesce(), time.perf_counter())
        device = device_info(devs, cell["chips"])
        trace = None
        if args.trace:
            path = next(Path(tmp).rglob("*.xplane.pb"))
            trace = trace_reduce.load(str(path))
    finally:
        if tmp:
            shutil.rmtree(tmp, ignore_errors=True)

    ticks = sorted(win.tick_s)
    log(f"window of {win.window_s:.3f} s: {win.steps} ticks (the last "
        f"ending {win.overrun_s:.3f} s past the close), "
        f"{win.tokens} tokens, {win.compiles} compiles; ticks (s) median "
        f"{statistics.median(ticks):.4f}, longest {ticks[-1]:.4f}, "
        f"{sum(t > 1.0 for t in ticks)} over 1 s")
    log(f"after the window: {memory(devs)}")
    if trace is not None:
        for name, runs, secs in trace_reduce.top_programs(trace):
            log(f"traced program {name}: {runs} runs, {secs:.4f} s")
    recs = list(served.recs.values())
    attempted = served.sent
    failed = sum(r.finish not in (None, "length", "stop") for r in recs)
    checked = sample(recs, args.seed)
    del eng, obs, params, served
    gc.collect()

    if args.trace:
        peak = counts.peaks(devs[0].device_kind)
        run = types.SimpleNamespace(
            cfg=c, cell=cell, peak=peak, chips=cell["chips"],
            slots=c["deployment"]["slots"], window_s=win.window_s,
            tokens=win.tokens, trace=trace, traced_steps=win.traced,
            counters={"ticks": ticks1 - ticks0,
                      "prefill_chunks": chunks1 - chunks0,
                      "compiles": win.compiles})
        metrics = {}
        for m in spec.metrics(bench, "per_layer", cell["name"]):
            v = spec.reader(m["name"], root)(run)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device["busy_s"] = trace_reduce.busy_s(trace)
        device["window_s"] = trace_reduce.window_s(trace)
        breakdown = {"device_ops": trace_reduce.top_ops(trace),
                     "idle_gaps": trace_reduce.idle_gaps(trace)}
    else:
        values = {"tokens_per_s": win.tokens / win.window_s,
                  "itl_p95_ms": 1e3 * p95(win.gaps) if len(win.gaps) > 1
                  else None,
                  "setup_s": setup_s}
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in spec.metrics(bench, "end_to_end", cell["name"])
                   if values.get(m["name"]) is not None}
        breakdown = None
    return types.SimpleNamespace(
        c=c, limits=limits, metrics=metrics, device=device,
        breakdown=breakdown, attempted=attempted, failed=failed,
        checked=checked)


def execute(args, root):
    from chipbench import reference
    out = serve_cell(args, root)
    limits, failed = out.limits, out.failed
    res = reference.max_gap(out.c, args.seed, out.checked)
    log(f"reference compared {res['tokens']} tokens of "
        f"{len(out.checked)} requests; widest gap {res['gap']}, largest "
        f"mean of one request {res['request_mean_gap']}")
    correct, checks = judge(limits, res, failed)
    line = {"correct": bool(correct), "attempted": out.attempted,
            "failed": failed, "metrics": out.metrics, "device": out.device}
    if out.breakdown is not None:
        line["breakdown"] = out.breakdown
    line["checks"] = {k: {"value": v["value"], "limit": v["limit"]}
                      for k, v in checks.items()}
    for name, v in checks.items():
        how, = limits["checks"][name]
        print(f"check {name} {v['value']} limit {how} {v['limit']} "
              f"{'ok' if v['ok'] else 'FAILED'}", file=sys.stderr)
    print(json.dumps(line), flush=True)
    return 0


def main(argv=None, root: Path = ROOT) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        return execute(args, Path(root))
    except NoChip as e:
        print(f"[chipbench] {e}", file=sys.stderr)
        return 2
    except Exception:
        traceback.print_exc()
        return 1


if __name__ == "__main__":
    sys.exit(main())
