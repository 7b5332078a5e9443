#!/usr/bin/env python3
"""Record the small profiler trace that ``test_chipbench_trace.py`` reads.

Run on a TPU (it exits 1 elsewhere):

    python chipbench/tests/record_trace.py [OUT_DIR]

It serves a two-layer cut of qwen3-0.6b at full width through the
``ContinuousEngine`` path the benchmark drives (paged pool, prefill chunk
128, overlapped ticks, Pallas kernels), traces a few ticks with the
harness's own spans around ``submit`` and ``step``, and writes to OUT_DIR
(default ``chiprun_out/trace``):

* ``small.xplane.pb`` -- the trace, copied to ``chipbench/tests/data/``
  by hand once it has been looked at;
* ``layout.json`` -- planes, lines, event names and statistics, to look at;
* ``expect.json`` -- what ``trace_reduce`` reads from it, which the test
  pins.
"""
from __future__ import annotations

import dataclasses
import glob
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def layout(path: str) -> dict:
    import jax
    pd = jax.profiler.ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        lines = []
        for line in plane.lines:
            evs = list(line.events)
            names = {}
            for e in evs:
                names[e.name] = names.get(e.name, 0) + 1
            sample = [{"name": e.name, "start_ns": e.start_ns,
                       "duration_ns": e.duration_ns,
                       "stats": [[k, str(v)[:200]] for k, v in e.stats]}
                      for e in evs[:4]]
            lines.append({"name": line.name, "events": len(evs),
                          "names": sorted(names.items(),
                                          key=lambda kv: -kv[1])[:40],
                          "sample": sample})
        out.append({"name": plane.name,
                    "stats": [[k, str(v)[:200]] for k, v in plane.stats],
                    "lines": lines})
    return {"planes": out}


def main(argv) -> int:
    import jax
    out_dir = Path(argv[0] if argv else ROOT / "chiprun_out" / "trace")
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"needs a TPU, found {dev.platform}", file=sys.stderr)
        return 1
    from repro.configs import get_config
    from repro.distributed import NULL_CTX
    from repro.distributed.convert_plan import convert_concrete
    from repro.models import lm
    from repro.serving import ContinuousEngine, SamplingParams
    from chipbench import trace_reduce

    cfg = dataclasses.replace(get_config("qwen3-0.6b"), n_layers=2)
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    params = convert_concrete(params, lm.model_specs(cfg), cfg, NULL_CTX)
    eng = ContinuousEngine(params, cfg, slots=4, max_tokens=512,
                           prefill_chunk=128, paged=True, overlap=True)
    import numpy as np
    rng = np.random.default_rng(0)

    def submit(n_tok, steps):
        with jax.profiler.TraceAnnotation(trace_reduce.SPAN_SUBMIT):
            eng.submit(rng.integers(0, cfg.vocab, n_tok),
                       SamplingParams(max_new_tokens=steps))

    for n in (200, 72, 200, 72):          # warm every shape used below
        submit(n, 140)
    while not eng.scheduler.done():
        eng.step()
    eng.quiesce()
    for n in (200, 72, 200):
        submit(n, 60)
    for _ in range(4):
        eng.step()
    tmp = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(tmp, profiler_options=opts)
    with jax.profiler.TraceAnnotation(trace_reduce.SPAN_WINDOW):
        submit(72, 20)
        for _ in range(8):
            with jax.profiler.TraceAnnotation(trace_reduce.SPAN_STEP):
                eng.step()
            time.sleep(0.01)
    jax.profiler.stop_trace()
    eng.quiesce()
    path = glob.glob(f"{tmp}/**/*.xplane.pb", recursive=True)[0]
    out_dir.mkdir(parents=True, exist_ok=True)
    shutil.copy(path, out_dir / "small.xplane.pb")
    size = os.path.getsize(path)
    (out_dir / "layout.json").write_text(json.dumps(layout(path), indent=1))
    tr = trace_reduce.load(path)
    (out_dir / "expect.json").write_text(json.dumps(
        trace_reduce.summary(tr), indent=1))
    print(f"wrote {out_dir} ({size} bytes of trace)")
    shutil.rmtree(tmp, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
