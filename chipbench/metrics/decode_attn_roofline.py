"""Roofline share of the fused paged decode-attention kernel in the traced
decode steps: the least time of one step's attention over the live
contexts (``counts.decode_attention``: compressed prefix at the declared K
and V densities with its bitmaps, dense bf16 tail; each layer's call
bounded by HBM or compute, whichever is slower -- HBM at these contexts),
averaged over the steps whose tokens the harness saw delivered in the
trace, times the decode-step runs that lie whole in the traced window,
over the device time of the ``sparse_decode_attention_fused_paged``
kernels inside those runs."""
from chipbench import counts, trace_reduce

KERNELS = ("sparse_decode_attention_fused_paged",)


def read(run):
    if run.trace is None:
        return None
    steps = trace_reduce.decode_steps(run.trace)
    secs, _ = trace_reduce.op_seconds(
        run.trace, lambda n: trace_reduce.base(n) in KERNELS, within=steps)
    seen = [s for s in run.traced_steps if s]
    if not steps or secs <= 0 or not seen:
        return None
    layers = run.cfg["num_hidden_layers"]
    per_step = 0.0
    for contexts in seen:
        flops, nbytes = counts.decode_attention(run.cfg, contexts)
        per_step += layers * counts.least_s(flops / layers, nbytes / layers,
                                            run.peak)
    return 100.0 * len(steps) * (per_step / len(seen)) / secs
