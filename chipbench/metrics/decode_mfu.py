"""Model FLOP/s utilization of decoding over the traced window: the model
operations of every token decoded in it (``counts.decode_token_flops``:
2 per non-zero linear weight, 2 per unembedding weight, and attention over
the token's live context) over the traced window's length, the chips and
the chip's bf16 peak."""
from chipbench import counts, trace_reduce


def read(run):
    if run.trace is None:
        return None
    window = trace_reduce.window_s(run.trace)
    flops = sum(counts.decode_token_flops(run.cfg, n)
                for step in run.traced_steps for n, _ in step)
    if window <= 0 or flops <= 0:
        return None
    return 100.0 * flops / (window * run.chips
                            * run.peak["bf16_flops_per_s"])
