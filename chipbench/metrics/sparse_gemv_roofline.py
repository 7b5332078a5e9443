"""Roofline share of the ``sparse_gemv`` kernel (the decode path of eight
rows or fewer) in the traced decode steps: the least time of every sparse
linear call of those steps (``counts.linear_step_least_s`` at the engine's
slot count: bf16 non-zeros, a bitmap bit per weight and the activations,
each call bounded by HBM or compute, whichever is slower -- HBM at eight
rows) over the device time of the ``sparse_gemv`` kernels inside the
decode-step runs that lie whole in the traced window.  None where no such
kernel ran (a cell that decodes more than eight rows a step)."""
from chipbench import counts, trace_reduce

KERNEL = "sparse_gemv"


def read(run):
    if run.trace is None:
        return None
    steps = trace_reduce.decode_steps(run.trace)
    secs, _ = trace_reduce.op_seconds(
        run.trace, lambda n: trace_reduce.base(n) == KERNEL, within=steps)
    if not steps or secs <= 0:
        return None
    least = len(steps) * counts.linear_step_least_s(run.cfg, run.slots,
                                                    run.peak)
    return 100.0 * least / secs
