"""Roofline share of the sparse linear kernels in the traced decode steps:
the least time of every sparse linear call of those steps
(``counts.decode_linears`` at the engine's slot count, each call bounded
by HBM or compute, whichever is slower; at decode batch both cells are
HBM-bound) over the device time of the ``sparse_matmul`` and
``sparse_gemv`` kernels inside the decode-step runs that lie whole in the
traced window."""
from chipbench import counts, trace_reduce

KERNELS = ("sparse_matmul", "sparse_gemv")


def read(run):
    if run.trace is None:
        return None
    steps = trace_reduce.decode_steps(run.trace)
    secs, _ = trace_reduce.op_seconds(
        run.trace, lambda n: trace_reduce.base(n) in KERNELS, within=steps)
    if not steps or secs <= 0:
        return None
    least = len(steps) * counts.linear_step_least_s(run.cfg, run.slots,
                                                    run.peak)
    return 100.0 * least / secs
