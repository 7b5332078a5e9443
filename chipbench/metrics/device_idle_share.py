"""Share of the traced window in which no operation ran on the device:
1 - (union of the ``XLA Ops`` intervals) / window, averaged over chips."""
from chipbench import trace_reduce


def read(run):
    if run.trace is None or not run.trace.n_devices:
        return None
    window = trace_reduce.window_s(run.trace)
    if window <= 0:
        return None
    return 100.0 * (1.0 - trace_reduce.busy_s(run.trace) / window)
