"""Mean device time of one run of the decode-step program in the traced
window: the ``XLA Modules`` events of the engine's jitted decode
(``jit__decode_chain``, or ``jit__decode`` on a cold pipeline)."""
from chipbench import trace_reduce


def read(run):
    if run.trace is None:
        return None
    runs = trace_reduce.decode_steps(run.trace)
    if not runs:
        return None
    return 1e3 * sum(e.dur for e in runs) / 1e9 / len(runs)
