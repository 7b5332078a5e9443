"""Mean device time of one fold: the ``XLA Modules`` events of the
engine's refreeze program (``jit__refreeze``) that lie whole in the traced
window.  A fold stalls every decoding request for its whole length, so
this moves ``itl_p95_ms``.  ``None`` when the window holds no whole fold,
or when the program does not name its refreeze (it then traces as
``jit__lambda``, beside other programs of that name)."""
from chipbench import trace_reduce

PROGRAM = "jit__refreeze"


def read(run):
    if run.trace is None:
        return None
    runs = trace_reduce.modules(
        run.trace, lambda n: trace_reduce.base(n) == PROGRAM)
    if not runs:
        return None
    return 1e3 * sum(e.dur for e in runs) / 1e9 / len(runs)
