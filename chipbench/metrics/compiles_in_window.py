"""Programs compiled or loaded from the compile cache during the window
(JAX's backend-compile events), counted by the harness.  Every program
the window runs should have been compiled in set-up: anything above 0 is
a stall on the path to ``itl_p95_ms``."""


def read(run):
    return run.counters["compiles"]
