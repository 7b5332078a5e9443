"""Share of the window's engine ticks that ran a prefill chunk, from the
program's own counters (``repro.obs``: prefill chunks over ticks, each
tick runs at most one chunk).  A tick with a chunk is a longer gap for
every decoding request, so this moves ``itl_p95_ms``."""


def read(run):
    ticks = run.counters["ticks"]
    if ticks <= 0:
        return None
    return 100.0 * run.counters["prefill_chunks"] / ticks
