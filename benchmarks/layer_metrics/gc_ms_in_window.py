"""Milliseconds the server's garbage collector ran inside the window,
all generations (``stpu_process_gc_seconds_total``, a ``gc.callbacks``
hook): the first suspect for a pause of the engine thread, read beside
``engine_long_phase_ms.*``. None on a program without the counter."""
from benchmarks.layer_metrics import _scrapes

NAME, UNIT, BETTER = "gc_ms_in_window", "ms", "lower"
LAYER = "scheduler"
MOVES = "tpot_p95_ms"
SOURCE = "program_counter"
RUNNERS = ("serve",)


def compute(run):
    grown = [_scrapes.counter_delta(
        run, "stpu_process_gc_seconds_total", generation=g)
        for g in ("0", "1", "2")]
    return None if None in grown else 1e3 * sum(grown)
