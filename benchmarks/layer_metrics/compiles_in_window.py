"""XLA programs the server built inside the window
(``stpu_xla_compiles_total``, backend compilations and persistent-cache
reads alike): warm-up, the prefix warmers and the lead-in should have
reached every shape, so 0 is the value to expect, and it is a value."""
from benchmarks.layer_metrics import _scrapes

NAME, UNIT, BETTER = "compiles_in_window", "count", "lower"
LAYER = "model step"
MOVES = "tpot_p95_ms"
SOURCE = "program_counter"
RUNNERS = ("serve",)


def compute(run):
    built = [_scrapes.counter_delta(run, "stpu_xla_compiles_total",
                                    source=s)
             for s in ("compiled", "cache")]
    return None if None in built else sum(built)
