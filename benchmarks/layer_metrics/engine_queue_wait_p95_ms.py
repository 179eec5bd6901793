"""p95 of the engine's submit-to-slot-assigned histogram
(``stpu_engine_queue_wait_seconds``) over the window: the part of
``engine_ttft_p95_ms`` a request spends waiting for a slot, before any
of its prompt is prefilled."""
from benchmarks.layer_metrics import _scrapes

NAME, UNIT, BETTER = "engine_queue_wait_p95_ms", "ms", "lower"
LAYER = "scheduler"
MOVES = "tpot_p95_ms"
SOURCE = "program_counter"
RUNNERS = ("serve",)


def compute(run):
    return _scrapes.histogram_p95_ms(run,
                                     "stpu_engine_queue_wait_seconds")
