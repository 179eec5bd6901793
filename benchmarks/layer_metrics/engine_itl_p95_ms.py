"""p95 of the gap between two token emissions of one slot as the engine
sees it (``stpu_engine_itl_seconds``, edges 15 % apart) over the
window: per token, where ``tpot_p95_ms`` is a client's mean over a
request."""
from benchmarks.layer_metrics import _scrapes

NAME, UNIT, BETTER = "engine_itl_p95_ms", "ms", "lower"
LAYER = "scheduler"
MOVES = "tpot_p95_ms"
SOURCE = "program_counter"
RUNNERS = ("serve",)


def compute(run):
    return _scrapes.histogram_p95_ms(run, "stpu_engine_itl_seconds")
