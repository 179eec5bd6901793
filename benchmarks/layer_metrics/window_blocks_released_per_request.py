"""Window-layer blocks a sequence gave back behind it, a request: the
growth of ``stpu_engine_window_blocks_released_total`` over that of the
requests that ended ok, over the window. 0 would mean that no sequence
crossed its window, or that every attention layer kept every block."""
from benchmarks.layer_metrics import _hybrid, _scrapes

NAME, UNIT, BETTER = "window_blocks_released_per_request", "count", "higher"
LAYER = "scheduler"
MOVES = "completed_tok_s"
SOURCE = "program_counter"
RUNNERS = ("serve",)


def compute(run):
    if run["config"].get("family") != _hybrid.FAMILY:
        return None
    released = _scrapes.counter_delta(
        run, "stpu_engine_window_blocks_released_total")
    done = _scrapes.counter_delta(run, "stpu_engine_requests_total",
                                  outcome="ok")
    if released is None or not done:
        return None
    return released / done
