"""Start-up phase ``weights`` of the server's process
(``stpu_startup_seconds_total{phase=weights}`` at the window's first
scrape): the parameter init traced, built (or read from the compile
cache) and dispatched. With the other three it splits ``ready_s`` from
inside the process. None on a program without the series."""
from benchmarks.layer_metrics import _window

NAME, UNIT, BETTER = "startup_weights_s", "s", "lower"
LAYER = "entry"
MOVES = "setup_s"
SOURCE = "program_counter"
RUNNERS = ("serve",)


def compute(run):
    return _window.startup_s(run, "weights")
