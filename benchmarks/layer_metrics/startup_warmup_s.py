"""Start-up phase ``warmup`` of the server's process
(``stpu_startup_seconds_total{phase=warmup}`` at the window's first
scrape): the chunk's and the step's programs built (or read from the
compile cache) and run once, until the server says ready. With the
other three it splits ``ready_s`` from inside the process. None on a
program without the series."""
from benchmarks.layer_metrics import _window

NAME, UNIT, BETTER = "startup_warmup_s", "s", "lower"
LAYER = "entry"
MOVES = "setup_s"
SOURCE = "program_counter"
RUNNERS = ("serve",)


def compute(run):
    return _window.startup_s(run, "warmup")
