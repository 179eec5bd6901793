"""Start-up phase ``engine`` of the server's process
(``stpu_startup_seconds_total{phase=engine}`` at the window's first
scrape): the decode engine built: pool layout and allocation, the trie.
With the other three it splits ``ready_s`` from inside the process.
None on a program without the series."""
from benchmarks.layer_metrics import _window

NAME, UNIT, BETTER = "startup_engine_s", "s", "lower"
LAYER = "entry"
MOVES = "setup_s"
SOURCE = "program_counter"
RUNNERS = ("serve",)


def compute(run):
    return _window.startup_s(run, "engine")
