"""XLA programs the server's back end COMPILED before the window
(``stpu_xla_compiles_total{source=compiled}`` at the window's first
scrape; reads from the persistent compile cache count under
``source=cache``), read beside the start-up phases it explains: a warm
start reads the few programs that compile in under half a second
(``compile_cache.enable()`` keeps those out of the cache), a cold one
the whole count, with the seconds under ``startup_warmup_s`` and
``startup_weights_s``. None on a program without the start-up phases:
the counter is older than they are (PR 26), the reading is not."""
from benchmarks.layer_metrics import _window

NAME, UNIT, BETTER = "startup_programs_compiled", "count", "lower"
LAYER = "entry"
MOVES = "setup_s"
SOURCE = "program_counter"
RUNNERS = ("serve",)


def compute(run):
    if _window.startup_s(run, "warmup") is None:
        return None
    return _window.first_value(run, "stpu_xla_compiles_total",
                               source="compiled")
