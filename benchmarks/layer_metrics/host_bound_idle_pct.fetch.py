"""The device's idle share that the engine loop itself saw under
``fetch`` (``_window.PARTS``): seconds of
``stpu_engine_drained_seconds_total`` in those phases over the
window's length, in per cent. With the other two parts it is the
program's own LOWER bound on ``device_idle_pct.serve``, over the
whole window. 0 is a value; None on a program without the counter."""
from benchmarks.layer_metrics import _window

NAME, UNIT, BETTER = "host_bound_idle_pct.fetch", "%", "lower"
LAYER = "scheduler"
MOVES = "completed_tok_s"
SOURCE = "program_counter"
RUNNERS = ("serve",)


def compute(run):
    return _window.host_bound_idle_pct(run, "fetch")
