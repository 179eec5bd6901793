"""Milliseconds of the window inside instances of the engine loop's
``schedule`` phases (``_window.PARTS``) that lasted 0.06 s or more
(``stpu_engine_long_phase_seconds_total``): a pause of the engine
thread, named by the phase it struck. 0 is the value of a run without
one; None on a program without the counter."""
from benchmarks.layer_metrics import _window

NAME, UNIT, BETTER = "engine_long_phase_ms.schedule", "ms", "lower"
LAYER = "scheduler"
MOVES = "tpot_p95_ms"
SOURCE = "program_counter"
RUNNERS = ("serve",)


def compute(run):
    return _window.long_phase_ms(run, "schedule")
