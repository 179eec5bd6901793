"""Share of decode steps the engine dispatched while the step before
was still unread on the device, so that the host's work between two
steps (emit, admit, plan) ran under a step and not after it: the
growth of ``stpu_engine_lookahead_steps_total`` over the growth of
``stpu_engine_steps_total{kind=decode|verify}``, between the scrapes
at the window's two ends. It falls where the loop has to read before
it plans (a slot drafting from its history, a verify step) and where
the engine runs dry between requests. None where the program is older
than the counter."""
from benchmarks.layer_metrics import _scrapes

NAME, UNIT, BETTER = "decode_lookahead_pct", "%", "higher"
LAYER = "scheduler"
MOVES = "tpot_p95_ms"
SOURCE = "program_counter"
RUNNERS = ("serve",)
KINDS = ("decode", "verify")


def compute(run):
    ahead = _scrapes.counter_delta(run, "stpu_engine_lookahead_steps_total")
    steps = [_scrapes.counter_delta(
        run, "stpu_engine_steps_total", kind=k) for k in KINDS]
    if ahead is None or None in steps or not sum(steps):
        return None
    return 100.0 * ahead / sum(steps)
