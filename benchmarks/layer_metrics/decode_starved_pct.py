"""Share of decode steps dispatched into a device whose queue had
drained: the growth of
``stpu_engine_starved_dispatches_total{kind=decode|verify}`` over the
growth of ``stpu_engine_steps_total{kind=decode|verify}`` between the
scrapes at the window's two ends. The loop polls ``is_ready()`` of the
newest program's result, never blocking; a starved step is one the
HOST kept the device waiting for. 0 is a value; None on a program
without the counter."""
from benchmarks.layer_metrics import _scrapes

NAME, UNIT, BETTER = "decode_starved_pct", "%", "lower"
LAYER = "scheduler"
MOVES = "tpot_p95_ms"
SOURCE = "program_counter"
RUNNERS = ("serve",)
KINDS = ("decode", "verify")


def compute(run):
    starved = [_scrapes.counter_delta(
        run, "stpu_engine_starved_dispatches_total", kind=k)
        for k in KINDS]
    steps = [_scrapes.counter_delta(
        run, "stpu_engine_steps_total", kind=k) for k in KINDS]
    if None in starved or None in steps or not sum(steps):
        return None
    return 100.0 * sum(starved) / sum(steps)
