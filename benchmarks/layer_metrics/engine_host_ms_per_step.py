"""Host milliseconds the engine thread spends per decode step outside
its blocking fetches: the growth of
``stpu_engine_loop_seconds_total{phase=schedule.*|emit}`` over the
growth of ``stpu_engine_steps_total{kind=decode|verify}``, between the
scrapes at the window's two ends (50 s of steps, not the trace's 3).
Prefill chunks' scheduling is in the numerator: an iteration with a
chunk costs the decoding slots that much more."""
from benchmarks.layer_metrics import _scrapes

NAME, UNIT, BETTER = "engine_host_ms_per_step", "ms", "lower"
LAYER = "scheduler"
MOVES = "tpot_p95_ms"
SOURCE = "program_counter"
RUNNERS = ("serve",)
PHASES = ("schedule.admit", "schedule.prefill", "schedule.decode", "emit")
KINDS = ("decode", "verify")


def compute(run):
    seconds = [_scrapes.counter_delta(
        run, "stpu_engine_loop_seconds_total", phase=p) for p in PHASES]
    steps = [_scrapes.counter_delta(
        run, "stpu_engine_steps_total", kind=k) for k in KINDS]
    if None in seconds or None in steps or not sum(steps):
        return None
    return 1e3 * sum(seconds) / sum(steps)
