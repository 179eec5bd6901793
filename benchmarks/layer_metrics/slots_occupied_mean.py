"""Mean of ``stpu_engine_slots_occupied`` scraped every 0.5 s of the
window."""
from benchmarks import loadgen

NAME, UNIT, BETTER = "slots_occupied_mean", "count", "higher"
LAYER = "scheduler"
MOVES = "completed_tok_s"
SOURCE = "program_counter"
RUNNERS = ("serve",)


def compute(run):
    vals = loadgen.gauge_series(run["samples"],
                                "stpu_engine_slots_occupied",
                                run["t0"], run["t1"])
    return sum(vals) / len(vals) if vals else None
