"""p95 of the engine's own submit-to-first-token histogram
(``stpu_engine_ttft_seconds``), as the difference between the scrapes at
the window's two ends, interpolated inside the bucket."""
from benchmarks import loadgen

NAME, UNIT, BETTER = "engine_ttft_p95_ms", "ms", "lower"
LAYER = "scheduler"
MOVES = "tpot_p95_ms"
SOURCE = "program_counter"
RUNNERS = ("serve",)


def compute(run):
    bounds, cum = loadgen.histogram_delta(
        run["samples"], "stpu_engine_ttft_seconds", run["t0"], run["t1"])
    q = loadgen.histogram_quantile(bounds, cum, 0.95)
    return None if q is None else q * 1e3
