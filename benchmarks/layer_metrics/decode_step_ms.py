"""Mean device time of one execution of the ``_paged_step`` program
(``serve/decode_engine.py``) in the trace."""
from benchmarks.layer_metrics import _common

NAME, UNIT, BETTER = "decode_step_ms", "ms", "lower"
LAYER = "model step"
MOVES = "tpot_p95_ms"
SOURCE = "device_trace"
RUNNERS = ("serve",)
PROGRAM = "_paged_step"


def compute(run):
    return _common.program_mean_ms(run, PROGRAM)
