"""1 - (union of the intervals in which an operation ran on the device)
/ (traced window), mean over the chips, while serving."""
from benchmarks.layer_metrics import _common

NAME, UNIT, BETTER = "device_idle_pct.serve", "%", "lower"
LAYER = "device"
MOVES = "completed_tok_s"
SOURCE = "device_trace"
RUNNERS = ("serve",)


def compute(run):
    return _common.idle_pct(run)
