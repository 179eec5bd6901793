"""1 - (union of the intervals in which an operation ran on the device)
/ (span of the steady steps in the trace), mean over the chips, while
training."""
from benchmarks.layer_metrics import _common

NAME, UNIT, BETTER = "device_idle_pct.train", "%", "lower"
LAYER = "device"
MOVES = "train_tok_s"
SOURCE = "device_trace"
RUNNERS = ("train",)


def compute(run):
    return _common.idle_pct(run)
