"""Device busy time per step over the steady steps of the trace."""
NAME, UNIT, BETTER = "train_step_device_ms", "ms", "lower"
LAYER = "trainer"
MOVES = "train_tok_s"
SOURCE = "device_trace"
RUNNERS = ("train",)


def compute(run):
    trace = run.get("trace") or {}
    if not trace.get("steps"):
        return None
    return trace["busy_s"] / trace["steps"] * 1e3
