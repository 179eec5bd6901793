"""Child start -> the server's /health says ok, or -> the first loss."""
NAME, UNIT, BETTER = "ready_s", "s", "lower"
LAYER = "entry"
MOVES = "setup_s"
SOURCE = "host_clock"
RUNNERS = ("serve", "train")


def compute(run):
    src = run.get("train") or run.get("child") or {}
    return src.get("ready_s")
