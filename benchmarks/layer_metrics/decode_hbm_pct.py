"""Share of the chips' HBM bandwidth a decode step reaches: the bytes
one step must read (``ops.decode_step_bytes``: the weights once and the
live tokens' keys and values once) over the mean device time of the
``_paged_step`` program times the peak bytes a second of the chips the
model is spread over.

Live tokens are taken from the client's side: for every request, its
prompt and the tokens it had received, averaged over the traced window
(tokens taken as arriving evenly between the first and the last)."""
from benchmarks import ops
from benchmarks.layer_metrics import _common

NAME, UNIT, BETTER = "decode_hbm_pct", "%", "higher"
LAYER = "kernel"
MOVES = "tpot_p95_ms"
SOURCE = "device_trace"
RUNNERS = ("serve",)


def live_kv_tokens(records, a, b) -> float:
    total = 0.0
    for r in records:
        if r["first"] is None or r["last"] is None:
            continue
        lo, hi = max(r["first"], a), min(r["last"], b)
        if hi <= lo:
            continue
        span = max(r["last"] - r["first"], 1e-9)
        n = len(r["tokens"])
        mid = ((lo + hi) / 2 - r["first"]) / span * n
        total += (r["prompt_tokens"] + mid) * (hi - lo)
    return total / max(b - a, 1e-9)


def compute(run):
    step_ms = _common.program_mean_ms(run, "_paged_step")
    if step_ms is None or not run.get("profile"):
        return None
    trace = run["trace"]
    step_s = step_ms / 1e3
    a, b = run["profile"]
    need = ops.decode_step_bytes(run["config"],
                                 live_kv_tokens(run["records"], a, b))
    peak = run["peaks"]["hbm_bytes_per_s"] * trace["devices"]
    return 100.0 * need / (step_s * peak)
