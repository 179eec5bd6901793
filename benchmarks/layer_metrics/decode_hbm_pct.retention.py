"""``decode_hbm_pct`` for a power-retention decoder: the bytes one
decode step must move (``ops_retention.decode_step_bytes``: the layers'
and the head's weights once and, for every sequence DECODING in the
traced window by the client's records, its state read once and written
once) over the mean device time of ``_paged_step`` times the chip's
peak bytes a second. Slots that do not decode are not counted, so a
program that carried them through the step would read lower, and the
share cannot pass 100."""
from benchmarks import ops_retention
from benchmarks.layer_metrics import _common, _retention

NAME, UNIT, BETTER = "decode_hbm_pct.retention", "%", "higher"
LAYER = "kernel"
MOVES = "tpot_p95_ms"
SOURCE = "device_trace"
RUNNERS = ("serve",)


def compute(run):
    cfg = run["config"]
    if cfg.get("family") != "brumby" or not run.get("profile"):
        return None
    step_ms = _common.program_mean_ms(run, "_paged_step")
    if step_ms is None:
        return None
    need = ops_retention.decode_step_bytes(
        cfg, _retention.live_sequences(run["records"], *run["profile"]))
    peak = run["peaks"]["hbm_bytes_per_s"] * run["trace"]["devices"]
    return 100.0 * need / (step_ms / 1e3 * peak)
