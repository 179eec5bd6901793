"""``decode_hbm_pct`` for a decoder-hybrid-decoder: the bytes one
decode step must move (``ops_hybrid.decode_step_bytes``: every layer's
weights and the tied matrix once and, for every sequence DECODING in
the traced window by the client's records, its state read and written,
the window layers' keys and values of its newest 512 tokens, and the
full layer's read by that layer and by each cross layer) over the mean
device time of ``_paged_step`` times the chip's peak bytes a second.
Slots that do not decode are not counted, so a program that carries
them through the step reads lower, and the share cannot pass 100."""
from benchmarks import ops_hybrid
from benchmarks.layer_metrics import _common, _hybrid

NAME, UNIT, BETTER = "decode_hbm_pct.hybrid", "%", "higher"
LAYER = "kernel"
MOVES = "tpot_p95_ms"
SOURCE = "device_trace"
RUNNERS = ("serve",)


def compute(run):
    cfg = run["config"]
    if cfg.get("family") != _hybrid.FAMILY or not run.get("profile"):
        return None
    step_ms = _common.program_mean_ms(run, "_paged_step")
    if step_ms is None:
        return None
    need = ops_hybrid.decode_step_bytes(
        cfg, _hybrid.decoding_lengths(run["records"], *run["profile"]))
    peak = run["peaks"]["hbm_bytes_per_s"] * run["trace"]["devices"]
    return 100.0 * need / (step_ms / 1e3 * peak)
