"""Share of the chip's HBM bandwidth the state kernel reaches: the
decoding sequences' state bytes, read once and written once a decode
step (``ops_retention.state_bytes_per_sequence`` x the sequences
decoding in the traced window by the client's records), over the
kernel's summed device time a decode step. The kernel's operations are
found in the reduced trace by the kernel's name,
``stpu_retention_step``."""
from benchmarks import ops_retention
from benchmarks.layer_metrics import _retention

NAME, UNIT, BETTER = "retention_state_hbm_pct", "%", "higher"
LAYER = "kernel"
MOVES = "tpot_p95_ms"
SOURCE = "device_trace"
RUNNERS = ("serve",)
KERNEL = "stpu_retention_step"


def compute(run):
    cfg = run["config"]
    trace = run.get("trace") or {}
    if cfg.get("family") != "brumby" or not run.get("profile"):
        return None
    steps = (trace.get("programs") or {}).get("_paged_step")
    kernel_s = sum(op["self_s"] for name, op in
                   (trace.get("ops") or {}).items() if KERNEL in name)
    if not steps or not steps["count"] or not kernel_s:
        return None
    kernel_s /= steps["count"]
    need = (2.0 * _retention.live_sequences(run["records"], *run["profile"])
            * ops_retention.state_bytes_per_sequence(cfg))
    peak = run["peaks"]["hbm_bytes_per_s"] * trace["devices"]
    return 100.0 * need / (kernel_s * peak)
