"""``decode_hbm_pct`` for a latent-attention, held-experts decoder:
the bytes one decode step must read (``ops_mla_moe.decode_step_bytes``:
the weights outside the routed experts, the held experts that the
step's tokens chose — ``stpu_moe_experts_hit_total`` a decode step over
the window —, the head, and 1,152 B a layer for every live token) over
the mean device time of ``_paged_step`` times the chips' peak bytes a
second. Chosen experts are counted, not all held ones, so the share
stays under 100 whether the expert layer computes all or only those."""
from benchmarks import ops_mla_moe
from benchmarks.layer_metrics import _common, _scrapes, decode_hbm_pct

NAME, UNIT, BETTER = "decode_hbm_pct.mla_moe", "%", "higher"
LAYER = "kernel"
MOVES = "tpot_p95_ms"
SOURCE = "device_trace"
RUNNERS = ("serve",)


def compute(run):
    cfg = run["config"]
    if cfg.get("family") != "deepseek" or not run.get("profile"):
        return None
    step_ms = _common.program_mean_ms(run, "_paged_step")
    hit = _scrapes.counter_delta(run, "stpu_moe_experts_hit_total")
    steps = _scrapes.counter_delta(run, "stpu_engine_steps_total",
                                   kind="decode")
    if step_ms is None or hit is None or not steps:
        return None
    live = decode_hbm_pct.live_kv_tokens(run["records"], *run["profile"])
    need = ops_mla_moe.decode_step_bytes(cfg, hit / steps, live)
    peak = run["peaks"]["hbm_bytes_per_s"] * run["trace"]["devices"]
    return 100.0 * need / (step_ms / 1e3 * peak)
