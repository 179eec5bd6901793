"""The share of the held experts' weights that a decode step reads: the
growth of ``stpu_moe_experts_computed_total`` (the held experts, summed
over sparse layers, that a step's program computed: what its expert
loop ran over, read back beside the tokens) over decode steps x experts
held x sparse layers, in per cent. A program that computes every held
expert for every token has no such counter and reads all of them: None
there. Lower is fewer bytes a step, down to the share that some
decoding row chose (``stpu_moe_experts_hit_total``, the host's count of
the same)."""
from benchmarks import ops_mla_moe
from benchmarks.layer_metrics import _scrapes

NAME, UNIT, BETTER = "moe_held_experts_read_pct", "%", "lower"
LAYER = "model step"
MOVES = "tpot_p95_ms"
SOURCE = "program_counter"
RUNNERS = ("serve",)


def compute(run):
    cfg = run["config"]
    if cfg.get("family") != "deepseek":
        return None
    computed = _scrapes.counter_delta(
        run, "stpu_moe_experts_computed_total")
    steps = _scrapes.counter_delta(run, "stpu_engine_steps_total",
                                   kind="decode")
    if computed is None or not steps:
        return None
    return 100.0 * computed / (steps * cfg["n_routed_experts"]
                               * ops_mla_moe.sparse_layers(cfg))
