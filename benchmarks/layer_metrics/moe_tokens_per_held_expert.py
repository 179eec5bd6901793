"""Tokens a held expert sees in a decode step, averaged over the held
experts, the sparse layers and the window's steps: the growth of
``stpu_moe_tokens_routed_total`` (token-expert pairs that landed on a
held expert) over decode steps x experts held x sparse layers. Higher
is nearer the load a rank of the stated deployment carries (each of its
ranks runs a batch of its own, so its experts see that batch from every
rank)."""
from benchmarks import ops_mla_moe
from benchmarks.layer_metrics import _scrapes

NAME, UNIT, BETTER = "moe_tokens_per_held_expert", "count", "higher"
LAYER = "model step"
MOVES = "tpot_p95_ms"
SOURCE = "program_counter"
RUNNERS = ("serve",)


def compute(run):
    cfg = run["config"]
    if cfg.get("family") != "deepseek":
        return None
    routed = _scrapes.counter_delta(run, "stpu_moe_tokens_routed_total")
    steps = _scrapes.counter_delta(run, "stpu_engine_steps_total",
                                   kind="decode")
    if routed is None or not steps:
        return None
    return routed / (steps * cfg["n_routed_experts"]
                     * ops_mla_moe.sparse_layers(cfg))
