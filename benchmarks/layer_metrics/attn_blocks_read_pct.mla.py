"""The share of the latent blocks that an all-slots read to the table's
end moves which a decode step reads: the growth of
``stpu_attn_blocks_read_total`` (the blocks the step's kernel calls
fetched from the latent pool, one call a layer, counted by the program
from the list that bounds the kernel's loop and read back beside the
tokens) over decode steps x slots x layers x the blocks a slot's table
spans, in per cent. A program whose step reads every slot's rows through
a tile loop counts nothing there (the series may exist, at 0, for
another family's kernel): None then, and for every other family. Lower
is fewer bytes a step, down to the decoding slots' own blocks."""
from benchmarks.layer_metrics import _scrapes

NAME, UNIT, BETTER = "attn_blocks_read_pct.mla", "%", "lower"
LAYER = "model step"
MOVES = "tpot_p95_ms"
SOURCE = "program_counter"
RUNNERS = ("serve",)


def compute(run):
    cfg = run["config"]
    if cfg.get("family") != "deepseek":
        return None
    read = _scrapes.counter_delta(run, "stpu_attn_blocks_read_total")
    steps = _scrapes.counter_delta(run, "stpu_engine_steps_total",
                                   kind="decode")
    if not read or not steps:
        return None
    kv = run["child"]["kv"]
    span = kv["max_seq"] // kv["chunk"]
    return 100.0 * read / (steps * kv["slots"] * cfg["num_hidden_layers"]
                           * span)
