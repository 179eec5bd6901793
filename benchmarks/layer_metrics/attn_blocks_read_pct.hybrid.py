"""The share of the key/value blocks that an all-slots gather moves which
a decode step reads: the growth of ``stpu_attn_blocks_read_total`` (the
blocks the step's sixteen attention reads fetched from the pools, counted
by the program from the per-slot counts that bound its kernel's loops and
read back beside the tokens) over decode steps x slots x the blocks a
slot's table spans in every layer that attends (8 window layers x 9
blocks + the full layer's 20, read by it and by 7 cross layers), in per
cent. A program that gathers for every slot to the table's end has no
such counter and moves all of them: None there, and for every other
family. Lower is fewer bytes a step, down to the decoding slots' visible
blocks."""
from benchmarks import ops_hybrid
from benchmarks.layer_metrics import _hybrid, _scrapes

NAME, UNIT, BETTER = "attn_blocks_read_pct.hybrid", "%", "lower"
LAYER = "model step"
MOVES = "tpot_p95_ms"
SOURCE = "program_counter"
RUNNERS = ("serve",)


def blocks_spanned(cfg, block_tokens: int, max_seq: int) -> int:
    """Blocks of one slot that the sixteen reads cover when each goes to
    its table's end: ceil(window / block) + 1 a window layer, the whole
    span for the full layer and each of its cross readers."""
    kinds = ops_hybrid.layer_kinds(cfg)
    window = -(-cfg["sliding_window"] // block_tokens) + 1
    span = max_seq // block_tokens
    return (kinds.count("window") * min(window, span)
            + (kinds.count("full") + kinds.count("cross")) * span)


def compute(run):
    cfg = run["config"]
    if cfg.get("family") != _hybrid.FAMILY:
        return None
    read = _scrapes.counter_delta(run, "stpu_attn_blocks_read_total")
    steps = _scrapes.counter_delta(run, "stpu_engine_steps_total",
                                   kind="decode")
    if read is None or not steps:
        return None
    kv = run["child"]["kv"]
    return 100.0 * read / (steps * kv["slots"] * blocks_spanned(
        cfg, kv["chunk"], kv["max_seq"]))
