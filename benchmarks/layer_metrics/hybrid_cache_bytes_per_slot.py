"""Device bytes of cache a live sequence holds, all three kinds: the
blocks that live slots' tables name (the gauge
``stpu_engine_cache_blocks{kind=global|window|state}``: distinct blocks,
so a shared prefix's count once) times the kind's block bytes
(``ops_hybrid.block_bytes``), over ``stpu_engine_slots_occupied``, each
a mean over the window's scrapes. At the server's 1,280-token cap a
sequence alone holds 33.4 MB; with every attention layer keeping every
block it would hold 62.2. The prefix trie's own nodes
(``kind=snapshot``) are the cache's, not a slot's, and are not
counted."""
from benchmarks import loadgen, ops_hybrid
from benchmarks.layer_metrics import _hybrid

NAME, UNIT, BETTER = "hybrid_cache_bytes_per_slot", "bytes", "lower"
LAYER = "scheduler"
MOVES = "completed_tok_s"
SOURCE = "program_counter"
RUNNERS = ("serve",)


def compute(run):
    cfg = run["config"]
    if cfg.get("family") != _hybrid.FAMILY:
        return None
    slots = loadgen.gauge_series(run["samples"],
                                 "stpu_engine_slots_occupied",
                                 run["t0"], run["t1"])
    per = ops_hybrid.block_bytes(cfg, run["child"]["kv"]["chunk"])
    total = 0.0
    for kind, nbytes in per.items():
        blocks = _hybrid.labelled_gauge_mean(
            run, "stpu_engine_cache_blocks", kind=kind)
        if blocks is None:
            return None
        total += blocks * nbytes
    occupied = sum(slots) / len(slots) if slots else 0.0
    return total / occupied if occupied else None
