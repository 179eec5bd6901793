"""Device bytes the pool spends on one sequence, all layers, whatever
its length: the server's ``stpu_engine_kv_pool_block_bytes`` gauge,
where a block is a sequence's whole state. 228,261,888 at ``D`` 9216 in
float32; a GQA cache of the same heads holds that at 9,288 tokens."""
from benchmarks import loadgen

NAME, UNIT, BETTER = "state_bytes_per_sequence", "bytes", "lower"
LAYER = "scheduler"
MOVES = "completed_tok_s"
SOURCE = "program_counter"
RUNNERS = ("serve",)


def compute(run):
    if run["config"].get("family") != "brumby":
        return None
    block = loadgen.gauge_series(run["samples"],
                                 "stpu_engine_kv_pool_block_bytes",
                                 run["t0"], run["t1"])
    return block[-1] if block else None
