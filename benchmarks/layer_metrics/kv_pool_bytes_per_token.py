"""Device bytes the paged pool spends on one cached token, all layers:
the server's ``stpu_engine_kv_pool_block_bytes`` gauge over the tokens
of a block. A latent pool reads layers x 1,152; the same tokens as
expanded heads would read layers x 81,920."""
from benchmarks import loadgen

NAME, UNIT, BETTER = "kv_pool_bytes_per_token", "bytes", "lower"
LAYER = "scheduler"
MOVES = "completed_tok_s"
SOURCE = "program_counter"
RUNNERS = ("serve",)


def compute(run):
    if run["config"].get("family") != "deepseek":
        return None
    block = loadgen.gauge_series(run["samples"],
                                 "stpu_engine_kv_pool_block_bytes",
                                 run["t0"], run["t1"])
    return block[-1] / run["child"]["kv"]["chunk"] if block else None
