"""``snapshot_hit_pct`` for a family whose prefix hit aliases two kinds
of block AND restores a state snapshot: the growth of
``stpu_engine_state_snapshots_total{event="restored"}`` over that of
the prefix cache's hits and misses together, over the window."""
from benchmarks.layer_metrics import _hybrid, _scrapes

NAME, UNIT, BETTER = "snapshot_hit_pct.hybrid", "%", "higher"
LAYER = "scheduler"
MOVES = "tpot_p95_ms"
SOURCE = "program_counter"
RUNNERS = ("serve",)


def compute(run):
    if run["config"].get("family") != _hybrid.FAMILY:
        return None
    restored = _scrapes.counter_delta(
        run, "stpu_engine_state_snapshots_total", event="restored")
    hits = _scrapes.counter_delta(
        run, "stpu_engine_prefix_cache_hits_total")
    misses = _scrapes.counter_delta(
        run, "stpu_engine_prefix_cache_misses_total")
    if restored is None or hits is None or misses is None \
            or not hits + misses:
        return None
    return 100.0 * restored / (hits + misses)
