"""``ops_hybrid.py`` against the program's own parameter tree and pools
(shapes only: ``jax.eval_shape``), at the configuration's ``tiny`` sizes
and at the cell's, and against counts made by hand; the new metrics'
readers on made-up runs; and the new cell's ``--tiny`` rehearsal, end to
end on the CPU."""
import importlib.util
import os
import pathlib
import subprocess
import sys

import jax
import pytest

from benchmarks import cells, ops_hybrid

CELL = "phi4flash-fewshot-reason-steady"


@pytest.mark.parametrize("tiny", [True, False])
def test_counts_equal_the_programs_tree_and_pools(tiny):
    config = cells.load_cell(CELL, tiny=tiny)["config"]
    module, cfg = cells.model_config(config)
    tree = jax.eval_shape(lambda: module.init(cfg, jax.random.key(0)))
    assert ops_hybrid.total_params(config) == sum(
        a.size for a in jax.tree.leaves(tree))
    assert ops_hybrid.layer_kinds(config) == [
        {"attn": "full" if group == "mid" else "window"}.get(kind, kind)
        for group, kind, _ in module.layer_kinds(cfg)]
    from skypilot_tpu.serve import kv_pool
    assert ops_hybrid.block_bytes(config, 64) == \
        kv_pool.block_bytes_by_kind(cfg, 64)
    assert config["ssm_dt_rank"] == cfg.dt_rank == ops_hybrid.dt_rank(config)


def test_the_cells_sizes_by_hand():
    cfg = cells.load_cell(CELL)["config"]
    assert cfg["reduced"] == []
    assert ops_hybrid.layer_kinds(cfg) == (
        ["ssm", "window"] * 8 + ["ssm", "full"] + ["gmu", "cross"] * 7)
    mlp = 3 * 2560 * 10240 + 4 * 2560
    assert ops_hybrid.layer_params(cfg, "ssm") == mlp + (
        2560 * 10240 + 5 * 5120 + 5120 * 192 + 160 * 5120 + 5120
        + 5120 * 16 + 5120 + 5120 * 2560) == 119_895_040
    assert ops_hybrid.layer_params(cfg, "window") == mlp + (
        2560 * 5120 + 5120 + 6 * 64 + 2560 * 2560 + 2560) == 98_322_304
    assert ops_hybrid.layer_params(cfg, "full") == 98_322_304
    assert ops_hybrid.layer_params(cfg, "gmu") == mlp + 2 * 2560 * 5120 \
        == 104_867_840
    assert ops_hybrid.layer_params(cfg, "cross") == mlp + (
        2560 * 2560 + 2560 + 6 * 64 + 2560 * 2560 + 2560) == 91_766_144
    assert ops_hybrid.head_params(cfg) == 512_163_840
    # 9 x 119.90 + 9 x 98.32 + 7 x 104.87 + 7 x 91.77 + 512.16 M and the
    # final norm: ISSUE 36's 3,852.6 M, the published "3.8B".
    assert ops_hybrid.total_params(cfg) == 3_852_562_944
    assert ops_hybrid.kv_bytes_per_token_layer(cfg) == 5_120
    assert ops_hybrid.state_bytes_per_sequence(cfg) == 9 * (
        327_680 + 30_720) == 3_225_600
    assert ops_hybrid.block_bytes(cfg, 64) == {
        "global": 327_680, "window": 2_621_440, "state": 3_225_600}
    # At the server's 1,280-token cap; 62.2 MB if every attention
    # layer kept every block.
    assert ops_hybrid.sequence_bytes(cfg, 1280, 64) == (
        20 * 327_680 + 9 * 2_621_440 + 3_225_600) == 33_372_160
    assert 20 * 9 * 327_680 + 3_225_600 == 62_208_000
    weights = 2 * 3_852_562_944
    assert ops_hybrid.decode_weight_bytes(cfg) == weights
    assert ops_hybrid.decode_step_bytes(cfg, []) == weights
    # A sequence of 700 tokens: the state twice, 512 tokens of eight
    # window layers, 700 tokens of one layer read by eight.
    one = 2 * 3_225_600 + 512 * 40_960 + 8 * 700 * 5_120
    assert ops_hybrid.decode_sequence_bytes(cfg, 700) == one == 56_094_720
    assert ops_hybrid.decode_sequence_bytes(cfg, 100) == (
        2 * 3_225_600 + 100 * 40_960 + 8 * 100 * 5_120)
    assert ops_hybrid.decode_step_bytes(cfg, [700, (700, 0.5)]) == \
        weights + 1.5 * one


def _metric(name):
    path = pathlib.Path(cells.ROOT) / "layer_metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _run(family="phi4flash"):
    cfg = dict(cells.load_cell(CELL)["config"], family=family)

    def sample(scale):
        return {("stpu_engine_slots_occupied", ()): 10.0,
                ("stpu_engine_cache_blocks", (("kind", "global"),)): 100.0,
                ("stpu_engine_cache_blocks", (("kind", "window"),)): 80.0,
                ("stpu_engine_cache_blocks", (("kind", "state"),)): 10.0,
                ("stpu_engine_cache_blocks", (("kind", "snapshot"),)): 30.0,
                ("stpu_engine_window_blocks_released_total", ()): 50 * scale,
                ("stpu_engine_requests_total",
                 (("outcome", "ok"),)): 10 * scale,
                ("stpu_engine_state_snapshots_total",
                 (("event", "restored"),)): 9 * scale,
                ("stpu_engine_prefix_cache_hits_total", ()): 9 * scale,
                ("stpu_engine_prefix_cache_misses_total", ()): 1 * scale}

    return {"config": cfg, "child": {"kv": {"chunk": 64}},
            "samples": [(1.0, sample(1.0)), (2.0, sample(3.0))],
            "t0": 0.0, "t1": 3.0, "profile": (1.0, 2.0),
            "records": [
                {"first": 0.0, "last": 4.0, "tokens": [0] * 200,
                 "prompt_tokens": 600},
                {"first": 1.5, "last": 3.5, "tokens": [0] * 100,
                 "prompt_tokens": 500},
                {"first": None, "last": None, "tokens": [],
                 "prompt_tokens": 400}],
            "trace": {"devices": 1, "programs": {
                "_paged_step": {"count": 100, "total_s": 1.2}}},
            "peaks": {"hbm_bytes_per_s": 819e9}}


def test_the_new_metrics_on_a_made_up_run():
    run = _run()
    cfg = run["config"]
    assert _metric("hybrid_cache_bytes_per_slot").compute(run) == (
        100 * 327_680 + 80 * 2_621_440 + 10 * 3_225_600) / 10
    assert _metric("window_blocks_released_per_request").compute(run) == 5.0
    assert _metric("snapshot_hit_pct.hybrid").compute(run) == 90.0
    # One sequence decodes all through the window at 600 + 75 tokens,
    # one through its second half at 500 + 12.5.
    need = (2 * 3_852_562_944
            + ops_hybrid.decode_sequence_bytes(cfg, 675.0)
            + 0.5 * ops_hybrid.decode_sequence_bytes(cfg, 512.5))
    got = _metric("decode_hbm_pct.hybrid").compute(run)
    assert got == pytest.approx(100 * need / (0.012 * 819e9))
    assert 70 < got < 90


@pytest.mark.parametrize("name", [
    "hybrid_cache_bytes_per_slot", "window_blocks_released_per_request",
    "snapshot_hit_pct.hybrid", "decode_hbm_pct.hybrid"])
def test_the_new_metrics_read_nothing_elsewhere(name):
    """``None`` for every other family, and for this one on a program
    that exports none of the series (the parent)."""
    mod = _metric(name)
    assert mod.compute(_run(family="brumby")) is None
    bare = _run()
    bare["samples"] = [(t, {k: v for k, v in s.items()
                            if k[0] == "stpu_engine_slots_occupied"})
                       for t, s in bare["samples"]]
    bare["trace"] = {"devices": 1, "programs": {}}
    assert mod.compute(bare) is None


def test_the_new_cells_tiny_rehearsal_passes_on_the_cpu(tmp_path):
    """Every step of a run at the configuration's ``tiny`` sizes: the
    server, the traffic, the reference check, the new metrics' readers.
    Exit code 1 is a rehearsal that passed (``run.py``)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(pathlib.Path(cells.ROOT) / "run.py"),
         "--workload", CELL, "--seed", "3600000011", "--seconds", "6",
         "--trace", "1", "--tiny", "--out", str(tmp_path)],
        capture_output=True, text=True, timeout=900, env=env)
    assert proc.returncode == 1, proc.stdout[-3000:] + proc.stderr[-3000:]
    assert "rehearsal passed" in proc.stdout
    for metric in ("hybrid_cache_bytes_per_slot",
                   "window_blocks_released_per_request",
                   "snapshot_hit_pct.hybrid"):
        assert f'"{metric}"' in proc.stdout
