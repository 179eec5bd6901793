"""``ops_retention.py`` against the program's own parameter tree and
state pool (shapes only: ``jax.eval_shape``), at the configuration's
``tiny`` sizes and at the cell's, and against counts made by hand; and
the new cell's ``--tiny`` rehearsal, end to end on the CPU."""
import os
import pathlib
import subprocess
import sys

import jax
import pytest

from benchmarks import cells, ops_retention

CELL = "brumby14b-reason-steady"


@pytest.mark.parametrize("tiny", [True, False])
def test_counts_equal_the_programs_tree_and_pool(tiny):
    config = cells.load_cell(CELL, tiny=tiny)["config"]
    module, cfg = cells.model_config(config)
    tree = jax.eval_shape(lambda: module.init(cfg, jax.random.key(0)))
    assert ops_retention.total_params(config) == sum(
        a.size for a in jax.tree.leaves(tree))
    assert config["retention_state_dim"] == cfg.state_dim
    pool = jax.eval_shape(lambda: module.init_paged_cache(cfg, 3, 64))
    nbytes = sum(a.size * a.dtype.itemsize for a in pool.values())
    assert ops_retention.state_bytes_per_sequence(config) == nbytes // 3


def test_the_cells_sizes_by_hand():
    cfg = cells.load_cell(CELL)["config"]
    # q 5120 x 5120, k and v 5120 x 1024, o 5120 x 5120, the gate
    # 5120 x 8, the MLP 3 x 5120 x 17408, norms 2 x 5120 + 2 x 128.
    assert ops_retention.layer_params(cfg) == (
        26_214_400 + 2 * 5_242_880 + 26_214_400 + 40_960
        + 267_386_880 + 10_496) == 330_352_896
    assert ops_retention.head_params(cfg) == 777_912_320
    # 6 layers, the final norm, embedding and head: ISSUE 33's count.
    assert ops_retention.total_params(cfg) == 3_537_947_136
    # 6 layers x 8 heads x 9216 x (128 + 1) x 4 bytes.
    assert ops_retention.state_bytes_per_sequence(cfg) == 228_261_888
    weights = 2 * (6 * 330_352_896 + 5120 + 777_912_320)
    assert ops_retention.decode_weight_bytes(cfg) == weights
    # Linear in the decoding sequences: each state read and written.
    assert ops_retention.decode_step_bytes(cfg, 0) == weights
    for live in (1, 7.5, 16):
        assert ops_retention.decode_step_bytes(cfg, live) == (
            weights + live * 2 * 228_261_888)


def test_the_new_cells_tiny_rehearsal_passes_on_the_cpu(tmp_path):
    """Every step of a run at the configuration's ``tiny`` sizes: the
    server, the traffic, the reference check, the new metrics' readers.
    Exit code 1 is a rehearsal that passed (``run.py``)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(pathlib.Path(cells.ROOT) / "run.py"),
         "--workload", CELL, "--seed", "3300000007", "--seconds", "6",
         "--trace", "1", "--tiny", "--out", str(tmp_path)],
        capture_output=True, text=True, timeout=900, env=env)
    assert proc.returncode == 1, proc.stdout[-3000:] + proc.stderr[-3000:]
    assert "rehearsal passed" in proc.stdout
    for metric in ("state_bytes_per_sequence", "snapshot_hit_pct"):
        assert f'"{metric}"' in proc.stdout
