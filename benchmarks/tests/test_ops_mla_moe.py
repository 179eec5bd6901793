"""``ops_mla_moe.py`` against the program's own parameter tree and pool
(shapes only: ``jax.eval_shape``), at the configuration's ``tiny`` sizes
and at the cell's, and against counts made by hand."""
import json
import pathlib

import jax
import pytest

from benchmarks import cells, ops_mla_moe

CONFIG = "deepseek-v3-5l-ep16"


def load(tiny):
    path = pathlib.Path(cells.ROOT) / "workloads"
    name = next(p.stem for p in sorted(path.glob("*.json"))
                if json.loads(p.read_text())["config"] == CONFIG)
    return cells.load_cell(name, tiny=tiny)["config"]


@pytest.mark.parametrize("tiny", [True, False])
def test_counts_equal_the_programs_tree_and_pool(tiny):
    config = load(tiny)
    module, cfg = cells.model_config(config)
    tree = jax.eval_shape(lambda: module.init(cfg, jax.random.key(0)))
    assert ops_mla_moe.total_params(config) == sum(
        a.size for a in jax.tree.leaves(tree))
    pool = jax.eval_shape(lambda: module.init_paged_cache(cfg, 3, 64))
    nbytes = sum(a.size * a.dtype.itemsize for a in pool.values())
    assert ops_mla_moe.latent_bytes_per_token(config) == nbytes // (3 * 64)


def test_the_cells_sizes_by_hand():
    cfg = load(False)
    # q_a 7168 x 1536, q_b 1536 x 128 x 192, kv_a 7168 x 576,
    # kv_b 512 x 128 x 256, o 16384 x 7168.
    assert ops_mla_moe.attention_params(cfg) == (
        11_010_048 + 37_748_736 + 4_128_768 + 16_777_216 + 117_440_512)
    assert ops_mla_moe.expert_params(cfg) == 44_040_192
    assert ops_mla_moe.router_params(cfg) == 7168 * 256 + 256
    assert ops_mla_moe.dense_layer_params(cfg) == (
        187_105_280 + 16_384 + 3 * 7168 * 18432)
    # Attention, norms, router, 16 held experts and the shared one.
    assert ops_mla_moe.sparse_layer_params(cfg, 16) == (
        187_105_280 + 16_384 + 1_835_264 + 17 * 44_040_192)
    # 1 dense + 4 sparse layers, final norm, embedding and head of
    # 16160 rows: 4,566 M.
    assert ops_mla_moe.total_params(cfg) == 4_565_721_088
    # 5 layers x (512 + 64) values x 2 bytes.
    assert ops_mla_moe.latent_bytes_per_token(cfg) == 5_760
    # No expert chosen, no live token: everything outside the routed
    # experts but the embedding.
    base = 2 * (583_483_392 + 4 * (937_640_192 - 16 * 44_040_192)
                + 7168 + 7168 * 16160)
    assert ops_mla_moe.decode_step_bytes(cfg, 0, 0) == base
    assert ops_mla_moe.decode_step_bytes(cfg, 40, 1000) == (
        base + 40 * 2 * 44_040_192 + 5_760_000)
    # All 64 held experts: what a step that computes every held expert
    # reads, 8.9 GB.
    assert 8.85e9 < ops_mla_moe.decode_step_bytes(cfg, 64, 0) < 8.95e9
