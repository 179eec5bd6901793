"""``ops.py`` against counts made by hand from the published sizes."""
import json
import pathlib

from benchmarks import ops

CONFIGS = pathlib.Path(__file__).resolve().parents[1] / "configs"


def load(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())


def test_mistral_7b_16_layers():
    cfg = load("mistral-7b-v0.3-16l")
    # q, k, v: 4096 x (32 + 8 + 8) x 128; o: 4096 x 4096.
    assert ops.attention_params(cfg) == 25_165_824 + 16_777_216
    # gate, up, down: 3 x 4096 x 14336.
    assert ops.mlp_params(cfg) == 176_160_768
    assert ops.layer_params(cfg) == 218_112_000
    # 16 layers + embedding + head (32768 x 4096 each) + final norm.
    assert ops.total_params(cfg) == 3_758_231_552
    # 2 (K and V) x 16 layers x 8 heads x 128 x 2 bytes = 64 KiB.
    assert ops.kv_bytes_per_token(cfg) == 65_536
    # Layers, final norm and head in bf16; the embedding is a gather.
    assert ops.decode_weight_bytes(cfg) == 7_248_027_648
    assert ops.decode_step_bytes(cfg, 1000) == 7_248_027_648 + 65_536_000


def test_mistral_7b_lora_flops_per_token():
    cfg = load("mistral-7b-v0.3-16l")
    # N = 16 x (41,943,040 + 176,160,768) + 134,217,728 (head).
    assert ops.active_matmul_params(cfg) == 3_623_878_656
    # Attention: 3 passes x 2 x 2048 x 4096 x 16 layers.
    assert ops.attention_flops_per_token(cfg, 2048, 3.0) == 805_306_368
    assert ops.lora_train_flops_per_token(cfg, 2048) == (
        4 * 3_623_878_656 + 805_306_368)


def test_mixtral_8x7b_4_layers():
    cfg = load("mixtral-8x7b-4l")
    assert ops.attention_params(cfg) == 41_943_040
    # 8 experts x 176,160,768 + the router's 4096 x 8.
    assert ops.mlp_params(cfg) == 1_409_286_144 + 32_768
    assert ops.layer_params(cfg) == 1_451_270_144
    # 4 layers + embedding + head (32000 x 4096 each) + final norm.
    assert ops.total_params(cfg) == 6_067_228_672
    assert ops.kv_bytes_per_token(cfg) == 16_384
    assert ops.decode_weight_bytes(cfg) == 2 * (
        4 * 1_451_270_144 + 4096 + 131_072_000)
    # A token passes through 2 of 8 experts.
    assert ops.active_matmul_params(cfg) == 4 * (
        41_943_040 + 2 * 176_160_768 + 32_768) + 131_072_000
