"""Parameters and bytes of a power-retention decoder (Brumby-14B) from
a configuration file's published keys: what ``ops.py`` cannot count (it
knows no gate, no per-head norms, and a cache that grows by the token
where this one is a state of fixed size). ``retention_state_dim`` is the
file's ``D``, the length of the feature map of one head."""
from __future__ import annotations

BF16 = 2
F32 = 4


def head_dim(cfg: dict) -> int:
    return int(cfg.get("head_dim")
               or cfg["hidden_size"] // cfg["num_attention_heads"])


def layer_params(cfg: dict) -> int:
    """q, k, v, o, the gate, the SwiGLU MLP, the two norms over the
    width and the two over a head."""
    d, hd = cfg["hidden_size"], head_dim(cfg)
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return (d * (h + 2 * kv) * hd + h * hd * d + d * kv
            + 3 * d * cfg["intermediate_size"] + 2 * d + 2 * hd)


def head_params(cfg: dict) -> int:
    return cfg["hidden_size"] * cfg["vocab_size"]


def total_params(cfg: dict) -> int:
    """Every parameter the program holds: embedding, layers, final
    norm, untied head."""
    return (cfg["num_hidden_layers"] * layer_params(cfg)
            + cfg["hidden_size"] + 2 * head_params(cfg))


def state_bytes_per_sequence(cfg: dict) -> int:
    """Bytes one sequence's state holds, all layers, whatever its
    length: S (head_dim x D) and z (D) a key/value head, float32."""
    return (cfg["num_hidden_layers"] * cfg["num_key_value_heads"]
            * cfg["retention_state_dim"] * (head_dim(cfg) + 1) * F32)


def decode_weight_bytes(cfg: dict) -> int:
    """Bytes of weights one decode step must read: every layer, the
    final norm and the head. The embedding is a gather of one row a
    token and is left out."""
    return BF16 * (cfg["num_hidden_layers"] * layer_params(cfg)
                   + cfg["hidden_size"] + head_params(cfg))


def decode_step_bytes(cfg: dict, live: float) -> float:
    """Bytes one decode step must move: the weights once, and the state
    of each of the ``live`` decoding sequences read once and written
    once."""
    return decode_weight_bytes(cfg) + 2.0 * live * state_bytes_per_sequence(cfg)
