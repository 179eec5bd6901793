"""Parameters and bytes of a decoder-hybrid-decoder (Phi-4-mini-flash-
reasoning, SambaY) from a configuration file's keys: what ``ops.py``
cannot count (it knows one kind of layer and keys and values in every
one of them). Five mixers (Mamba-1, differential attention under a
window and without one, gated memory units, cross attention on the one
full layer's keys and values) and three kinds of per-sequence memory:
the full layer's keys and values a token, the window layers' for the
newest ``sliding_window`` tokens, one state of fixed size."""
from __future__ import annotations

BF16 = 2
F32 = 4


def head_dim(cfg: dict) -> int:
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def inner(cfg: dict) -> int:
    """``E``: the width of Mamba and of the memory units."""
    return cfg["ssm_expand"] * cfg["hidden_size"]


def dt_rank(cfg: dict) -> int:
    return -(-cfg["hidden_size"] // 16)


def layer_kinds(cfg: dict) -> list:
    """The mixer of every layer, by index."""
    half = cfg["num_hidden_layers"] // 2
    out = []
    for i in range(cfg["num_hidden_layers"]):
        if i % 2 == 0:
            out.append("ssm" if i <= half else "gmu")
        elif i < half:
            out.append("window")
        else:
            out.append("full" if i == half + 1 else "cross")
    return out


def mixer_params(cfg: dict, kind: str) -> int:
    d, e, hd = cfg["hidden_size"], inner(cfg), head_dim(cfg)
    q = cfg["num_attention_heads"] * hd
    kv = cfg["num_key_value_heads"] * hd
    n, r = cfg["ssm_d_state"], dt_rank(cfg)
    # The four lambda vectors, the pair's norm, the output projection
    # and its bias.
    diff = 4 * hd + 2 * hd + q * d + d
    if kind == "ssm":
        # in, conv and its bias, x, dt and its bias, A, D, out.
        return (d * 2 * e + cfg["ssm_d_conv"] * e + e + e * (r + 2 * n)
                + r * e + e + e * n + e + e * d)
    if kind in ("window", "full"):
        return d * (q + 2 * kv) + (q + 2 * kv) + diff
    if kind == "gmu":
        return 2 * d * e
    if kind == "cross":
        return d * q + q + diff
    raise ValueError(kind)


def mlp_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def layer_params(cfg: dict, kind: str) -> int:
    """Mixer, MLP and the two LayerNorms (weight and bias each)."""
    return mixer_params(cfg, kind) + mlp_params(cfg) \
        + 4 * cfg["hidden_size"]


def head_params(cfg: dict) -> int:
    return cfg["hidden_size"] * cfg["vocab_size"]


def total_params(cfg: dict) -> int:
    """Every parameter the program holds: the layers, the final
    LayerNorm, and the embedding, which is the head too."""
    return (sum(layer_params(cfg, k) for k in layer_kinds(cfg))
            + 2 * cfg["hidden_size"] + head_params(cfg))


def kv_bytes_per_token_layer(cfg: dict) -> int:
    """Keys and values of one token in one attention layer."""
    return 2 * cfg["num_key_value_heads"] * head_dim(cfg) * BF16


def state_bytes_per_sequence(cfg: dict) -> int:
    """One sequence's recurrent state, every Mamba layer: ``h`` (E x N,
    float32) and the conv's last inputs (bfloat16)."""
    e = inner(cfg)
    per_layer = e * cfg["ssm_d_state"] * F32 \
        + (cfg["ssm_d_conv"] - 1) * e * BF16
    return layer_kinds(cfg).count("ssm") * per_layer


def block_bytes(cfg: dict, block_tokens: int) -> dict:
    """Bytes of one pool block, by kind."""
    per_layer = block_tokens * kv_bytes_per_token_layer(cfg)
    kinds = layer_kinds(cfg)
    return {"global": kinds.count("full") * per_layer,
            "window": kinds.count("window") * per_layer,
            "state": state_bytes_per_sequence(cfg)}


def sequence_bytes(cfg: dict, tokens: int, block_tokens: int) -> int:
    """What a sequence of ``tokens`` holds: ceil(tokens / block) blocks
    of the full layer, of the window layers at most the blocks a window
    can touch, one state."""
    per = block_bytes(cfg, block_tokens)
    blocks = -(-tokens // block_tokens)
    window = -(-cfg["sliding_window"] // block_tokens) + 1
    return (blocks * per["global"] + min(blocks, window) * per["window"]
            + per["state"])


def decode_weight_bytes(cfg: dict) -> int:
    """Bytes of weights one decode step must read: every layer, the
    final norm, and the tied matrix ONCE, as the head (the embedding is
    a gather of one row a token and is left out)."""
    return BF16 * (sum(layer_params(cfg, k) for k in layer_kinds(cfg))
                   + 2 * cfg["hidden_size"] + head_params(cfg))


def decode_sequence_bytes(cfg: dict, tokens: float) -> float:
    """Bytes one decoding sequence of ``tokens`` adds to a step: its
    state read and written, the window layers' keys and values of its
    newest ``sliding_window`` tokens, and the full layer's keys and
    values read by that layer and by every cross layer."""
    kinds = layer_kinds(cfg)
    per = kv_bytes_per_token_layer(cfg)
    readers = kinds.count("full") + kinds.count("cross")
    return (2 * state_bytes_per_sequence(cfg)
            + min(tokens, cfg["sliding_window"])
            * kinds.count("window") * per
            + readers * tokens * per)


def decode_step_bytes(cfg: dict, lengths) -> float:
    """Bytes one decode step must move: the weights once, and what
    each decoding sequence adds (``lengths``: their tokens, each
    weighted 1, or (tokens, weight) pairs for a mean over a window)."""
    total = float(decode_weight_bytes(cfg))
    for item in lengths:
        tokens, weight = item if isinstance(item, tuple) else (item, 1.0)
        total += weight * decode_sequence_bytes(cfg, tokens)
    return total
