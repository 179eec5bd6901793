"""Operations and bytes from shapes: the yardstick's own arithmetic.

Everything here is computed from a configuration file's published keys
(``hidden_size``, ``num_hidden_layers``, ...), never from the program's
``flops_per_token`` (which counts 6N where a LoRA step over a frozen
base needs 4N) and never from a measured quantity. A "required" count is
what the algorithm needs; what a kernel executes beyond it (recompute
under remat, masked blocks) is not counted unless a function says so.
"""
from __future__ import annotations

BF16 = 2


def _experts(cfg: dict) -> int:
    return int(cfg.get("num_local_experts", 1))


def head_dim(cfg: dict) -> int:
    return int(cfg.get("head_dim")
               or cfg["hidden_size"] // cfg["num_attention_heads"])


def attention_params(cfg: dict) -> int:
    """Matmul parameters of one layer's attention: q, k, v, o."""
    d, hd = cfg["hidden_size"], head_dim(cfg)
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return d * (h + 2 * kv) * hd + h * hd * d


def mlp_params(cfg: dict) -> int:
    """Matmul parameters of one layer's MLP: gate, up, down of every
    expert, and the router where there is one."""
    d, ff, e = cfg["hidden_size"], cfg["intermediate_size"], _experts(cfg)
    return e * 3 * d * ff + (d * e if e > 1 else 0)


def layer_params(cfg: dict) -> int:
    """All parameters of one layer, its two norms included."""
    return attention_params(cfg) + mlp_params(cfg) + 2 * cfg["hidden_size"]


def head_params(cfg: dict) -> int:
    return cfg["hidden_size"] * cfg["vocab_size"]


def total_params(cfg: dict) -> int:
    """Every parameter the program holds: embedding, layers, final
    norm, and the output head unless it is tied."""
    tied = bool(cfg.get("tie_word_embeddings"))
    return (cfg["num_hidden_layers"] * layer_params(cfg)
            + cfg["hidden_size"]
            + head_params(cfg) * (1 if tied else 2))


def kv_bytes_per_token(cfg: dict) -> int:
    """Bytes of keys and values one cached token holds, all layers."""
    return (2 * cfg["num_hidden_layers"] * cfg["num_key_value_heads"]
            * head_dim(cfg) * BF16)


def decode_weight_bytes(cfg: dict) -> int:
    """Bytes of weights one decode step must read: every layer (with a
    batch of tens of tokens choosing 2 of 8 experts each, every expert is
    chosen by some token, so all are required), the final norm and the
    output head. The embedding is a gather of one row a token and is
    left out."""
    return BF16 * (cfg["num_hidden_layers"] * layer_params(cfg)
                   + cfg["hidden_size"] + head_params(cfg))


def decode_step_bytes(cfg: dict, live_kv_tokens: float) -> float:
    """Bytes one decode step must read from HBM: the weights once and
    the keys and values of every live token once."""
    return decode_weight_bytes(cfg) + live_kv_tokens * kv_bytes_per_token(cfg)


def active_matmul_params(cfg: dict) -> int:
    """Matmul parameters one token passes through: attention, the MLP of
    the experts it is routed to, the router, the output head."""
    d, ff, e = cfg["hidden_size"], cfg["intermediate_size"], _experts(cfg)
    k = int(cfg.get("num_experts_per_tok", 1))
    mlp = k * 3 * d * ff + (d * e if e > 1 else 0)
    return (cfg["num_hidden_layers"] * (attention_params(cfg) + mlp)
            + head_params(cfg))


def attention_flops_per_token(cfg: dict, seq_len: int,
                              passes: float) -> float:
    """Causal score and value matmuls of one token at mean context
    ``seq_len / 2``: 2 * seq_len * hidden a layer forward (QK^T and PV,
    2 FLOPs a multiply-add, halved by the mask), times ``passes``."""
    return (passes * 2.0 * seq_len * cfg["hidden_size"]
            * cfg["num_hidden_layers"])


def lora_train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Required FLOPs a token of a LoRA step over a frozen base: the
    forward pass (2N) and the backward pass's activation gradients (2N;
    a frozen weight gets no gradient), N the matmul parameters a token
    passes through, plus causal attention forward and its backward (twice
    the forward: it has no weights). The adapters' own matmuls (rank 8:
    under 0.1% of N) and recomputation under remat are not counted."""
    return (4.0 * active_matmul_params(cfg)
            + attention_flops_per_token(cfg, seq_len, passes=3.0))
