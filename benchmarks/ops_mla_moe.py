"""Parameters and bytes of a latent-attention, held-experts decoder
(DeepSeek-V3), from a configuration file's published keys: what
``ops.py`` cannot count (it reads ``intermediate_size`` as every layer's
MLP and ``hidden / heads`` as the head size). In such a file
``n_routed_experts`` counts the experts HELD on this chip and
``n_routed_experts_published`` is the router's width (configs/
deepseek-v3-5l-ep16.json)."""
from __future__ import annotations

BF16 = 2


def attention_params(cfg: dict) -> int:
    """q_a, q_b, kv_a, kv_b (nope keys and values), o."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    q, c = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    nope, rope, v = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                     cfg["v_head_dim"])
    return (d * q + q * h * (nope + rope) + d * (c + rope)
            + c * h * (nope + v) + h * v * d)


def norm_params(cfg: dict) -> int:
    """A layer's four norms: input, q latent, kv latent, MLP input."""
    return (2 * cfg["hidden_size"] + cfg["q_lora_rank"]
            + cfg["kv_lora_rank"])


def expert_params(cfg: dict) -> int:
    """gate, up, down of one routed (or one shared) expert."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def router_params(cfg: dict) -> int:
    """The router over ALL routed experts and its selection bias."""
    return (cfg["hidden_size"] + 1) * cfg["n_routed_experts_published"]


def dense_layer_params(cfg: dict) -> int:
    return (attention_params(cfg) + norm_params(cfg)
            + 3 * cfg["hidden_size"] * cfg["intermediate_size"])


def sparse_layer_params(cfg: dict, experts: int) -> int:
    """A sparse layer holding ``experts`` routed experts."""
    return (attention_params(cfg) + norm_params(cfg) + router_params(cfg)
            + (experts + cfg["n_shared_experts"]) * expert_params(cfg))


def sparse_layers(cfg: dict) -> int:
    return cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]


def head_params(cfg: dict) -> int:
    return cfg["hidden_size"] * cfg["vocab_size"]


def total_params(cfg: dict) -> int:
    """Every parameter the program holds: embedding, dense layers,
    sparse layers with their held experts, final norm, untied head."""
    return (cfg["first_k_dense_replace"] * dense_layer_params(cfg)
            + sparse_layers(cfg)
            * sparse_layer_params(cfg, cfg["n_routed_experts"])
            + cfg["hidden_size"] + 2 * head_params(cfg))


def latent_bytes_per_token(cfg: dict) -> int:
    """Bytes one cached token holds, all layers: the latent and the
    roped key all heads share."""
    return (cfg["num_hidden_layers"]
            * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) * BF16)


def decode_step_bytes(cfg: dict, experts_hit: float,
                      live_tokens: float) -> float:
    """Bytes one decode step must read from HBM: the weights outside
    the routed experts once (the embedding is a gather and left out),
    the final norm and the head, the ``experts_hit`` held experts
    (summed over the sparse layers) that some token of the step chose,
    and every live token's latent rows."""
    weights = (cfg["first_k_dense_replace"] * dense_layer_params(cfg)
               + sparse_layers(cfg) * sparse_layer_params(cfg, 0)
               + cfg["hidden_size"] + head_params(cfg)
               + experts_hit * expert_params(cfg))
    return BF16 * weights + live_tokens * latent_bytes_per_token(cfg)
