"""Gemma-class decoder transformer — third model family of the recipe
tree (reference analog: llm/gemma — the reference launches Gemma through
HF TGI/vLLM serve YAMLs, /root/reference/llm/gemma/README.md; here the
model is native).

Gemma exercises the generality of the shared llama kernel family with
three architectural deltas, all expressed as config knobs the shared
blocks honor (models/llama.py):

  * **RMSNorm with a (1 + w) scale** (weights init to zeros) —
    ``norm_offset = 1.0``;
  * **GeGLU MLP** (tanh-approx gelu gate instead of SiLU) —
    ``mlp_activation = "gelu_tanh"``;
  * **sqrt(dim)-scaled embeddings + tied LM head** —
    ``embed_multiplier``, no ``lm_head`` param;

plus **MQA** (n_kv_heads=1, the gemma-2B layout) and a head_dim (256)
decoupled from dim/n_heads, both of which the GQA attention stack and
the Pallas flash kernel already support — that coverage is the point of
the family (VERDICT r4 next #6).

Training, KV-cache decode, LoRA injection, and the serving loop are the
shared llama machinery applied to this config; only init/specs and the
config live here, exactly like mixtral shares the attention stack.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from skypilot_tpu.models import llama

Params = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class GemmaConfig:
    vocab_size: int = 256000
    dim: int = 2048
    n_layers: int = 18
    n_heads: int = 8
    n_kv_heads: int = 1          # MQA (gemma-2B); gemma-7B is MHA 16/16
    head_dim_: int = 256         # decoupled from dim // n_heads
    mlp_dim: int = 16384
    rope_theta: float = 10000.0
    norm_eps: float = 1e-6
    max_seq_len: int = 8192
    dtype: Any = jnp.bfloat16
    attention_impl: str = "auto"  # auto|pallas|reference|ring
    remat: bool = True
    remat_policy: str = "full"

    # Knobs the shared llama blocks read (see module docstring).
    norm_offset: float = 1.0
    mlp_activation: str = "gelu_tanh"
    tie_embeddings: bool = True

    @property
    def head_dim(self) -> int:
        return self.head_dim_

    @property
    def embed_multiplier(self) -> float:
        return math.sqrt(self.dim)

    @staticmethod
    def gemma_2b() -> "GemmaConfig":
        return GemmaConfig()

    @staticmethod
    def gemma_7b() -> "GemmaConfig":
        return GemmaConfig(dim=3072, n_layers=28, n_heads=16,
                           n_kv_heads=16, mlp_dim=24576)

    @staticmethod
    def tiny(vocab_size: int = 512) -> "GemmaConfig":
        return GemmaConfig(vocab_size=vocab_size, dim=128, n_layers=4,
                           n_heads=8, n_kv_heads=1, head_dim_=32,
                           mlp_dim=256, max_seq_len=512)

    @staticmethod
    def single_chip_bench() -> "GemmaConfig":
        """Gemma-2B geometry scaled to a 16 GB v5e chip for the serving
        bench (vocab shrunk like the llama/mixtral bench configs; the
        256k tied table alone is 1 GB bf16)."""
        return GemmaConfig(vocab_size=32768, dim=2048, n_layers=18,
                           n_heads=8, n_kv_heads=1, head_dim_=256,
                           mlp_dim=16384, max_seq_len=2048)

    def flops_per_token(self, seq_len: Optional[int] = None) -> float:
        """6N convention; with seq_len adds causal attention matmuls
        (same accounting as LlamaConfig.flops_per_token)."""
        p_layer = (self.dim * (self.n_heads + 2 * self.n_kv_heads) *
                   self.head_dim +
                   self.n_heads * self.head_dim * self.dim +
                   3 * self.dim * self.mlp_dim)
        p = self.n_layers * p_layer + self.vocab_size * self.dim * (
            1 if self.tie_embeddings else 2)
        flops = 6.0 * p
        if seq_len is not None:
            flops += 6.0 * self.n_layers * seq_len * \
                self.n_heads * self.head_dim
        return flops

    def num_params(self) -> int:
        p_layer = (self.dim * (self.n_heads + 2 * self.n_kv_heads) *
                   self.head_dim +
                   self.n_heads * self.head_dim * self.dim +
                   3 * self.dim * self.mlp_dim + 2 * self.dim)
        return (self.n_layers * p_layer + self.dim +
                self.vocab_size * self.dim * (
                    1 if self.tie_embeddings else 2))


def param_specs(cfg: GemmaConfig, *, quantized: bool = False) -> Params:
    """Logical-axis names, mirroring init()'s tree (the default tied
    head has no lm_head leaf; ``tie_embeddings=False`` adds one).
    ``quantized`` mirrors the quantize_params tree — see
    llama.param_specs."""
    specs = {
        "embed": ("vocab", "embed"),
        "layers": {
            "attn_norm": ("layers", "embed"),
            "wq": ("layers", "embed", "q_heads_x_dim"),
            "wk": ("layers", "embed", "kv_heads_x_dim"),
            "wv": ("layers", "embed", "kv_heads_x_dim"),
            "wo": ("layers", "q_heads_x_dim", "embed"),
            "mlp_norm": ("layers", "embed"),
            "w_gate": ("layers", "embed", "mlp"),
            "w_up": ("layers", "embed", "mlp"),
            "w_down": ("layers", "mlp", "embed"),
        },
        "final_norm": ("embed",),
    }
    if not cfg.tie_embeddings:
        specs["lm_head"] = ("embed", "vocab")
    if quantized:
        specs["embed_scale"] = ("vocab",)
        for name in llama.QUANT_LAYER_WEIGHTS:
            out_axis = specs["layers"][name][-1]
            specs["layers"][name + "_scale"] = ("layers", out_axis)
        if "lm_head" in specs:
            specs["lm_head_scale"] = ("vocab",)
    return specs


def init(cfg: GemmaConfig, key: jax.Array) -> Params:
    """Stacked-layer params. Norm weights are ZEROS (the (1 + w) scale
    starts at identity — gemma's checkpoint convention); with the
    default ``tie_embeddings=True`` the LM head is embed^T
    (llama.head_weights handles the absent lm_head), with it False an
    untied lm_head is created — config, num_params and flops_per_token
    all honor the flag."""
    k = jax.random.split(key, 9)
    d, hd = cfg.dim, cfg.head_dim
    L = cfg.n_layers
    dt = cfg.dtype

    def dense(key, shape, fan_in):
        return (jax.random.normal(key, shape, dtype=jnp.float32) *
                (fan_in ** -0.5)).astype(dt)

    params: Params = {
        "embed": dense(k[0], (cfg.vocab_size, d), d),
        "layers": {
            "attn_norm": jnp.zeros((L, d), dtype=dt),
            "wq": dense(k[1], (L, d, cfg.n_heads * hd), d),
            "wk": dense(k[2], (L, d, cfg.n_kv_heads * hd), d),
            "wv": dense(k[3], (L, d, cfg.n_kv_heads * hd), d),
            "wo": dense(k[4], (L, cfg.n_heads * hd, d),
                        cfg.n_heads * hd),
            "mlp_norm": jnp.zeros((L, d), dtype=dt),
            "w_gate": dense(k[5], (L, d, cfg.mlp_dim), d),
            "w_up": dense(k[6], (L, d, cfg.mlp_dim), d),
            "w_down": dense(k[7], (L, cfg.mlp_dim, d), cfg.mlp_dim),
        },
        "final_norm": jnp.zeros((d,), dtype=dt),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense(k[8], (d, cfg.vocab_size), d)
    return params


# The forward/decode machinery is llama's, driven by this config's
# knobs — one shared implementation of attention, cache masking, remat,
# and the serving loop across the dense families.

def forward(cfg: GemmaConfig, params: Params, tokens: jax.Array,
            positions: Optional[jax.Array] = None,
            constrain=lambda x, spec: x) -> jax.Array:
    """Token ids (B, S) -> fp32 logits (B, S, vocab)."""
    return llama.forward(cfg, params, tokens, positions, constrain)


def forward_trunk(cfg: GemmaConfig, params: Params, tokens: jax.Array,
                  positions: Optional[jax.Array] = None,
                  constrain=lambda x, spec: x) -> jax.Array:
    return llama.forward_trunk(cfg, params, tokens, positions, constrain)


def head_weights(params: Params) -> jax.Array:
    return llama.head_weights(params)


def init_cache(cfg: GemmaConfig, batch: int, max_seq: int):
    return llama.init_cache(cfg, batch, max_seq)


cache_specs = llama.cache_specs

# int8 weight serving: gemma's param tree uses llama's layer keys, so
# the quantization transform (and its tied-head embed_scale handling)
# is llama's shared machinery.
quantize_params = llama.quantize_params
params_quantized = llama.params_quantized

# Paged KV block pool (decode-engine paged mode): layout and block-
# table attention are llama's shared machinery.
init_paged_cache = llama.init_paged_cache
forward_with_paged_cache = llama.forward_with_paged_cache

# Speculative decoding (decode-engine verify path): the multi-token
# verify window is llama's shared machinery driven by this config's
# knobs (norm offset, GeGLU, scaled embeddings, MQA cache layout).
verify_step_paged = llama.verify_step_paged


def forward_with_cache(cfg: GemmaConfig, params: Params,
                       tokens: jax.Array, cache, start_pos,
                       valid_len=None, logits_at=None):
    return llama.forward_with_cache(cfg, params, tokens, cache,
                                    start_pos, valid_len=valid_len,
                                    logits_at=logits_at)


def decode(cfg: GemmaConfig, params: Params, prompt: jax.Array,
           true_len, max_tokens: int, max_seq: int,
           temperature: float = 0.0, key=None, *,
           cache=None, return_cache: bool = False) -> jax.Array:
    """Prefill + KV-cached decode through the shared serving loop
    (scalar or ragged (B,) true_len; optional donated cache)."""
    return llama.decode(cfg, params, prompt, true_len, max_tokens,
                        max_seq, temperature, key, cache=cache,
                        return_cache=return_cache)
