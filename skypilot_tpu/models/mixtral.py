"""Mixtral-class sparse MoE decoder with expert parallelism.

Recipe-parity target: the reference serves Mixtral by handing vLLM a set of
GPUs (reference: llm/mixtral/serve.yaml — vLLM does the expert math). Here
the MoE layer is native and TPU-first: top-2 routing is computed as one-hot
capacity dispatch/combine einsums (all MXU matmuls, no gather/scatter), the
expert axis is a logical axis (`expert` -> `ep` mesh axis via the rule
table), and XLA inserts the all-to-alls when the mesh shards it.

Shares the attention stack with llama.py; only the MLP differs.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from skypilot_tpu.models import llama

Params = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class MixtralConfig:
    vocab_size: int = 32000
    dim: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 8
    mlp_dim: int = 14336
    n_experts: int = 8
    top_k: int = 2
    capacity_factor: float = 1.25
    router_aux_weight: float = 0.02
    rope_theta: float = 1e6
    norm_eps: float = 1e-5
    max_seq_len: int = 8192
    dtype: Any = jnp.bfloat16
    attention_impl: str = "auto"
    remat: bool = True

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads

    @staticmethod
    def mixtral_8x7b() -> "MixtralConfig":
        return MixtralConfig()

    @staticmethod
    def tiny(vocab_size: int = 256) -> "MixtralConfig":
        return MixtralConfig(vocab_size=vocab_size, dim=64, n_layers=2,
                             n_heads=4, n_kv_heads=2, mlp_dim=128,
                             n_experts=4, top_k=2, max_seq_len=256)

    def flops_per_token(self) -> float:
        attn = self.dim * (self.n_heads + 2 * self.n_kv_heads) * \
            self.head_dim + self.n_heads * self.head_dim * self.dim
        moe = self.top_k * 3 * self.dim * self.mlp_dim
        router = self.dim * self.n_experts
        p_active = self.n_layers * (attn + moe + router) + \
            2 * self.vocab_size * self.dim
        return 6.0 * p_active


def param_specs(cfg: MixtralConfig, *, quantized: bool = False) -> Params:
    specs = {
        "embed": ("vocab", "embed"),
        "layers": {
            "attn_norm": ("layers", "embed"),
            "wq": ("layers", "embed", "q_heads_x_dim"),
            "wk": ("layers", "embed", "kv_heads_x_dim"),
            "wv": ("layers", "embed", "kv_heads_x_dim"),
            "wo": ("layers", "q_heads_x_dim", "embed"),
            "mlp_norm": ("layers", "embed"),
            "router": ("layers", "embed", None),
            "w_gate": ("layers", "expert", "embed", "mlp"),
            "w_up": ("layers", "expert", "embed", "mlp"),
            "w_down": ("layers", "expert", "mlp", "embed"),
        },
        "final_norm": ("embed",),
        "lm_head": ("embed", "vocab"),
    }
    if quantized:
        # int8 serving tree (quantize_params): per-output-channel
        # scales keep the weight's trailing axes minus the reduced
        # in-features axis — expert weights keep their expert axis so
        # EP sharding places each expert's scales beside its codes.
        # The f32 router is NOT quantized (routing decisions are
        # discrete; a code flip would change which experts fire).
        specs["embed_scale"] = ("vocab",)
        for name in llama.QUANT_LAYER_WEIGHTS:
            spec = specs["layers"][name]
            specs["layers"][name + "_scale"] = (
                spec[:-2] + spec[-1:])
        specs["lm_head_scale"] = ("vocab",)
    return specs


def init(cfg: MixtralConfig, key: jax.Array) -> Params:
    k = jax.random.split(key, 10)
    d, hd, L, E = cfg.dim, cfg.head_dim, cfg.n_layers, cfg.n_experts
    dt = cfg.dtype

    def dense(key, shape, fan_in):
        return (jax.random.normal(key, shape, dtype=jnp.float32) *
                (fan_in ** -0.5)).astype(dt)

    return {
        "embed": dense(k[0], (cfg.vocab_size, d), d),
        "layers": {
            "attn_norm": jnp.ones((L, d), dtype=dt),
            "wq": dense(k[1], (L, d, cfg.n_heads * hd), d),
            "wk": dense(k[2], (L, d, cfg.n_kv_heads * hd), d),
            "wv": dense(k[3], (L, d, cfg.n_kv_heads * hd), d),
            "wo": dense(k[4], (L, cfg.n_heads * hd, d), cfg.n_heads * hd),
            "mlp_norm": jnp.ones((L, d), dtype=dt),
            "router": dense(k[5], (L, d, E), d).astype(jnp.float32),
            "w_gate": dense(k[6], (L, E, d, cfg.mlp_dim), d),
            "w_up": dense(k[7], (L, E, d, cfg.mlp_dim), d),
            "w_down": dense(k[8], (L, E, cfg.mlp_dim, d), cfg.mlp_dim),
        },
        "final_norm": jnp.ones((d,), dtype=dt),
        "lm_head": dense(k[9], (d, cfg.vocab_size), d),
    }


def quantize_params(cfg: MixtralConfig, params: Params) -> Params:
    """int8 weight-serving transform, mirroring
    ``param_specs(cfg, quantized=True)``: llama's per-output-channel
    scheme over the shared attention weights plus the expert tensors
    (in-features axis is always axis -2, expert axes survive into the
    scale), with the f32 router left exact — routing is a discrete
    argmax and must not move under quantization noise."""
    out = dict(params)
    out["embed"], out["embed_scale"] = llama._quantize_weight(
        params["embed"], -1)
    layers = dict(params["layers"])
    for name in llama.QUANT_LAYER_WEIGHTS:
        layers[name], layers[name + "_scale"] = llama._quantize_weight(
            layers[name], -2)
    out["layers"] = layers
    out["lm_head"], out["lm_head_scale"] = llama._quantize_weight(
        params["lm_head"], -2)
    return out


params_quantized = llama.params_quantized


def _top2_dispatch(gates: jax.Array, capacity: int
                   ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """GShard-style top-2 capacity routing, all one-hot matmul friendly.

    gates: (T, E) softmax probabilities.
    Returns (dispatch (T, E, C) bool, combine (T, E, C) f32, aux_loss ()).
    """
    t, e = gates.shape
    idx1 = jnp.argmax(gates, axis=-1)
    mask1 = jax.nn.one_hot(idx1, e, dtype=gates.dtype)
    gates_no1 = gates * (1.0 - mask1)
    idx2 = jnp.argmax(gates_no1, axis=-1)
    mask2 = jax.nn.one_hot(idx2, e, dtype=gates.dtype)

    # Load-balancing aux loss (Switch-style): fraction of tokens routed to
    # each expert * mean router prob per expert.
    density = jnp.mean(mask1, axis=0)
    density_proxy = jnp.mean(gates, axis=0)
    aux = jnp.sum(density * density_proxy) * (e ** 2) / 1.0

    # Positions within each expert's buffer; tokens past capacity dropped.
    pos1 = jnp.cumsum(mask1, axis=0) * mask1 - mask1  # (T, E)
    keep1 = (pos1 < capacity) * mask1
    pos2 = (jnp.cumsum(mask2, axis=0) + jnp.sum(mask1, axis=0,
                                                keepdims=True)) * mask2 - \
        mask2
    keep2 = (pos2 < capacity) * mask2

    g1 = jnp.sum(gates * keep1, axis=-1)
    g2 = jnp.sum(gates * keep2, axis=-1)
    denom = jnp.maximum(g1 + g2, 1e-9)
    g1, g2 = g1 / denom, g2 / denom

    cap_iota = jnp.arange(capacity, dtype=pos1.dtype)
    # (T, E, C) one-hots of each token's slot in each expert buffer.
    slot1 = keep1[:, :, None] * (pos1[:, :, None] == cap_iota)
    slot2 = keep2[:, :, None] * (pos2[:, :, None] == cap_iota)
    combine = g1[:, None, None] * slot1 + g2[:, None, None] * slot2
    dispatch = (slot1 + slot2) > 0
    return dispatch, combine.astype(jnp.float32), aux


def _moe_mlp(cfg: MixtralConfig, y: jax.Array, lp: Params, constrain
             ) -> Tuple[jax.Array, jax.Array]:
    """y: (B, S, D) -> (B, S, D), aux loss."""
    b, s, d = y.shape
    t = b * s
    e = cfg.n_experts
    capacity = max(int(cfg.capacity_factor * cfg.top_k * t / e), cfg.top_k)
    yt = y.reshape(t, d)
    logits = yt.astype(jnp.float32) @ lp["router"]
    gates = jax.nn.softmax(logits, axis=-1)
    dispatch, combine, aux = _top2_dispatch(gates, capacity)
    # Dispatch: (T,E,C) x (T,D) -> (E,C,D); sharded expert axis makes XLA
    # insert the all-to-all here.
    xs = jnp.einsum("tec,td->ecd", dispatch.astype(y.dtype), yt)
    xs = constrain(xs, ("expert", None, "act_embed"))
    gate = jax.nn.silu(jnp.einsum("ecd,edm->ecm", xs, lp["w_gate"]))
    up = jnp.einsum("ecd,edm->ecm", xs, lp["w_up"])
    out = jnp.einsum("ecm,emd->ecd", gate * up, lp["w_down"])
    out = constrain(out, ("expert", None, "act_embed"))
    yo = jnp.einsum("tec,ecd->td", combine.astype(y.dtype), out)
    return yo.reshape(b, s, d), aux


def _layer(cfg: MixtralConfig, x: jax.Array, lp: Params,
           positions: jax.Array, constrain) -> Tuple[jax.Array, jax.Array]:
    x = llama.attention_block(cfg, x, lp, positions, constrain)
    y = llama.rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
    moe_out, aux = _moe_mlp(cfg, y, lp, constrain)
    x = x + constrain(moe_out, ("batch", "act_seq", "act_embed"))
    return x, aux


def forward(cfg: MixtralConfig, params: Params, tokens: jax.Array,
            positions: Optional[jax.Array] = None,
            constrain=lambda x, spec: x,
            with_aux: bool = True):
    """Token ids (B, S) -> (logits (B, S, vocab), router aux loss).

    ``with_aux=True`` by default so the load-balancing loss can only be
    dropped deliberately — training without it collapses the router.
    """
    b, s = tokens.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    x = llama.embed_tokens(params, tokens, constrain)

    def layer_fn(carry, lp):
        x, aux_sum = carry
        x, aux = _layer(cfg, x, lp, positions, constrain)
        return (x, aux_sum + aux), None

    if cfg.remat:
        layer_fn = jax.checkpoint(layer_fn, prevent_cse=False)
    (x, aux_total), _ = jax.lax.scan(
        layer_fn, (x, jnp.zeros((), jnp.float32)), params["layers"])

    logits = llama.lm_head(cfg, params, x, constrain)
    if with_aux:
        return logits, cfg.router_aux_weight * aux_total / cfg.n_layers
    return logits


# ----------------------------------------------------------- KV-cache decode
def _moe_mlp_dense(cfg: MixtralConfig, y: jax.Array,
                   lp: Params) -> jax.Array:
    """Inference-time MoE: every expert computed, top-2 combined.

    Capacity routing (training) makes a token's output depend on which
    OTHER tokens compete for expert slots — so incremental decode could
    never reproduce a full pass. Per-token dense routing is
    composition-independent (incremental == full by construction) and
    cheap at decode chunk sizes; it equals the capacity path exactly
    whenever capacity is not exceeded.
    """
    e = cfg.n_experts
    logits = y.astype(jnp.float32) @ lp["router"]        # (B,T,E)
    gates = jax.nn.softmax(logits, axis=-1)
    # Select via top_k INDICES (exactly two experts, matching training's
    # two argmax picks) — a value threshold would activate 3+ experts on
    # tied gates and diverge from the capacity path.
    _, idx = jax.lax.top_k(gates, 2)                     # (B,T,2)
    sel = jax.nn.one_hot(idx, e, dtype=gates.dtype).sum(axis=-2)
    w = gates * sel
    w = w / jnp.maximum(jnp.sum(w, axis=-1, keepdims=True), 1e-9)

    def expert_mm(eq, x, name):
        # Expert matmul, dequantizing per-(expert, channel) scales
        # when the weight is int8 (quantize_params tree): the scale's
        # trailing (E, out) axes broadcast against the einsum's
        # (..., E, out) result.
        wt = lp[name]
        scale = lp.get(name + "_scale")
        if scale is None:
            return jnp.einsum(eq, x, wt)
        r = jnp.einsum(eq, x, wt.astype(x.dtype))
        return (r.astype(jnp.float32) * scale).astype(x.dtype)

    gate = jax.nn.silu(expert_mm("btd,edm->btem", y, "w_gate"))
    up = expert_mm("btd,edm->btem", y, "w_up")
    out = expert_mm("btem,emd->bted", gate * up, "w_down")
    return jnp.einsum("bte,bted->btd", w.astype(out.dtype), out)


def init_cache(cfg: MixtralConfig, batch: int, max_seq: int):
    """Layer-stacked KV cache — same layout as llama's (the attention
    blocks are shared); experts add no per-token state."""
    return llama.init_cache(cfg, batch, max_seq)


cache_specs = llama.cache_specs

# Paged KV block pool: llama's layout (and, above, its specs), experts
# add no per-token cache state.
init_paged_cache = llama.init_paged_cache


def _moe_block(cfg: MixtralConfig, x: jax.Array, lp: Params) -> jax.Array:
    """Pre-norm dense-routed MoE residual block (inference)."""
    y = llama.rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
    return x + _moe_mlp_dense(cfg, y, lp)


def forward_with_cache(cfg: MixtralConfig, params: Params,
                       tokens: jax.Array, cache, start_pos: jax.Array,
                       valid_len: Optional[jax.Array] = None,
                       logits_at: Optional[jax.Array] = None):
    """Incremental MoE forward: llama's cache loop (attention/mask
    contract lives there, in one place) with the dense-routed top-2
    expert MLP swapped in — the serving loop the reference delegates to
    vLLM for Mixtral (llm/mixtral/serve.yaml). Same scalar-or-(B,)
    start_pos/valid_len/logits_at contract as
    llama.forward_with_cache."""
    return llama.forward_with_cache(
        cfg, params, tokens, cache, start_pos, valid_len=valid_len,
        logits_at=logits_at, mlp_fn=_moe_block)


def forward_with_paged_cache(cfg: MixtralConfig, params: Params,
                             tokens: jax.Array, cache, table,
                             start_pos, valid_len=None,
                             logits_at=None, *, window: int,
                             write_block=None):
    """Paged incremental MoE forward: llama's block-table cache loop
    with the dense-routed top-2 expert MLP swapped in — same pattern
    as forward_with_cache."""
    return llama.forward_with_paged_cache(
        cfg, params, tokens, cache, table, start_pos,
        valid_len=valid_len, logits_at=logits_at, window=window,
        write_block=write_block, mlp_fn=_moe_block)


def verify_step_paged(cfg: MixtralConfig, params: Params,
                      tokens: jax.Array, cache, table, start_pos,
                      spec_len, *, window: int):
    """Paged speculative verify window with the MoE MLP swapped in."""
    return llama.verify_step_paged(cfg, params, tokens, cache, table,
                                   start_pos, spec_len, window=window,
                                   mlp_fn=_moe_block)


def decode(cfg: MixtralConfig, params: Params, prompt: jax.Array,
           true_len: jax.Array, max_tokens: int, max_seq: int,
           temperature: float = 0.0,
           key: Optional[jax.Array] = None, *,
           cache=None, return_cache: bool = False) -> jax.Array:
    """Prefill + cached decode for Mixtral (llama.decode's loop with the
    MoE cache functions plugged in; scalar or ragged (B,) true_len)."""
    return llama.decode(cfg, params, prompt, true_len, max_tokens,
                        max_seq, temperature=temperature, key=key,
                        fwd_cache=forward_with_cache,
                        cache_init=init_cache, cache=cache,
                        return_cache=return_cache)
