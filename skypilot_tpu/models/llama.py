"""Llama-3-class decoder transformer, pure-JAX functional style.

Flagship dense model of the recipe tree (reference analog:
llm/llama-3_1-finetuning -- the reference shells out to torchtune; here the
model is native). Design is TPU-first:

  * params are plain pytrees of arrays with a parallel pytree of *logical
    axis* tuples -> shardings come from `parallel.mesh.ShardingRules`;
  * layers are stacked on a leading axis and executed with `lax.scan`
    (one compiled layer body, fast XLA compiles, natural remat point);
  * attention dispatches to the Pallas flash kernel on TPU;
  * all matmuls run in bfloat16 on the MXU, softmax/norm stats in fp32.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

Params = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 128256
    dim: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 8
    mlp_dim: int = 14336
    rope_theta: float = 500000.0
    norm_eps: float = 1e-5
    max_seq_len: int = 8192
    dtype: Any = jnp.bfloat16
    tie_embeddings: bool = False
    attention_impl: str = "auto"  # auto|pallas|reference|ring
    remat: bool = True
    # "full": classic layer remat (everything recomputed in bwd).
    # "save_flash": layer remat, but the flash kernel's outputs
    # (named flash_out/flash_lse in its vjp fwd) are pinned — the bwd
    # recomputes projections/norms/MLP yet never re-runs the quadratic
    # attention kernel. Costs ~(2*S*D + 4*S*H) bytes per layer; at long
    # context the kernel re-run it saves dominates.
    # "save_flash_qkv": save_flash plus the roped q/k/v — also skips
    # the qkv-projection recompute for another ~2*S*D*2 bytes/layer.
    # "save_flash_offload_qkv": save_flash's HBM budget with
    # save_flash_qkv's recompute savings — q/k/v park in pinned host
    # RAM and stream back for the bwd. Long-context default: measured
    # to match save_flash_qkv at 8k and beat save_flash by +1.5 MFU pts
    # at 16k+ where pinned qkv OOMs (docs/performance.md).
    remat_policy: str = "full"
    # full|save_flash|save_flash_qkv|save_flash_offload_qkv

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads

    @staticmethod
    def llama3_8b() -> "LlamaConfig":
        return LlamaConfig()

    @staticmethod
    def tiny(vocab_size: int = 512) -> "LlamaConfig":
        return LlamaConfig(vocab_size=vocab_size, dim=128, n_layers=4,
                           n_heads=8, n_kv_heads=4, mlp_dim=256,
                           max_seq_len=512)

    def flops_per_token(self, seq_len: Optional[int] = None) -> float:
        """Approximate fwd+bwd FLOPs per token for MFU accounting.

        Without ``seq_len``: the conservative 6N parameter-matmul count
        (PaLM's "model FLOPs" convention; understates real work). With
        ``seq_len``: adds the causal attention score/value matmuls
        (~6 * L * S * d per token), the attention-inclusive figure.
        """
        p_layer = (self.dim * (self.n_heads + 2 * self.n_kv_heads) *
                   self.head_dim + self.n_heads * self.head_dim * self.dim +
                   3 * self.dim * self.mlp_dim)
        p = self.n_layers * p_layer + self.vocab_size * self.dim * (
            1 if self.tie_embeddings else 2)
        flops = 6.0 * p
        if seq_len is not None:
            # QK^T + PV: 4*S*d fwd per layer, halved by causal masking,
            # tripled for fwd+bwd.
            flops += 6.0 * self.n_layers * seq_len * self.dim
        return flops

    def num_params(self) -> int:
        p_layer = (self.dim * (self.n_heads + 2 * self.n_kv_heads) *
                   self.head_dim + self.n_heads * self.head_dim * self.dim +
                   3 * self.dim * self.mlp_dim + 2 * self.dim)
        return (self.n_layers * p_layer + self.dim +
                self.vocab_size * self.dim * (1 if self.tie_embeddings else 2))


def param_specs(cfg: LlamaConfig, *, quantized: bool = False) -> Params:
    """Logical-axis names for every param, mirroring init()'s tree.

    ``quantized`` mirrors :func:`quantize_params`' tree instead: every
    int8 weight keeps its bf16 spec (codes shard exactly like the
    values they encode) and gains a ``<name>_scale`` entry whose spec
    is the weight's OUTPUT axis — per-channel scales live on the same
    device as the channel's matmul shard, so TP serving never gathers
    them."""
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
        "lm_head": ("embed", "vocab"),
    }
    if cfg.tie_embeddings:
        specs.pop("lm_head")
    if quantized:
        specs["embed_scale"] = ("vocab",)
        for name in QUANT_LAYER_WEIGHTS:
            out_axis = specs["layers"][name][-1]
            specs["layers"][name + "_scale"] = ("layers", out_axis)
        if "lm_head" in specs:
            specs["lm_head_scale"] = ("vocab",)
    return specs


def init(cfg: LlamaConfig, key: jax.Array) -> Params:
    """Initialize params (stacked-layer layout)."""
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
            "attn_norm": jnp.ones((L, d), dtype=dt),
            "wq": dense(k[1], (L, d, cfg.n_heads * hd), d),
            "wk": dense(k[2], (L, d, cfg.n_kv_heads * hd), d),
            "wv": dense(k[3], (L, d, cfg.n_kv_heads * hd), d),
            "wo": dense(k[4], (L, cfg.n_heads * hd, d), cfg.n_heads * hd),
            "mlp_norm": jnp.ones((L, d), dtype=dt),
            "w_gate": dense(k[5], (L, d, cfg.mlp_dim), d),
            "w_up": dense(k[6], (L, d, cfg.mlp_dim), d),
            "w_down": dense(k[7], (L, cfg.mlp_dim, d), cfg.mlp_dim),
        },
        "final_norm": jnp.ones((d,), dtype=dt),
        "lm_head": dense(k[8], (d, cfg.vocab_size), d),
    }
    if cfg.tie_embeddings:
        params.pop("lm_head")
    return params


# Layer weights the int8 serving path quantizes (norms and LoRA
# adapters stay in their checkpoint dtype; mixtral extends this with
# its expert tensors and keeps the f32 router exact).
QUANT_LAYER_WEIGHTS = ("wq", "wk", "wv", "wo", "w_gate", "w_up",
                      "w_down")


def _quantize_weight(w: jax.Array, reduce_axis: int):
    """Symmetric per-channel int8: absmax over the in-features axis
    (``reduce_axis``), one f32 scale per output channel. Codes span
    [-127, 127] so the representation is sign-symmetric."""
    wf = w.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(wf), axis=reduce_axis) / 127.0,
                        1e-8)
    q = jnp.round(wf / jnp.expand_dims(scale, reduce_axis))
    return jnp.clip(q, -127, 127).astype(jnp.int8), scale


def quantize_params(cfg: LlamaConfig, params: Params) -> Params:
    """int8 weight-serving transform: every matmul weight becomes int8
    codes plus a per-output-channel f32 ``<name>_scale`` (the embed
    table's scale is per vocab ROW, which is simultaneously the tied
    lm_head's per-output-channel scale). The tree shape mirrors
    ``param_specs(cfg, quantized=True)`` so TP sharding
    (gang_replica.shard_params) works unchanged; norms and LoRA
    adapters keep their dtype. The matmuls upcast codes in-register at
    use — the win is HBM: resident weight bytes halve, and decode is
    memory-bound."""
    out = dict(params)
    out["embed"], out["embed_scale"] = _quantize_weight(
        params["embed"], -1)
    layers = dict(params["layers"])
    for name in QUANT_LAYER_WEIGHTS:
        layers[name], layers[name + "_scale"] = _quantize_weight(
            layers[name], -2)
    out["layers"] = layers
    if "lm_head" in params:
        out["lm_head"], out["lm_head_scale"] = _quantize_weight(
            params["lm_head"], -2)
    return out


def params_quantized(params: Params) -> bool:
    """True when ``params`` is a :func:`quantize_params` tree."""
    return "embed_scale" in params


def rms_norm(x: jax.Array, w: jax.Array, eps: float,
             offset: float = 0.0) -> jax.Array:
    """``offset`` generalizes the scale to (offset + w): llama/mixtral
    use offset 0 (scale = w, init ones); gemma uses offset 1 (scale =
    1 + w, init zeros — its checkpoint convention). Configs advertise it
    via ``norm_offset``."""
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    normed32 = x32 * jax.lax.rsqrt(var + eps)
    if offset:
        # Scale applied in fp32: in bf16, eps(1.0)=2^-8, so gemma
        # checkpoint norm deltas under ~0.002 would vanish into the
        # (offset + w) addition (and into the product) if done in the
        # weight dtype.
        return (normed32 *
                (w.astype(jnp.float32) + offset)).astype(x.dtype)
    return normed32.astype(x.dtype) * w


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotary embedding. x: (B, S, H, D), positions: (B, S)."""
    d = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (B,S,D/2)
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                          axis=-1)
    return out.astype(x.dtype)


def lora_dense(y: jax.Array, lp: Params, name: str,
               out_dtype=None) -> jax.Array:
    """y @ W, plus the low-rank LoRA path y @ A @ B when the layer params
    carry `<name>_lora_a`/`<name>_lora_b` adapters (recipes/llama_lora.py
    injects them; base checkpoints don't have the keys and skip it).

    When the layer carries a `<name>_scale` (quantize_params tree) the
    weight is int8: codes upcast to the activation dtype in-register,
    the matmul runs as usual, and the per-output-channel f32 scale
    multiplies the result — one extra VPU pass, half the HBM reads.

    The result is in the activations' dtype, or in ``out_dtype``: f32
    hands over the MXU's accumulator unrounded (the operands stay what
    they are)."""
    w = lp[name]
    scale = lp.get(name + "_scale")
    out_dtype = out_dtype or y.dtype
    if scale is None:
        out = jnp.matmul(y, w, preferred_element_type=out_dtype)
    else:
        out = ((y @ w.astype(y.dtype)).astype(jnp.float32) *
               scale).astype(out_dtype)
    a = lp.get(name + "_lora_a")
    if a is not None:
        out = out + (y @ a) @ lp[name + "_lora_b"]
    return out


def _qkv(cfg, y: jax.Array, lp: Params, positions: jax.Array, dense):
    """q, k and v of one layer: ``dense(y, lp, name)`` for the three
    products, heads, RoPE on q and k. One body for every forward; the
    two entries below differ in ``dense`` alone."""
    b, t = y.shape[0], y.shape[1]
    h, kvh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = dense(y, lp, "wq").reshape(b, t, h, hd)
    kk = dense(y, lp, "wk").reshape(b, t, kvh, hd)
    vv = dense(y, lp, "wv").reshape(b, t, kvh, hd)
    return (rope(q, positions, cfg.rope_theta).astype(y.dtype),
            rope(kk, positions, cfg.rope_theta).astype(y.dtype),
            vv.astype(y.dtype))


def qkv_proj(cfg, y: jax.Array, lp: Params, positions: jax.Array):
    """Projection + RoPE of the TRAINING forward
    (:func:`attention_block`). Returns (q, k, v); v unroped. The
    compiler may fuse the products into the reshape to heads and the
    rope that follow: at 8 x 2048 rows what that does to a weight's
    layout is 0.03 % of a layer, and a finished product would be one
    more pass over q, k and v. The forwards against a KV cache go
    through :func:`cached_qkv_proj`."""
    return _qkv(cfg, y, lp, positions, lora_dense)


def _finished_dense(y: jax.Array, lp: Params, name: str) -> jax.Array:
    """:func:`lora_dense` FINISHED: a (B, T, out) array that exists
    before anything downstream may be fused into the product — in f32,
    the accumulator as it is. See :func:`cached_qkv_proj`."""
    return jax.lax.optimization_barrier(
        lora_dense(y, lp, name, out_dtype=jnp.float32))


def cached_qkv_proj(cfg, y: jax.Array, lp: Params,
                    positions: jax.Array):
    """Projection + RoPE of the forwards against a KV cache —
    :func:`cached_attention_block` (the row-cache reference) and
    :func:`paged_attention_block` (what serves) both reach q, k and v
    through here and nowhere else, so they stay the same arithmetic.

    Each product is FINISHED before it is reshaped to heads. Without
    the barrier the v5e compiler picks the layout of q that the reshape
    and ``rope``'s half-split want, f32[B,T,H,D]{3,0,2,1}, and re-lays
    the WEIGHT to suit: in every layer of every step it slices the
    layer's ``wq`` out of the stack into a buffer of its own
    (``constant_dynamic-slice_fusion bf16[1,4096,4096]{2,1,0}``),
    transposes that (``copy bf16[1,4096,4096]{1,2,0}``) and only then
    multiplies — two more passes over 33.5 MB for a product of half a
    megabyte, 81 us of a layer at Mistral-7B's widths, and the same for
    ``wk`` and ``wv``: 142 us a layer where 72 do (PERF.md, PR 32).
    Finished first, each weight is read once, in the layout it is
    stored in, by a product that has its ``dynamic-slice`` fused in, as
    ``wo`` and the MLP are read; what is re-laid is the product.
    tests/test_tpu_compile.py holds the compiled programs to that. The
    parameter tree cannot carry the layout instead: the benchmark's
    plain reference reads ``wq``/``wk``/``wv`` as (dim, heads x
    head_dim).

    Finished in f32, not in the activations' bf16: that fused program
    fed the f32 accumulator straight into ``rope`` (XLA drops a
    bf16 round trip between two fused operations), and q and k were
    rounded once, after the rotation. A product finished in bf16 rounds
    them twice, and Mixtral's served tokens then left the float32
    reference at 4 of 489 positions over the check's margin where the
    fused program left it at none of 544 (PERF.md, PR 32). The f32 product is
    a megabyte; ``rope`` computes in f32 either way."""
    return _qkv(cfg, y, lp, positions, _finished_dense)


def _mlp_activation(cfg):
    """Gated-MLP nonlinearity by config: SwiGLU (llama/mixtral, the
    default) or GeGLU with tanh-approx gelu (gemma)."""
    name = getattr(cfg, "mlp_activation", "silu")
    if name == "silu":
        return jax.nn.silu
    if name == "gelu_tanh":
        return lambda a: jax.nn.gelu(a, approximate=True)
    raise ValueError(f"unknown mlp_activation {name!r}")


def mlp_block(cfg, x: jax.Array, lp: Params,
              constrain=lambda a, _spec: a) -> jax.Array:
    """Pre-norm gated-MLP residual block (SwiGLU or GeGLU by config),
    shared by training and decode."""
    y = rms_norm(x, lp["mlp_norm"], cfg.norm_eps,
                 getattr(cfg, "norm_offset", 0.0))
    # Through lora_dense so the int8 weight-serving path (per-channel
    # `_scale` entries) covers the MLP projections too; without scales
    # or adapters it is exactly `y @ w`.
    gate = _mlp_activation(cfg)(lora_dense(y, lp, "w_gate"))
    up = lora_dense(y, lp, "w_up")
    mlp = constrain(gate * up, ("batch", "act_seq", "mlp"))
    return x + constrain(lora_dense(mlp, lp, "w_down"),
                         ("batch", "act_seq", "act_embed"))


def attention_block(cfg, x: jax.Array, lp: Params, positions: jax.Array,
                    constrain) -> jax.Array:
    """Pre-norm GQA attention residual block, shared by llama and mixtral.

    `cfg` needs: n_heads, n_kv_heads, head_dim, norm_eps, rope_theta,
    attention_impl.
    """
    b, s, d = x.shape
    h, kvh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    y = rms_norm(x, lp["attn_norm"], cfg.norm_eps,
                 getattr(cfg, "norm_offset", 0.0))
    q, kk, vv = qkv_proj(cfg, y, lp, positions)
    q = constrain(q, ("batch", "act_seq", "heads", None))
    kk = constrain(kk, ("batch", "act_seq", "kv_heads", None))
    if cfg.attention_impl == "ring":
        from skypilot_tpu.parallel import ring_attention
        attn = ring_attention.ring_attention_from_context(q, kk, vv)
    else:
        from skypilot_tpu.parallel import mesh_attention
        attn = mesh_attention.attention_from_context(
            q, kk, vv, causal=True, impl=cfg.attention_impl)
    attn = attn.reshape(b, s, h * hd)
    return x + constrain(lora_dense(attn, lp, "wo"),
                         ("batch", "act_seq", "act_embed"))


def embed_tokens(params: Params, tokens: jax.Array, constrain) -> jax.Array:
    """Token embedding lookup, SPMD-aware.

    Under a multi-device mesh the lookup is a one-hot matmul rather than
    a gather: a gather whose operand is sharded on the embed dim (the
    fsdp layout of the table) produces output sharded on that dim, and
    the SPMD partitioner cannot move that sharding to the batch dim
    without an "involuntary full rematerialization" (replicate + re-
    partition — the warning the multichip dryrun used to log). A dot is
    freely repartitionable: XLA all-gathers the table's fsdp shards
    (exactly FSDP's prefetch-before-use) and psums over a sharded vocab.
    Single-device paths (serving decode, CPU tests) keep the O(1) gather.
    """
    from skypilot_tpu.parallel import mesh as mesh_lib
    table = params["embed"]
    ctx = mesh_lib.current_mesh_rules()
    if ctx is not None and ctx[0].size > 1:
        one_hot = jax.nn.one_hot(tokens, table.shape[0],
                                 dtype=table.dtype)
        one_hot = constrain(one_hot, ("batch", "act_seq", "vocab"))
        x = one_hot @ table
    else:
        x = table[tokens]
    return constrain(x, ("batch", "act_seq", "act_embed"))


def _decode_embed(cfg, params: Params, tokens: jax.Array) -> jax.Array:
    """Token-embedding gather for the serving decode paths: O(1)
    single-device gather (decode never runs the one-hot SPMD matmul —
    the table is either replicated or vocab-sharded with a cheap (B, T)
    collective), dequantizing per-row embed scales when the table is
    int8 and applying gemma's sqrt(dim) embed multiplier."""
    x = params["embed"][tokens]
    row_scale = params.get("embed_scale")
    mult = getattr(cfg, "embed_multiplier", 1.0)
    if row_scale is not None:
        x = (x.astype(jnp.float32) *
             (row_scale[tokens][..., None] * mult)).astype(cfg.dtype)
    elif mult != 1.0:  # gemma: embeddings scaled by sqrt(dim)
        x = (x.astype(jnp.float32) * mult).astype(x.dtype)
    return x


def _vocab_proj(params: Params, x: jax.Array, constrain) -> jax.Array:
    """(B,S,D) hidden -> fp32 logits. bf16 INPUTS into the MXU with f32
    accumulation (preferred_element_type) — casting the operands to f32
    first runs the vocab matmul at the fp32 rate, ~4x below bf16 peak,
    and at vocab 32k this projection alone is ~1 TFLOP per 8k-token
    step."""
    logits = jax.lax.dot_general(
        x, head_weights(params).astype(x.dtype), (((2,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    # int8 serving: the head's per-vocab-channel scale (embed_scale for
    # a tied head — the embed table's per-ROW scale transposes into the
    # head's per-column scale) folds into the f32 logits.
    scale = (params.get("lm_head_scale") if "lm_head" in params
             else params.get("embed_scale"))
    if scale is not None:
        logits = logits * scale
    return constrain(logits, ("batch", "act_seq", "vocab"))


def lm_head(cfg, params: Params, x: jax.Array, constrain) -> jax.Array:
    """Final norm + (tied or untied) output projection, fp32 logits."""
    x = rms_norm(x, params["final_norm"], cfg.norm_eps,
                 getattr(cfg, "norm_offset", 0.0))
    return _vocab_proj(params, x, constrain)


def _remat_policy(cfg):
    """jax.checkpoint policy for the layer body (see
    LlamaConfig.remat_policy)."""
    name = getattr(cfg, "remat_policy", "full")
    if name == "save_flash":
        return jax.checkpoint_policies.save_only_these_names(
            "flash_out", "flash_lse")
    if name == "save_flash_qkv":
        return jax.checkpoint_policies.save_only_these_names(
            "flash_out", "flash_lse", "flash_q", "flash_k", "flash_v")
    if name == "save_flash_offload_qkv":
        # save_flash's HBM budget, save_flash_qkv's recompute savings:
        # kernel outputs stay on-device, the roped q/k/v park in pinned
        # host RAM and stream back for the bwd. Whether the PCIe/ICI
        # round-trip beats the qkv-projection recompute is measured in
        # docs/performance.md (long-context offload experiment).
        return jax.checkpoint_policies.save_and_offload_only_these_names(
            names_which_can_be_saved=["flash_out", "flash_lse"],
            names_which_can_be_offloaded=["flash_q", "flash_k",
                                          "flash_v"],
            offload_src="device", offload_dst="pinned_host")
    if name != "full":
        # A typo silently degrading to full remat would re-run the
        # quadratic kernel every bwd — the exact cost the knob avoids.
        raise ValueError(
            f"Unknown remat_policy {name!r}; expected 'full', "
            "'save_flash', 'save_flash_qkv' or "
            "'save_flash_offload_qkv'.")
    return None


def _layer(cfg: LlamaConfig, x: jax.Array, layer_params: Params,
           positions: jax.Array, constrain) -> jax.Array:
    lp = layer_params
    x = attention_block(cfg, x, lp, positions, constrain)
    return mlp_block(cfg, x, lp, constrain)


def forward(cfg: LlamaConfig, params: Params, tokens: jax.Array,
            positions: Optional[jax.Array] = None,
            constrain=lambda x, spec: x) -> jax.Array:
    """Token ids (B, S) -> logits (B, S, vocab).

    `constrain` is an optional callback (x, logical_axes) -> x used by the
    trainer to inject with_sharding_constraint under a concrete mesh; the
    default is identity so the model runs un-meshed (single device).
    """
    x = forward_trunk(cfg, params, tokens, positions, constrain)
    return _vocab_proj(params, x, constrain)


def forward_trunk(cfg: LlamaConfig, params: Params, tokens: jax.Array,
                  positions: Optional[jax.Array] = None,
                  constrain=lambda x, spec: x) -> jax.Array:
    """Token ids (B, S) -> FINAL-NORMED hidden states (B, S, dim) — the
    trunk without the vocab projection. The chunked-CE training loss
    (train/trainer.py chunked_cross_entropy_loss) projects chunk-by-
    chunk so the (B, S, vocab) fp32 logits tensor never materializes in
    HBM (it is ~1GB at seq 8192 x vocab 32k, and the round-trips through
    it dominate the loss region's step time)."""
    b, s = tokens.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    x = embed_tokens(params, tokens, constrain)
    scale = getattr(cfg, "embed_multiplier", 1.0)
    if scale != 1.0:  # gemma: embeddings scaled by sqrt(dim)
        x = (x.astype(jnp.float32) * scale).astype(x.dtype)
    layer_fn = lambda carry, lp: (_layer(cfg, carry, lp, positions,
                                         constrain), None)
    if cfg.remat:
        layer_fn = jax.checkpoint(layer_fn, prevent_cse=False,
                                  policy=_remat_policy(cfg))
    x, _ = jax.lax.scan(layer_fn, x, params["layers"])
    return rms_norm(x, params["final_norm"], cfg.norm_eps,
                    getattr(cfg, "norm_offset", 0.0))


def head_weights(params: Params) -> jax.Array:
    """(dim, vocab) output projection — untied head or embed^T."""
    head = params.get("lm_head")
    if head is None:
        head = params["embed"].T
    return head


# ----------------------------------------------------------- KV-cache decode

# KV rows read per split-KV block. 256 keeps tiny test caches (< 256
# rows) on a single block — bit-identical to the dense softmax — while
# bounding VMEM working set at serving cache sizes.
SPLIT_KV_BLOCK = 256


def init_cache(cfg: LlamaConfig, batch: int,
               max_seq: int) -> Dict[str, jax.Array]:
    """Per-layer KV cache, stacked on the layer axis like the params
    (so the decode step scans layers and caches together)."""
    shape = (cfg.n_layers, batch, max_seq, cfg.n_kv_heads, cfg.head_dim)
    return {"k": jnp.zeros(shape, dtype=cfg.dtype),
            "v": jnp.zeros(shape, dtype=cfg.dtype)}


def cache_specs(cfg: LlamaConfig) -> Dict[str, tuple]:
    """Logical-axis names for the KV cache, mirroring init_cache()'s
    (layers, batch, max_seq, kv_heads, head_dim) layout — the serving
    analog of param_specs. Under a TP mesh the kv_heads axis shards
    over ICI neighbors (each chip holds its heads' rows); batch and
    seq stay replicated because every decode step touches all slots.
    Callers that build concrete shardings must re-point the rule at
    the trailing head_dim axis when tp does not divide n_kv_heads
    (gemma's single KV head) — serve/gang_replica.cache_shardings is
    the one place that check lives."""
    spec = ("layers", None, None, "kv_heads", None)
    return {"k": spec, "v": spec}


def init_paged_cache(cfg: LlamaConfig, num_blocks: int,
                     block_tokens: int, *,
                     quantized: bool = False) -> Dict[str, jax.Array]:
    """ONE device-resident paged KV pool shared by every engine slot
    AND the shared-prefix cache: ``num_blocks`` blocks of
    ``block_tokens`` token rows each, stacked on the layer axis like
    the dense cache. The paged forward scans the layer parameters and
    the layer index and CARRIES this stack whole: a layer writes rows
    at ``[li, block, offset]`` and gathers blocks at ``[li, block]``,
    so the pool is never sliced per layer and stays one buffer under
    the callers' donation (held by the temp-size test in
    tests/test_paged_kv.py). Slots map logical positions to blocks
    through per-slot block tables (serve/kv_pool.py owns the
    accounting); block 0 is the scratch block free slots write into.

    ``quantized`` stores the pool as int8 codes plus parallel
    per-(layer, block, kv_head) f32 scale arrays — sized off the same
    block count, so the block table indexes codes and scales alike.
    Bytes per block roughly halve against bf16 (codes are half, the
    scale adds 4 bytes per kv_head per block against block_tokens *
    head_dim rows), which is where the ~2x pool capacity at a fixed
    HBM budget comes from."""
    shape = (cfg.n_layers, num_blocks, block_tokens, cfg.n_kv_heads,
             cfg.head_dim)
    if not quantized:
        return {"k": jnp.zeros(shape, dtype=cfg.dtype),
                "v": jnp.zeros(shape, dtype=cfg.dtype)}
    sshape = (cfg.n_layers, num_blocks, cfg.n_kv_heads)
    return {"k": jnp.zeros(shape, dtype=jnp.int8),
            "v": jnp.zeros(shape, dtype=jnp.int8),
            "k_scale": jnp.zeros(sshape, dtype=jnp.float32),
            "v_scale": jnp.zeros(sshape, dtype=jnp.float32)}


def _attn_tile(qf: jax.Array, scale: float, kb: jax.Array,
               vb: jax.Array, msk: jax.Array, m: jax.Array,
               el: jax.Array, acc: jax.Array):
    """One online-softmax tile (running max / normalizer / accumulator
    update) shared by the dense and paged split-KV loops — one
    implementation so the two paths are the same arithmetic term for
    term, which is what makes paged decode bit-identical to dense when
    their tile boundaries align.

    qf: (B, T, KVH, G, D) f32 queries; kb/vb: (B, W, KVH, D) f32 tile;
    msk: (B, T, W) bool. Returns (m, el, acc) updated.
    """
    s_blk = jnp.einsum("btkgd,bskd->bkgts", qf, kb) * scale
    s_blk = jnp.where(msk[:, None, None], s_blk, -1e30)
    m_new = jnp.maximum(m, jnp.max(s_blk, axis=-1))
    corr = jnp.exp(m - m_new)
    # Masked entries multiplied to exactly 0 (not just exp(-big)):
    # a fully-masked slot (free engine slot) must stay finite.
    p = jnp.exp(s_blk - m_new[..., None]) * msk[:, None, None]
    el = el * corr + jnp.sum(p, axis=-1)
    pv = jnp.einsum("bkgts,bskd->btkgd", p, vb)
    corr_t = corr.transpose(0, 3, 1, 2)[..., None]
    return m_new, el, acc * corr_t + pv


def _attn_carry(b: int, t: int, kvh: int, g: int, d: int):
    return (jnp.int32(0),
            jnp.full((b, kvh, g, t), -1e30, jnp.float32),
            jnp.zeros((b, kvh, g, t), jnp.float32),
            jnp.zeros((b, t, kvh, g, d), jnp.float32))


def _attn_normalize(el: jax.Array, acc: jax.Array) -> jax.Array:
    el_t = el.transpose(0, 3, 1, 2)[..., None]
    return jnp.where(el_t > 0, acc / jnp.maximum(el_t, 1e-30), 0.0)


def _split_kv_attention(qg: jax.Array, ck: jax.Array, cv: jax.Array,
                        positions: jax.Array, valid_len: jax.Array,
                        block: Optional[int] = None) -> jax.Array:
    """Flash-decode-style attention against the ragged KV cache.

    Instead of one dense (T, max_seq) score einsum that reads every
    cache row, the cache is consumed in key blocks with an online
    softmax (running max / normalizer / accumulator), and the block loop
    is a ``lax.while_loop`` bounded by the LONGEST valid prefix in the
    batch — cache rows past every slot's frontier are never read, so a
    batch of short sequences in a long-max_seq cache pays for its actual
    tokens, not the allocation.

    qg: (B, T, KVH, G, D) grouped queries; ck/cv: (B, max_seq, KVH, D).
    positions: (B, T) absolute query positions. valid_len: (B,) — rows
    >= valid_len[b] are masked even though they hold (stale) data; this
    is the padding-KV-never-attendable invariant slot reuse relies on.
    Returns f32 (B, T, KVH, G, D).
    """
    b, t, kvh, g, d = qg.shape
    max_seq = ck.shape[1]
    block = min(block or SPLIT_KV_BLOCK, max_seq)
    qf = qg.astype(jnp.float32)
    scale = d ** -0.5
    # Rows a query of slot b can ever attend stop at
    # min(its position + 1, valid_len[b]); the loop bound is the batch
    # max so every slot's frontier is covered.
    limit = jnp.max(jnp.minimum(positions[:, -1] + 1, valid_len))
    limit = jnp.minimum(limit, max_seq)

    def body(carry):
        s0, m, el, acc = carry
        # When block does not divide max_seq, the final window clamps
        # back to max_seq - block; rows before the nominal start s0
        # (already consumed by earlier blocks) are masked out below, so
        # the overlap never double-counts.
        start = jnp.minimum(s0, max_seq - block)
        kb = jax.lax.dynamic_slice_in_dim(ck, start, block,
                                          axis=1).astype(jnp.float32)
        vb = jax.lax.dynamic_slice_in_dim(cv, start, block,
                                          axis=1).astype(jnp.float32)
        kpos = start + jnp.arange(block)
        msk = ((kpos[None, None, :] >= s0) &
               (kpos[None, None, :] <= positions[..., None]) &
               (kpos[None, None, :] < valid_len[:, None, None]))
        m_new, el, acc = _attn_tile(qf, scale, kb, vb, msk, m, el, acc)
        return s0 + block, m_new, el, acc

    _, _, el, acc = jax.lax.while_loop(
        lambda c: c[0] < limit, body, _attn_carry(b, t, kvh, g, d))
    return _attn_normalize(el, acc)


def _paged_split_kv_attention(qg: jax.Array, li: jax.Array,
                              pk: jax.Array, pv: jax.Array,
                              table: jax.Array,
                              positions: jax.Array,
                              valid_len: jax.Array,
                              window: int,
                              k_scale: Optional[jax.Array] = None,
                              v_scale: Optional[jax.Array] = None
                              ) -> jax.Array:
    """Split-KV attention reading K/V THROUGH a per-slot block table.

    The paged twin of :func:`_split_kv_attention`: instead of each slot
    owning a contiguous (max_seq, ...) cache row, K/V live in one
    shared pool of fixed-size blocks and ``table[b, j]`` names the
    physical block holding slot ``b``'s logical chunk ``j``. Each
    ``lax.while_loop`` iteration gathers ``window // block_tokens``
    blocks per slot (a batched dynamic-slice of the table + one gather
    into the pool at ``[li, phys]``: the layer is an index of the
    gather, never a slice taken first), reassembles the same
    (B, W, KVH, D) tile the dense
    loop slices out, and runs the IDENTICAL online-softmax tile
    (:func:`_attn_tile`) — so when ``window`` matches the dense path's
    block and tile boundaries align (window | max_seq, true for every
    shipped config), paged output is bit-identical to dense.

    pk/pv: (layers, num_blocks, block_tokens, KVH, D) — the WHOLE
    stacked pool; ``li`` (scalar int32) is the layer read.
    table: (B, table_len) int32; entries past a slot's frontier may be
    stale/zero (the scratch block) — their rows are masked to exact 0
    like any invalid dense row, so garbage never contributes.

    ``k_scale``/``v_scale`` ((layers, num_blocks, KVH) f32) arm the
    int8 pool: the SAME ``[li, phys]`` gather that pulls a tile's
    code blocks pulls their per-(block, head) scales, and the dequant
    multiply folds into the tile's existing f32 upcast — so
    :func:`_attn_tile` below stays the ONE online-softmax kernel
    shared with the dense loop, fed f32 tiles either way.
    """
    b, t, kvh, g, d = qg.shape
    bt = pk.shape[2]
    nb_win = window // bt
    if nb_win * bt != window:
        raise ValueError(f"window {window} must be a multiple of the "
                         f"block size {bt}")
    qf = qg.astype(jnp.float32)
    scale = d ** -0.5
    limit = jnp.max(jnp.minimum(positions[:, -1] + 1, valid_len))
    limit = jnp.minimum(limit, table.shape[1] * bt)

    def body(carry):
        s0, m, el, acc = carry
        phys = jax.lax.dynamic_slice(
            table, (jnp.int32(0), s0 // bt), (b, nb_win))  # (B, nbw)
        kb = pk[li, phys].astype(jnp.float32)   # (B, nbw, bt, KVH, D)
        vb = pv[li, phys].astype(jnp.float32)
        if k_scale is not None:
            kb = kb * k_scale[li, phys][:, :, None, :, None]
            vb = vb * v_scale[li, phys][:, :, None, :, None]
        kb = kb.reshape(b, window, kvh, d)
        vb = vb.reshape(b, window, kvh, d)
        kpos = s0 + jnp.arange(window)
        msk = ((kpos[None, None, :] >= s0) &
               (kpos[None, None, :] <= positions[..., None]) &
               (kpos[None, None, :] < valid_len[:, None, None]))
        m_new, el, acc = _attn_tile(qf, scale, kb, vb, msk, m, el, acc)
        return s0 + window, m_new, el, acc

    _, _, el, acc = jax.lax.while_loop(
        lambda c: c[0] < limit, body, _attn_carry(b, t, kvh, g, d))
    return _attn_normalize(el, acc)


def cached_attention_block(cfg, x: jax.Array, lp: Params,
                           ck: jax.Array, cv: jax.Array,
                           positions: jax.Array, start_pos: jax.Array,
                           valid_len: jax.Array):
    """One pre-norm GQA attention residual block against the KV cache
    (shared by llama's and mixtral's decode paths). ``start_pos`` and
    ``valid_len`` are per-slot (B,) vectors — every slot in the batch
    may sit at a different sequence position.
    Returns (x + attn_out, updated ck, updated cv)."""
    b, t = x.shape[0], x.shape[1]
    h, kvh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    y = rms_norm(x, lp["attn_norm"], cfg.norm_eps,
                 getattr(cfg, "norm_offset", 0.0))
    q, k_new, v_new = cached_qkv_proj(cfg, y, lp, positions)
    upd = lambda c, u, s: jax.lax.dynamic_update_slice(c, u, (s, 0, 0))
    ck = jax.vmap(upd)(ck, k_new.astype(ck.dtype), start_pos)
    cv = jax.vmap(upd)(cv, v_new.astype(cv.dtype), start_pos)
    # GQA grouped attention against the UNEXPANDED cache (the head-
    # order convention of ops/attention.py): q regrouped per KV head
    # so no repeat()ed copy of the cache hits HBM on the hot path.
    groups = h // kvh
    qg = q.reshape(b, t, kvh, groups, hd)
    attn = _split_kv_attention(qg, ck, cv, positions, valid_len)
    attn = attn.astype(x.dtype).reshape(b, t, h * hd)
    return x + lora_dense(attn, lp, "wo"), ck, cv


def slot_positions(b: int, t: int, start_pos, valid_len):
    """The per-slot (B,) ``start_pos`` and ``valid_len`` (default
    start_pos + T) of an incremental forward, each given as a scalar or
    a vector, and the (B, T) absolute positions of its tokens."""
    start_pos = jnp.asarray(start_pos, jnp.int32)
    if start_pos.ndim == 0:
        start_pos = jnp.broadcast_to(start_pos, (b,))
    if valid_len is None:
        valid_len = start_pos + t
    valid_len = jnp.asarray(valid_len, jnp.int32)
    if valid_len.ndim == 0:
        valid_len = jnp.broadcast_to(valid_len, (b,))
    positions = start_pos[:, None] + jnp.arange(t)[None, :]  # (B, T)
    return start_pos, valid_len, positions


def read_out(x: jax.Array, logits_at) -> jax.Array:
    """The rows of (B, T, D) the head is computed at: all of them, or
    the one chunk-relative ``logits_at`` names (a scalar, or one per
    slot for ragged prompts). Serving prefill reads exactly one
    position and skips the O(T x vocab) head on the padded chunk."""
    if logits_at is None:
        return x
    logits_at = jnp.asarray(logits_at, jnp.int32)
    if logits_at.ndim == 0:
        return jax.lax.dynamic_slice_in_dim(x, logits_at, 1, axis=1)
    return x[jnp.arange(x.shape[0]), logits_at][:, None]


def forward_with_cache(cfg, params: Params,
                       tokens: jax.Array, cache: Dict[str, jax.Array],
                       start_pos: jax.Array,
                       valid_len: Optional[jax.Array] = None,
                       logits_at: Optional[jax.Array] = None, *,
                       mlp_fn=None
                       ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Incremental forward: process a chunk, reading/writing the cache.

    tokens (B, T) are positions [start_pos, start_pos+T); returns
    (logits (B, T, vocab), updated cache). T == prompt length for
    prefill, T == 1 for each decode step; per-token cost is
    O(longest valid prefix), not O(seq^2) — the property a serving
    endpoint needs (vLLM/JetStream analog; the reference delegates this
    entirely to vLLM).

    ``start_pos``, ``valid_len`` and ``logits_at`` each accept a scalar
    (whole batch at one position — the bucketed fixed-batch path) OR a
    per-slot (B,) vector: under continuous batching every slot sits at
    its own sequence position, so the cache write offset, the
    attendable prefix, and the read-out index are all per-example.

    ``valid_len`` (default start_pos + T): cache positions >= valid_len
    are masked out of attention. Right-padded prefill chunks pass their
    true length so padding K/V never becomes attendable (padding slots
    are overwritten by later decode steps before valid_len reaches
    them). ``logits_at`` (chunk-relative index) computes the lm_head at
    just that position, returning (B, 1, vocab).
    """
    b, t = tokens.shape
    start_pos, valid_len, positions = slot_positions(b, t, start_pos,
                                                     valid_len)
    x = _decode_embed(cfg, params, tokens)

    # Pluggable residual MLP half — mixtral swaps in its dense-routed
    # MoE (models/mixtral.py) while the attention/cache/mask contract
    # (padding K/V never attendable) stays in exactly one place.
    mlp_fn = mlp_fn or (lambda cfg, x2, lp: mlp_block(cfg, x2, lp))

    def layer_fn(x, scanned):
        lp, ck, cv = scanned                               # per-layer
        x2, ck, cv = cached_attention_block(cfg, x, lp, ck, cv,
                                            positions, start_pos,
                                            valid_len)
        return mlp_fn(cfg, x2, lp), (ck, cv)

    x, (new_k, new_v) = jax.lax.scan(
        layer_fn, x, (params["layers"], cache["k"], cache["v"]))
    logits = lm_head(cfg, params, read_out(x, logits_at),
                     lambda a, _spec: a)
    return logits, {"k": new_k, "v": new_v}


def _quant_scatter_row(pk: jax.Array, ks: jax.Array, li: jax.Array,
                       blk: jax.Array, off: jax.Array,
                       row: jax.Array):
    """Scatter one new K/V row per slot into the int8 pool, keeping
    the one-scale-per-(block, head) invariant.

    Works in CODE space: the row's absmax can only grow the block's
    scale (never shrink it), and when it doesn't — the common decode
    step — the rescale ratio is exactly 1.0, so existing codes round
    back to themselves and repeated steps never random-walk. When the
    row does grow the scale, the block's prior codes rescale once by
    old/new. ``off == 0`` (first row of a freshly granted block)
    resets the inherited scale: pool blocks recycle without zeroing,
    and a dead block's stale scale must not inflate the new
    sequence's quantization step. Free slots ride along targeting the
    scratch block (possibly many per batch — last write wins, scratch
    contents are never attendable).

    pk: (L, NB, BT, KVH, D) int8; ks: (L, NB, KVH) f32 — the stacked
    pool, touched at layer ``li`` only; blk/off: (B,) int32;
    row: (B, KVH, D). Returns (pk, ks) updated.
    """
    b = blk.shape[0]
    cur = pk[li, blk].astype(jnp.float32)           # (B, BT, KVH, D)
    old_s = jnp.where((off == 0)[:, None], 0.0,
                      ks[li, blk])                           # (B, KVH)
    row_s = jnp.max(jnp.abs(row.astype(jnp.float32)),
                    axis=-1) / 127.0
    new_s = jnp.maximum(jnp.maximum(old_s, row_s), 1e-8)
    ratio = (old_s / new_s)[:, None, :, None]
    scaled = jnp.round(cur * ratio)
    q_row = jnp.round(row.astype(jnp.float32) / new_s[..., None])
    scaled = scaled.at[jnp.arange(b), off].set(q_row)
    q = jnp.clip(scaled, -127, 127).astype(jnp.int8)
    return pk.at[li, blk].set(q), ks.at[li, blk].set(new_s)


def _quant_block_write(pk: jax.Array, ks: jax.Array, li: jax.Array,
                       write_block: jax.Array, rows: jax.Array,
                       valid_rows: jax.Array):
    """Whole-block int8 overwrite (single-slot chunk prefill): a fresh
    per-(block, head) scale from the chunk's VALID rows — a
    right-padded final chunk's junk rows are excluded so padding can
    never inflate the quantization step — then every row quantized
    under it (junk rows too; they are masked at read like any invalid
    row). pk/ks are the stacked pool, written at ``[li, write_block]``
    — the codes as a row scatter like every other pool write (see
    :func:`paged_attention_block` for what a whole-block
    dynamic-update-slice costs on the TPU);
    rows: (BT, KVH, D); valid_rows: (BT,) bool."""
    rf = rows.astype(jnp.float32)
    masked = jnp.where(valid_rows[:, None, None], jnp.abs(rf), 0.0)
    s = jnp.maximum(jnp.max(masked, axis=(0, 2)) / 127.0, 1e-8)
    q = jnp.clip(jnp.round(rf / s[None, :, None]),
                 -127, 127).astype(jnp.int8)
    return (pk.at[li, write_block, jnp.arange(rows.shape[0])].set(q),
            ks.at[li, write_block].set(s))


def paged_write_targets(table: jax.Array, bt: int, b: int, t: int,
                        start_pos: jax.Array,
                        write_block: Optional[jax.Array],
                        write_pos: Optional[jax.Array]):
    """(blk, off), each (B, T): the physical block and the row in it
    that each token of a paged forward writes, by the three callers'
    ways of naming them (see :func:`paged_attention_block`)."""
    if write_pos is not None:
        # Speculative verify: per-(slot, token) scatter THROUGH the
        # table. Junk columns (a slot's draft tail shorter than the
        # batch's static T) carry a sentinel >= the table span and
        # route to the scratch block — like free slots' rides, their
        # garbage is masked to exact 0 by valid_len, never attendable.
        ok = write_pos < table.shape[1] * bt
        blk_idx = jnp.clip(write_pos // bt, 0, table.shape[1] - 1)
        blk = jnp.where(ok, jnp.take_along_axis(table, blk_idx,
                                                axis=1), 0)
        return blk, jnp.where(ok, write_pos % bt, 0)
    if t == 1:
        blk = jnp.take_along_axis(table, (start_pos // bt)[:, None],
                                  axis=1)
        return blk, (start_pos % bt)[:, None]
    if b != 1 or t != bt or write_block is None:
        raise ValueError(
            "paged chunk prefill needs B == 1, T == block_tokens "
            "and a write_block (chunk-aligned whole-block write); "
            f"got B={b}, T={t}, block_tokens={bt}")
    # The chunk's rows land at offsets 0..bt-1 of write_block. A
    # whole-block .at[li, write_block].set() is a dynamic-update-slice,
    # and the TPU compiler then gives the WHOLE carried pool the layout
    # of its update operand (the projection's output) and converts it
    # back for the gather, layer by layer (PERF.md, PR 27); the row
    # scatter keeps the pool in the layout it arrived in.
    return (jnp.broadcast_to(write_block, (1, t)),
            jnp.arange(t)[None, :])


def paged_attention_block(cfg, x: jax.Array, lp: Params,
                          li: jax.Array,
                          pk: jax.Array, pv: jax.Array,
                          ks: Optional[jax.Array],
                          vs: Optional[jax.Array],
                          table: jax.Array, positions: jax.Array,
                          start_pos: jax.Array, valid_len: jax.Array,
                          window: int,
                          write_block: Optional[jax.Array],
                          write_pos: Optional[jax.Array] = None):
    """One pre-norm GQA attention residual block against the PAGED KV
    pool (the block-table twin of :func:`cached_attention_block`).

    ``pk``/``pv`` are the WHOLE stacked pool
    (layers, num_blocks, block_tokens, KVH, D) and ``li`` the layer
    this block is: every write and every read names ``[li, ...]``, so
    no layer's pool is ever sliced out of the stack or written back
    into it (the layer scan carries the pool; see
    :func:`forward_with_paged_cache`).

    Writes route through the table: T == 1 (batched decode step)
    scatters each slot's new K/V row into block ``table[b, pos//bt]``
    at offset ``pos % bt`` — free slots ride along with table row 0
    (the scratch block), so their ignored writes can never clobber a
    live slot's block. T == block_tokens (single-slot chunk prefill,
    B == 1, chunk-aligned) overwrites the whole physical block
    ``write_block``. Aliased (shared-prefix) blocks are never write
    targets: admission aligns the cached prefix to whole blocks and
    prefill/decode only ever write from the first non-cached block on.
    ``ks``/``vs`` ((layers, num_blocks, KVH) f32 scales, None for the
    bf16 pool) arm the int8 pool: every write path quantizes against
    the target block's one-scale-per-(block, head) entry (fresh scale
    on whole-block prefill, grow-only code-space rescale on row
    scatters) and the attention gather dequantizes with the same
    scales. Returns (x + attn_out, pk, pv, ks, vs)."""
    b, t = x.shape[0], x.shape[1]
    h, kvh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    bt = pk.shape[2]
    quant = ks is not None
    y = rms_norm(x, lp["attn_norm"], cfg.norm_eps,
                 getattr(cfg, "norm_offset", 0.0))
    q, k_new, v_new = cached_qkv_proj(cfg, y, lp, positions)
    # Every write to the bf16 pool is ONE row scatter
    # pk.at[li, blk, off] with (B, T) targets (the int8 pool re-scales
    # per block).
    blk, off = paged_write_targets(table, bt, b, t, start_pos,
                                   write_block, write_pos)
    if not quant:
        pk = pk.at[li, blk, off].set(k_new.astype(pk.dtype))
        pv = pv.at[li, blk, off].set(v_new.astype(pv.dtype))
    elif write_pos is not None or t == 1:
        # Columns in order: the verify window's positions are
        # consecutive per slot, so a block boundary (off == 0, scale
        # reset) is always crossed BEFORE that block's later offsets
        # are written.
        for j in range(t):
            pk, ks = _quant_scatter_row(pk, ks, li, blk[:, j],
                                        off[:, j], k_new[:, j])
            pv, vs = _quant_scatter_row(pv, vs, li, blk[:, j],
                                        off[:, j], v_new[:, j])
    else:
        valid_rows = positions[0] < valid_len[0]
        pk, ks = _quant_block_write(pk, ks, li, write_block,
                                    k_new[0], valid_rows)
        pv, vs = _quant_block_write(pv, vs, li, write_block,
                                    v_new[0], valid_rows)
    groups = h // kvh
    qg = q.reshape(b, t, kvh, groups, hd)
    attn = _paged_split_kv_attention(qg, li, pk, pv, table, positions,
                                     valid_len, window,
                                     k_scale=ks, v_scale=vs)
    attn = attn.astype(x.dtype).reshape(b, t, h * hd)
    return x + lora_dense(attn, lp, "wo"), pk, pv, ks, vs


def forward_with_paged_cache(cfg, params: Params, tokens: jax.Array,
                             cache: Dict[str, jax.Array],
                             table: jax.Array, start_pos: jax.Array,
                             valid_len: Optional[jax.Array] = None,
                             logits_at: Optional[jax.Array] = None, *,
                             window: int,
                             write_block: Optional[jax.Array] = None,
                             write_pos: Optional[jax.Array] = None,
                             mlp_fn=None
                             ) -> Tuple[jax.Array,
                                        Dict[str, jax.Array]]:
    """Incremental forward against the paged block pool — the same
    scalar-or-(B,) ``start_pos``/``valid_len``/``logits_at`` contract
    as :func:`forward_with_cache`, with the KV cache replaced by
    ``cache`` (init_paged_cache pool, DONATED by callers) plus
    ``table`` (B, table_len) int32 block tables. ``window`` (static)
    is the attention tile width; match it to the dense path's
    ``min(SPLIT_KV_BLOCK, max_seq)`` for bit-parity. ``write_block``
    is the single-slot prefill write target (see
    :func:`paged_attention_block`).

    The layer scan SCANS the layer parameters and the layer index and
    CARRIES the stacked pool (codes, and the int8 pool's scales beside
    them) next to the activations: each layer scatters its rows into
    ``[li, ...]`` of the carried buffer and gathers from it, so under
    the callers' donation the pool is one buffer from entry to exit.
    Handing the pool to the scan as a scanned input and collecting it
    as a stacked output instead makes XLA slice a layer's pool out,
    write it back into a second stack and copy that over the donated
    one — three reads and writes of the whole pool a program (PERF.md,
    PR 27). tests/test_paged_kv.py holds the compiled temporaries
    under a quarter of the pool."""
    b, t = tokens.shape
    start_pos, valid_len, positions = slot_positions(b, t, start_pos,
                                                     valid_len)
    x = _decode_embed(cfg, params, tokens)

    # Pluggable residual MLP half, exactly as in forward_with_cache
    # (mixtral swaps in its dense-routed MoE).
    mlp_fn = mlp_fn or (lambda cfg, x2, lp: mlp_block(cfg, x2, lp))

    def layer_fn(carry, scanned):
        x, pk, pv, ks, vs = carry
        lp, li = scanned
        x2, pk, pv, ks, vs = paged_attention_block(
            cfg, x, lp, li, pk, pv, ks, vs, table, positions,
            start_pos, valid_len, window, write_block,
            write_pos=write_pos)
        return (mlp_fn(cfg, x2, lp), pk, pv, ks, vs), None

    # The bf16 pool has no scales: None is an empty pytree and rides
    # the carry as nothing, so one layer_fn serves both pools.
    (x, *pool), _ = jax.lax.scan(
        layer_fn,
        (x, cache["k"], cache["v"],
         cache.get("k_scale"), cache.get("v_scale")),
        (params["layers"], jnp.arange(cache["k"].shape[0])))
    new_cache = {name: leaf for name, leaf in
                 zip(("k", "v", "k_scale", "v_scale"), pool)
                 if leaf is not None}
    logits = lm_head(cfg, params, read_out(x, logits_at),
                     lambda a, _spec: a)
    return logits, new_cache


def _verify_write_positions(t: int, start_pos: jax.Array,
                            spec_len: jax.Array,
                            span: int) -> jax.Array:
    """(B, T) cache-write positions for a speculative verify window:
    column j of slot b lands at start_pos[b] + j while j <= spec_len[b]
    (the slot's real token + its drafts) and at the out-of-range
    sentinel ``span`` past its draft tail, which the paged scatter
    routes to the scratch block: a short-draft slot's junk columns
    write nothing attendable."""
    offs = jnp.arange(t)[None, :]
    wpos = start_pos[:, None] + offs
    return jnp.where(offs <= spec_len[:, None], wpos, span)


def verify_step_paged(cfg, params: Params, tokens: jax.Array,
                      cache: Dict[str, jax.Array], table: jax.Array,
                      start_pos: jax.Array, spec_len: jax.Array, *,
                      window: int, mlp_fn=None
                      ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Multi-token speculative verification against the paged pool.

    ``tokens`` (B, T) is, per slot, its last emitted token followed by
    up to T-1 drafted tokens (``spec_len`` (B,) real drafts; the tail
    is padding). One forward computes logits at ALL T positions —
    column j is the target distribution for the token at absolute
    position ``start_pos + j + 1``, conditioned on the draft prefix
    whose K/V this same pass wrote — which is what lets the engine
    accept k drafted tokens for the price of one memory-bound pass.

    Writes scatter THROUGH each slot's block table
    (:func:`_verify_write_positions`: junk columns route to the scratch
    block), attention gathers by :func:`_paged_split_kv_attention`, and
    ``valid_len = start_pos + spec_len + 1`` masks each slot's junk
    columns out of every other query. The engine backs the window's
    blocks from the slot's admission reservation before the call and
    truncates the rejected suffix's blocks back afterwards.

    Returns (logits (B, T, vocab), pool)."""
    b, t = tokens.shape
    start_pos = jnp.asarray(start_pos, jnp.int32)
    if start_pos.ndim == 0:
        start_pos = jnp.broadcast_to(start_pos, (b,))
    spec_len = jnp.asarray(spec_len, jnp.int32)
    if spec_len.ndim == 0:
        spec_len = jnp.broadcast_to(spec_len, (b,))
    span = table.shape[1] * cache["k"].shape[2]
    wpos = _verify_write_positions(t, start_pos, spec_len, span)
    return forward_with_paged_cache(
        cfg, params, tokens, cache, table, start_pos,
        valid_len=start_pos + spec_len + 1, window=window,
        write_pos=wpos, mlp_fn=mlp_fn)


def decode(cfg: LlamaConfig, params: Params, prompt: jax.Array,
           true_len: jax.Array, max_tokens: int, max_seq: int,
           temperature: float = 0.0,
           key: Optional[jax.Array] = None, *,
           fwd_cache=None, cache_init=None,
           cache=None, return_cache: bool = False) -> jax.Array:
    """Prefill + cached decode: prompt (B, S_pad) -> (B, max_tokens).

    ``true_len`` is the un-padded prompt length — a scalar shared by
    the whole batch, or a per-example (B,) vector: a RAGGED batch
    (heterogeneous prompt lengths right-padded to one bucket) decodes
    in a single batched call, each row masked to its own valid prefix
    and read out at its own last prompt token. One O(S) prefill pass,
    then max_tokens steps each bounded by the longest live prefix
    (split-KV attention). temperature == 0 is greedy; > 0 samples from
    softmax(logits/T) (key required).

    ``cache``: optional preallocated KV cache (init_cache layout).
    Callers that jit this function should allocate the cache outside,
    DONATE it (``donate_argnums``), and pass ``return_cache=True`` so
    the final cache is part of the jit output — XLA only aliases a
    donated input to an output, so without returning it the donation
    is inert and each call still materializes a second full-size cache
    in HBM. With it, the O(layers * batch * max_seq) buffer updates in
    place (the caller simply drops the returned cache).
    """
    true_len = jnp.asarray(true_len, jnp.int32)
    b, s_pad = prompt.shape
    if true_len.ndim == 0:
        true_len = jnp.broadcast_to(true_len, (b,))
    elif true_len.shape != (b,):
        raise ValueError(
            f"true_len must be a scalar or a (batch,) vector of "
            f"un-padded prompt lengths; got shape {true_len.shape} "
            f"for batch {b}.")
    if s_pad + max_tokens > max_seq:
        raise ValueError(
            f"prompt ({s_pad}) + max_tokens ({max_tokens}) exceeds the "
            f"cache (max_seq={max_seq}); dynamic_update_slice would "
            f"silently clamp and corrupt the tail.")
    if temperature > 0.0 and key is None:
        raise ValueError("temperature > 0 needs a PRNG key")
    if key is None:
        key = jax.random.key(0)  # unused on the greedy path

    def pick(logits_row, k):
        if temperature > 0.0:
            return jax.random.categorical(
                k, logits_row / temperature, axis=-1).astype(jnp.int32)
        return jnp.argmax(logits_row, axis=-1).astype(jnp.int32)

    # Pluggable cache fns: mixtral reuses this loop with its MoE layers
    # (models/mixtral.py decode).
    fwd_cache = fwd_cache or forward_with_cache
    cache_init = cache_init or init_cache
    if cache is None:
        cache = cache_init(cfg, b, max_seq)
    logits, cache = fwd_cache(
        cfg, params, prompt, cache, jnp.int32(0), valid_len=true_len,
        logits_at=true_len - 1)
    key, sub = jax.random.split(key)
    first = pick(logits[:, 0], sub)

    def step(carry, i):
        tok, cache, key = carry
        logits, cache = fwd_cache(
            cfg, params, tok[:, None], cache, true_len + i)
        key, sub = jax.random.split(key)
        nxt = pick(logits[:, -1], sub)
        return (nxt, cache, key), tok

    (_, cache, _), toks = jax.lax.scan(
        step, (first, cache, key),
        jnp.arange(max_tokens, dtype=jnp.int32))
    if return_cache:
        return toks.T, cache
    return toks.T                                          # (B, max_tokens)


def greedy_decode(cfg: LlamaConfig, params: Params, prompt: jax.Array,
                  true_len: jax.Array, max_tokens: int,
                  max_seq: int) -> jax.Array:
    return decode(cfg, params, prompt, true_len, max_tokens, max_seq)


def forward_pipelined(cfg: LlamaConfig, params: Params, tokens: jax.Array,
                      *, mesh, rules, num_microbatches: int,
                      positions: Optional[jax.Array] = None) -> jax.Array:
    """GPipe-pipelined forward: layer stack split into mesh.shape['pp']
    stages, batch split into microbatches. Use with PIPELINE_RULES so the
    stored layer stack is sharded over pp and the stage reshape is local.
    """
    from skypilot_tpu.parallel import mesh as mesh_lib
    from skypilot_tpu.parallel import pipeline as pipeline_lib

    if cfg.attention_impl == "ring":
        raise NotImplementedError(
            "attention_impl='ring' is not supported under pipeline "
            "parallelism: ring attention's shard_map over 'sp' cannot nest "
            "inside the pipeline's shard_map over 'pp'. Use ring attention "
            "with a dp/sp/tp mesh, or pipeline with impl='auto'.")
    n_stages = mesh.shape.get(mesh_lib.PP, 1)
    if cfg.n_layers % max(n_stages, 1):
        raise ValueError(f"n_layers={cfg.n_layers} not divisible by "
                         f"pp={n_stages}")
    b, s = tokens.shape
    m = num_microbatches
    if b % m:
        raise ValueError(f"batch={b} not divisible by microbatches={m}")
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s), (b, s))

    def constrain(x, spec):
        return mesh_lib.constrain(x, mesh, rules, spec)

    x = embed_tokens(params, tokens, constrain)
    d = x.shape[-1]

    # (L, ...) -> (P, L/P, ...): local view change under PIPELINE_RULES.
    def to_stages(a):
        return a.reshape(n_stages, cfg.n_layers // n_stages, *a.shape[1:])
    stage_params = jax.tree.map(to_stages, params["layers"])
    stage_params = jax.tree.map(
        lambda a: jax.lax.with_sharding_constraint(
            a, rules.sharding(("stage", "layers") + (None,) * (a.ndim - 2),
                              mesh)),
        stage_params)

    x_mb = x.reshape(m, b // m, s, d)
    pos_mb = positions.reshape(m, b // m, s)

    def stage_fn(lp, x_in, pos_in):
        def layer_fn(carry, layer_p):
            return _layer(cfg, carry, layer_p, pos_in,
                          lambda a, _spec: a), None
        if cfg.remat:
            layer_fn = jax.checkpoint(layer_fn, prevent_cse=False)
        out, _ = jax.lax.scan(layer_fn, x_in, lp)
        return out

    # Ambient for the ops that need the mesh inside a stage (the
    # attention kernel's shard_map), whoever calls this.
    with mesh_lib.use_mesh(mesh, rules):
        x = pipeline_lib.gpipe(stage_fn, stage_params, x_mb, pos_mb,
                               mesh=mesh, num_microbatches=m)
    x = x.reshape(b, s, d)
    return lm_head(cfg, params, x, constrain)
