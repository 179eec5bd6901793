"""DeepSeek-V3 decoder, serving path: multi-head latent attention (MLA)
over a LATENT paged pool, dense-then-sparse layers, group-limited
sigmoid routing over all routed experts with a held share of them
computed here, and a shared expert.

What differs from llama.py / mixtral.py, and where it lives:

  * the cache holds ONE row of ``kv_lora_rank + qk_rope_head_dim`` values
    a token a layer (the normed latent ``c_kv`` and the roped shared key
    ``k_r``), shared by all heads: ``init_paged_cache`` builds one pool
    of two leaves, ``c_kv`` (layers, blocks, block_tokens, 512) and
    ``k_r`` (the 64-wide key's 128 bytes a token);
  * attention has two forms pinned to one reference: a prefill chunk
    EXPANDS the latent rows of its context to per-head keys and values
    (``w_uk``/``w_uv``, the two halves of ``kv_b_proj``) and attends at
    head size 192/128; a decode step ABSORBS ``w_uk`` into the query and
    ``w_uv`` into the output and attends against the latent rows, so no
    per-head key or value is built for a cached token;
  * two stacks of layers (dense MLP first, then mixture of experts)
    under two scans that both CARRY the pool (llama.py's rule: every
    write a row scatter at ``[li, block, offset]``, every read a gather
    at ``[li, block]``);
  * the expert layer is told which experts it holds (``ep_rank`` of
    ``ep_size``), routes over ALL ``n_routed_experts`` as published and
    returns the held experts' part of the result plus the shared
    expert's. Nothing stands in for the absent ranks or their exchange.

Not supported, and refused by name (:func:`refuse`): the int8 pool and
int8 weights, ``tp > 1`` (a latent row has no head axis to shard) and
LoRA adapters. The multi-token-prediction module
(``num_nextn_predict_layers``) takes no part in the next-token forward
pass and is left out. The plain reference the tests hold this module to
is benchmarks/reference/deepseek_arch.py: there is no row-cache
``decode`` here.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from skypilot_tpu.models import llama

Params = Dict[str, Any]

_YARN_V3 = (("beta_fast", 32), ("beta_slow", 1), ("factor", 40),
            ("mscale", 1.0), ("mscale_all_dim", 1.0),
            ("original_max_position_embeddings", 4096),
            ("type", "yarn"))


def refuse(what: str, why: str):
    raise NotImplementedError(
        f"deepseek (DeepSeek-V3): {what} is not supported: {why}")


@dataclasses.dataclass(frozen=True)
class DeepseekV3Config:
    vocab_size: int = 129280
    dim: int = 7168
    n_layers: int = 61
    n_dense_layers: int = 3              # first_k_dense_replace
    n_heads: int = 128
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    mlp_dim: int = 18432                 # the dense layers' SwiGLU
    moe_mlp_dim: int = 2048              # one expert's SwiGLU
    n_routed_experts: int = 256          # the router's width
    n_shared_experts: int = 1
    top_k: int = 8
    n_group: int = 8
    topk_group: int = 4
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    # The share of a stated deployment: this rank holds experts
    # [ep_rank * n_experts_held, (ep_rank + 1) * n_experts_held).
    n_experts_held: int = 256
    ep_size: int = 1
    ep_rank: int = 0
    rope_theta: float = 10000.0
    # The published ``rope_scaling`` group (a dict is accepted and kept
    # as sorted items, so the config stays a static jit argument); None
    # or () is plain RoPE.
    rope_scaling: Any = _YARN_V3
    norm_eps: float = 1e-6
    max_seq_len: int = 163840
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        if isinstance(self.rope_scaling, dict):
            object.__setattr__(self, "rope_scaling",
                               tuple(sorted(self.rope_scaling.items())))
        if self.n_experts_held * self.ep_size != self.n_routed_experts \
                or not 0 <= self.ep_rank < self.ep_size:
            raise ValueError(
                f"deepseek: {self.ep_size} ranks of "
                f"{self.n_experts_held} held experts (rank "
                f"{self.ep_rank}) do not make the router's "
                f"{self.n_routed_experts}")
        if self.n_routed_experts % self.n_group \
                or not 0 <= self.n_dense_layers <= self.n_layers:
            raise ValueError("deepseek: n_group must divide the routed "
                             "experts and the dense layers lie within "
                             "the depth")

    @property
    def yarn(self) -> Dict[str, Any]:
        return dict(self.rope_scaling or ())

    @staticmethod
    def v3_5l_ep16() -> "DeepseekV3Config":
        """One of 16 chips that share each layer of a decode
        deployment, cut to one dense and four sparse layers
        (benchmarks/configs/deepseek-v3-5l-ep16.json)."""
        return DeepseekV3Config(vocab_size=16160, n_layers=5,
                                n_dense_layers=1, n_experts_held=16,
                                ep_size=16)

    @staticmethod
    def tiny(vocab_size: int = 256) -> "DeepseekV3Config":
        return DeepseekV3Config(
            vocab_size=vocab_size, dim=64, n_layers=3, n_dense_layers=1,
            n_heads=4, q_lora_rank=48, kv_lora_rank=32,
            qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
            mlp_dim=128, moe_mlp_dim=32, n_routed_experts=16, top_k=4,
            n_group=4, topk_group=2, n_experts_held=4, ep_size=4,
            max_seq_len=2048)


# ------------------------------------------------------------------ YaRN
def _yarn_mscale(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_inv_freq(cfg: DeepseekV3Config) -> np.ndarray:
    """The rotary frequencies: ``theta^(-2i/d)`` blended with their
    1/factor between the dimensions that make ``beta_fast`` and
    ``beta_slow`` rotations over the original context, as the published
    ``rope_scaling`` says. A host constant of the trace."""
    d = cfg.qk_rope_head_dim
    plain = cfg.rope_theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    ys = cfg.yarn
    if not ys:
        return plain.astype(np.float32)
    orig = ys["original_max_position_embeddings"]

    def dim_of(rotations):
        return (d * math.log(orig / (rotations * 2 * math.pi))
                / (2 * math.log(cfg.rope_theta)))

    low = max(math.floor(dim_of(ys["beta_fast"])), 0)
    high = min(math.ceil(dim_of(ys["beta_slow"])), d - 1)
    ramp = np.clip((np.arange(d // 2) - low)
                   / ((high - low) or 0.001), 0.0, 1.0)
    return (plain / ys["factor"] * ramp
            + plain * (1.0 - ramp)).astype(np.float32)


def softmax_scale(cfg: DeepseekV3Config) -> float:
    """(nope + rope)^-1/2, times YaRN's m^2 with
    m = 0.1 * mscale_all_dim * ln(factor) + 1."""
    scale = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
    ys = cfg.yarn
    if ys and ys.get("mscale_all_dim"):
        scale *= _yarn_mscale(ys["factor"], ys["mscale_all_dim"]) ** 2
    return scale


def rope_yarn(cfg: DeepseekV3Config, x: jax.Array,
              positions: jax.Array) -> jax.Array:
    """x: (B, T, H, R), positions (B, T). The checkpoint pairs the
    values (2i, 2i + 1); they are brought to the half-split order
    [evens, odds] and rotated there, queries and the shared key alike,
    so their products are those of the paired form."""
    ys = cfg.yarn
    m = (_yarn_mscale(ys["factor"], ys["mscale"])
         / _yarn_mscale(ys["factor"], ys["mscale_all_dim"])) if ys else 1.0
    ang = positions[..., None].astype(jnp.float32) * yarn_inv_freq(cfg)
    cos = (jnp.cos(ang) * m)[:, :, None, :]
    sin = (jnp.sin(ang) * m)[:, :, None, :]
    xf = x.astype(jnp.float32)
    a, b = xf[..., 0::2], xf[..., 1::2]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos],
                           axis=-1).astype(x.dtype)


# ------------------------------------------------------------ parameters
_ATTN_SPECS = {
    "attn_norm": ("layers", "embed"),
    "wq_a": ("layers", "embed", None),
    "q_norm": ("layers", None),
    "wq_nope": ("layers", "heads", None, None),
    "wq_rope": ("layers", None, "heads", None),
    "wkv_a": ("layers", "embed", None),
    "kv_norm": ("layers", None),
    "w_uk": ("layers", "heads", None, None),
    "w_uv": ("layers", "heads", None, None),
    "wo": ("layers", "q_heads_x_dim", "embed"),
    "mlp_norm": ("layers", "embed"),
}


def param_specs(cfg: DeepseekV3Config, *, quantized: bool = False
                ) -> Params:
    if quantized:
        refuse("int8 weights", "quantize_params has no MLA tree")
    return {
        "embed": ("vocab", "embed"),
        "dense_layers": {
            **_ATTN_SPECS,
            "w_gate": ("layers", "embed", "mlp"),
            "w_up": ("layers", "embed", "mlp"),
            "w_down": ("layers", "mlp", "embed"),
        },
        "moe_layers": {
            **_ATTN_SPECS,
            "router": ("layers", "embed", None),
            "router_bias": ("layers", None),
            "we_gate": ("layers", "expert", "embed", "mlp"),
            "we_up": ("layers", "expert", "embed", "mlp"),
            "we_down": ("layers", "expert", "mlp", "embed"),
            "ws_gate": ("layers", "embed", "mlp"),
            "ws_up": ("layers", "embed", "mlp"),
            "ws_down": ("layers", "mlp", "embed"),
        },
        "final_norm": ("embed",),
        "lm_head": ("embed", "vocab"),
    }


def init(cfg: DeepseekV3Config, key: jax.Array) -> Params:
    """Seeded random parameters in two stacks. The per-head
    projections lie head-major, split by what they make, in the layout
    their products read: ``q_b_proj`` as ``wq_nope`` (heads, nope,
    q_lora_rank) and ``wq_rope`` (rope, heads, q_lora_rank: the rotation
    takes the rope axis apart), ``kv_b_proj`` as ``w_uk`` (heads,
    nope, kv_lora_rank) and ``w_uv`` (heads, kv_lora_rank, v). (As one
    (q_lora_rank, heads * 192) matrix the TPU sliced each layer's 75 MB
    out of the stack and transposed it, 1.2 ms a step; PERF.md, PR 28.)
    ``router_bias`` (``e_score_correction_bias``) is seeded NON-zero, or
    the difference between what selects an expert and what weighs it
    would never be exercised."""
    d, h, dt = cfg.dim, cfg.n_heads, cfg.dtype
    q, c = cfg.q_lora_rank, cfg.kv_lora_rank
    nope, rope, v = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                     cfg.v_head_dim)
    e, held, ff = cfg.n_routed_experts, cfg.n_experts_held, cfg.moe_mlp_dim
    shared = cfg.n_shared_experts * ff

    def dense(key, shape, fan_in, dtype=dt):
        return (jax.random.normal(key, shape, dtype=jnp.float32) *
                (fan_in ** -0.5)).astype(dtype)

    def attention(key, n):
        k = jax.random.split(key, 7)
        return {
            "attn_norm": jnp.ones((n, d), dtype=dt),
            "wq_a": dense(k[0], (n, d, q), d),
            "q_norm": jnp.ones((n, q), dtype=dt),
            "wq_nope": dense(k[1], (n, h, nope, q), q),
            "wq_rope": dense(k[6], (n, rope, h, q), q),
            "wkv_a": dense(k[2], (n, d, c + rope), d),
            "kv_norm": jnp.ones((n, c), dtype=dt),
            "w_uk": dense(k[3], (n, h, nope, c), c),
            "w_uv": dense(k[4], (n, h, c, v), c),
            "wo": dense(k[5], (n, h * v, d), h * v),
            "mlp_norm": jnp.ones((n, d), dtype=dt),
        }

    k = jax.random.split(key, 16)
    nd, nm = cfg.n_dense_layers, cfg.n_layers - cfg.n_dense_layers
    return {
        "embed": dense(k[0], (cfg.vocab_size, d), d),
        "dense_layers": {
            **attention(k[1], nd),
            "w_gate": dense(k[2], (nd, d, cfg.mlp_dim), d),
            "w_up": dense(k[3], (nd, d, cfg.mlp_dim), d),
            "w_down": dense(k[4], (nd, cfg.mlp_dim, d), cfg.mlp_dim),
        },
        "moe_layers": {
            **attention(k[5], nm),
            "router": dense(k[6], (nm, d, e), d, jnp.float32),
            "router_bias": 0.1 * jax.random.normal(
                k[7], (nm, e), dtype=jnp.float32),
            "we_gate": dense(k[8], (nm, held, d, ff), d),
            "we_up": dense(k[9], (nm, held, d, ff), d),
            "we_down": dense(k[10], (nm, held, ff, d), ff),
            "ws_gate": dense(k[11], (nm, d, shared), d),
            "ws_up": dense(k[12], (nm, d, shared), d),
            "ws_down": dense(k[13], (nm, shared, d), shared),
        },
        "final_norm": jnp.ones((d,), dtype=dt),
        "lm_head": dense(k[14], (d, cfg.vocab_size), d),
    }


def quantize_params(cfg: DeepseekV3Config, params: Params) -> Params:
    param_specs(cfg, quantized=True)


def params_quantized(params: Params) -> bool:
    return False


# --------------------------------------------------------------- routing
def route(cfg: DeepseekV3Config, logits: jax.Array, bias: jax.Array
          ) -> Tuple[jax.Array, jax.Array]:
    """Router logits (..., E) float32 -> (weights (..., E) float32,
    chosen (..., E) bool), over ALL routed experts. Scores are
    sigmoids; the bias steers the choice and not the weight; a group's
    score is the sum of its two largest biased scores, ``topk_group``
    groups are kept and the ``top_k`` largest biased scores among their
    experts chosen; weights are the chosen experts' unbiased scores,
    renormalised and scaled."""
    e, g = cfg.n_routed_experts, cfg.n_group
    s = jax.nn.sigmoid(logits)
    sb = s + bias
    grouped = sb.reshape(sb.shape[:-1] + (g, e // g))
    group_score = jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1)
    _, kept = jax.lax.top_k(group_score, cfg.topk_group)
    group_on = jnp.sum(jax.nn.one_hot(kept, g, dtype=s.dtype), axis=-2)
    expert_on = jnp.repeat(group_on, e // g, axis=-1) > 0
    _, idx = jax.lax.top_k(jnp.where(expert_on, sb, -jnp.inf), cfg.top_k)
    chosen = jnp.sum(jax.nn.one_hot(idx, e, dtype=s.dtype), axis=-2) > 0
    w = jnp.where(chosen, s, 0.0)
    if cfg.norm_topk_prob:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return w * cfg.routed_scaling_factor, chosen


_EXPERTS = ("we_gate", "we_up", "we_down")


def experts_asked(chosen: jax.Array, counts: Optional[jax.Array]
                  ) -> jax.Array:
    """(held,) bool: the held experts that a row which COUNTS chose
    (``chosen`` (B, T, held), ``counts`` (B, T) bool; None: every
    row). What :func:`moe_block` computes, and what the engine counts
    as computed."""
    if counts is not None:
        chosen = chosen & counts[..., None]
    return jnp.any(chosen, axis=(0, 1))


def moe_block(cfg: DeepseekV3Config, x: jax.Array, lp: Params,
              counts: Optional[jax.Array] = None,
              layer: Optional[jax.Array] = None
              ) -> Tuple[jax.Array, jax.Array]:
    """Pre-norm sparse residual block: (x + held routed part + shared
    expert, chosen-and-held (B, T, held) bool).

    A held expert is computed only if a row that counts chose it
    (:func:`experts_asked`), and no byte of its three matrices is read
    otherwise: one pass of a loop for each such expert, taken in order,
    does its products for all rows (zero weight where a row did not
    choose it) and adds into a float32 sum that is rounded once. An
    expert left out would have added exact zeros to every counted row,
    so such a row's result depends on its batch by the order of that
    sum at most, and incremental decode equals a full pass; a row that
    does not count comes out without the experts only it chose, and
    nobody reads it. With ``layer``, ``lp``'s three expert leaves are
    the whole (layers, held, ...) stacks and the loop reads
    ``[layer, e]`` where it lies: a layer's experts cut out ahead of
    the loop would be a copy of all of them."""
    lo = cfg.ep_rank * cfg.n_experts_held
    hi = lo + cfg.n_experts_held
    we_gate, we_up, we_down = (
        lp[name] if layer is not None else lp[name][None]
        for name in _EXPERTS)
    layer = jnp.int32(0) if layer is None else layer
    with jax.named_scope("stpu.moe"):
        y = llama.rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
        # The choice is discrete: its logits are worth six bf16 passes
        # of a (tokens, dim) x (dim, experts) product.
        logits = jnp.einsum("btd,de->bte", y.astype(jnp.float32),
                            lp["router"],
                            precision=jax.lax.Precision.HIGHEST)
        w, chosen = route(cfg, logits, lp["router_bias"])
        w, chosen = w[..., lo:hi].astype(y.dtype), chosen[..., lo:hi]
        asked = experts_asked(chosen, counts)
        order = jnp.argsort(~asked, stable=True)

        def one_expert(i, routed):
            e = order[i]
            gate = jax.nn.silu(y @ we_gate[layer, e])
            up = y @ we_up[layer, e]
            w_e = jax.lax.dynamic_index_in_dim(w, e, axis=2)
            return routed + jnp.einsum(
                "btm,md->btd", gate * up * w_e, we_down[layer, e],
                preferred_element_type=jnp.float32)

        routed = jax.lax.fori_loop(
            0, jnp.sum(asked, dtype=jnp.int32), one_expert,
            jnp.zeros(x.shape, jnp.float32)).astype(x.dtype)
        shared = (jax.nn.silu(y @ lp["ws_gate"]) * (y @ lp["ws_up"])
                  ) @ lp["ws_down"]
        return x + routed + shared, chosen


# ------------------------------------------------------------- attention
def _project(cfg: DeepseekV3Config, x: jax.Array, lp: Params,
             positions: jax.Array):
    """(q_nope (B, T, H, nope), q_rope (B, T, H, rope), c_kv (B, T, C),
    k_r (B, T, rope)): the queries, and what the cache holds of each
    token: the normed latent and the roped key all heads share."""
    if any(name.endswith("_lora_a") for name in lp):
        refuse("LoRA", "lora_dense has no adapters for the latent "
               "projections")
    c = cfg.kv_lora_rank
    y = llama.rms_norm(x, lp["attn_norm"], cfg.norm_eps)
    c_q = llama.rms_norm(y @ lp["wq_a"], lp["q_norm"], cfg.norm_eps)
    q_nope = jnp.einsum("btq,hnq->bthn", c_q, lp["wq_nope"])
    q_rope = rope_yarn(cfg, jnp.einsum("btq,rhq->bthr", c_q,
                                       lp["wq_rope"]), positions)
    kv = y @ lp["wkv_a"]
    c_kv = llama.rms_norm(kv[..., :c], lp["kv_norm"], cfg.norm_eps)
    k_r = rope_yarn(cfg, kv[..., None, c:], positions)[:, :, 0]
    return q_nope, q_rope, c_kv, k_r


def _f32_dot(eq: str, a: jax.Array, b: jax.Array) -> jax.Array:
    """A product of the attention tile, accumulated and kept in
    float32 (llama._attn_tile's idiom: the TPU's default precision
    multiplies float32 operands in one bf16 pass, so the upcast costs
    the values nothing and the CPU computes the same)."""
    return jnp.einsum(eq, a.astype(jnp.float32), b.astype(jnp.float32))


def _softmax_tile(s, msk, values, eq, m, el, acc):
    """One online-softmax tile (llama._attn_tile's arithmetic in this
    block's layouts). s: (B, H, T, W) float32; msk: (B, T, W) bool;
    m, el: (B, H, T); acc: (B, T, H, Dv) float32."""
    s = jnp.where(msk[:, None], s, -1e30)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1))
    corr = jnp.exp(m - m_new)
    # Masked entries are exactly 0: a fully masked (free) slot stays
    # finite.
    p = jnp.exp(s - m_new[..., None]) * msk[:, None]
    el = el * corr + jnp.sum(p, axis=-1)
    pv = _f32_dot(eq, p, values)
    return m_new, el, acc * corr.transpose(0, 2, 1)[..., None] + pv


def _attend(cfg: DeepseekV3Config, q_nope, q_rope, lp: Params, tiles,
            limit, absorbed: bool) -> jax.Array:
    """Attention of the queries over cached rows served tile by tile:
    ``tiles(s0) -> (c_kv (B, W, C), k_r (B, W, rope), msk (B, T, W))``
    for the W rows from position ``s0`` on, until ``limit``.

    ``absorbed`` (decode): ``W_UK`` goes into the query and ``W_UV``
    onto the output, and the latent rows are attended as they lie.
    Else (a prefill chunk, or no cache) each tile's rows are expanded
    to per-head keys (nope + rope wide) and values. Returns the heads'
    outputs (B, T, H * v) ahead of ``wo``."""
    b, t, h, _ = q_nope.shape
    w_uk, w_uv = lp["w_uk"], lp["w_uv"]
    if absorbed:
        q_nope = jnp.einsum("bthn,hnc->bthc", q_nope, w_uk)

    def body(carry):
        s0, m, el, acc = carry
        c_kv, k_r, msk = tiles(s0)
        s = _f32_dot("bthr,bsr->bhts", q_rope, k_r)
        if absorbed:
            s += _f32_dot("bthc,bsc->bhts", q_nope, c_kv)
            values, eq = c_kv, "bhts,bsc->bthc"
        else:
            k_nope = jnp.einsum("bsc,hnc->bshn", c_kv, w_uk)
            s += _f32_dot("bthn,bshn->bhts", q_nope, k_nope)
            values = jnp.einsum("bsc,hcv->bshv", c_kv, w_uv)
            eq = "bhts,bshv->bthv"
        return (s0 + c_kv.shape[1],
                *_softmax_tile(s * softmax_scale(cfg), msk, values, eq,
                               m, el, acc))

    _, _, el, acc = jax.lax.while_loop(
        lambda carry: carry[0] < limit, body,
        (jnp.int32(0), jnp.full((b, h, t), -1e30, jnp.float32),
         jnp.zeros((b, h, t), jnp.float32),
         jnp.zeros((b, t, h, cfg.kv_lora_rank if absorbed
                    else cfg.v_head_dim), jnp.float32)))
    el = el.transpose(0, 2, 1)[..., None]
    out = jnp.where(el > 0, acc / jnp.maximum(el, 1e-30), 0.0)
    out = out.astype(q_rope.dtype)
    if absorbed:
        out = jnp.einsum("bthc,hcv->bthv", out, w_uv)
    return out.reshape(b, t, h * cfg.v_head_dim)


def attention_block(cfg: DeepseekV3Config, x: jax.Array, lp: Params,
                    positions: jax.Array) -> jax.Array:
    """Pre-norm MLA residual block with no cache: expanded keys and
    values, one causal tile over the whole sequence."""
    with jax.named_scope("stpu.mla"):
        q_nope, q_rope, c_kv, k_r = _project(cfg, x, lp, positions)
        msk = positions[:, None, :] <= positions[:, :, None]
        attn = _attend(cfg, q_nope, q_rope, lp,
                       lambda s0: (c_kv, k_r, msk),
                       jnp.int32(x.shape[1]), absorbed=False)
        return x + attn @ lp["wo"]


# -------------------------------------------------------- the latent pool
_UINT = {2: jnp.uint16, 4: jnp.uint32}


def init_paged_cache(cfg: DeepseekV3Config, num_blocks: int,
                     block_tokens: int, *, quantized: bool = False
                     ) -> Dict[str, jax.Array]:
    """The latent paged pool: kv_lora_rank + qk_rope_head_dim values a
    token a layer and nothing else, as two leaves (block 0 is the
    scratch block, as in llama.init_paged_cache): ``c_kv`` (layers,
    blocks, block_tokens, kv_lora_rank) and ``k_r``, the roped key's
    BYTES (layers, blocks, block_tokens, 2 * qk_rope_head_dim) uint8.

    Why bytes: the TPU lays an array whose last axis is no multiple of
    128 lanes out with its LARGEST axis minor (here: the blocks), and
    every program would convert the whole pool on its way in and out
    (576 wide, or 64 wide beside 512: PERF.md, PR 28). 64 bf16 values
    are 128 bytes, so the byte view keeps the row-major layout, a
    token's key a row of its own, and both writes the row scatter
    llama.py's pool has."""
    if quantized:
        refuse("the int8 pool (kv_quant)",
               "the per-(block, head) scales have no head to hang on")
    rows = (cfg.n_layers, num_blocks, block_tokens)
    key_bytes = cfg.qk_rope_head_dim * jnp.dtype(cfg.dtype).itemsize
    return {"c_kv": jnp.zeros(rows + (cfg.kv_lora_rank,), cfg.dtype),
            "k_r": jnp.zeros(rows + (key_bytes,), jnp.uint8)}


def _to_bytes(x: jax.Array) -> jax.Array:
    """(..., n) values -> (..., n * itemsize) uint8, PLANAR: every
    value's low byte, then every value's next byte. (Interleaved, as a
    bitcast to uint8 gives them, reading them back splits the minor
    axis, which the TPU does by re-laying the tile out: 0.06 ms a tile
    against the 0.08 ms of the rest of it; PERF.md, PR 28.)"""
    size = jnp.dtype(x.dtype).itemsize
    u = jax.lax.bitcast_convert_type(x, _UINT[size])
    return jnp.concatenate(
        [(u >> (8 * k)).astype(jnp.uint8) for k in range(size)], axis=-1)


def _from_bytes(b: jax.Array, dtype) -> jax.Array:
    size = jnp.dtype(dtype).itemsize
    n = b.shape[-1] // size
    u = sum(b[..., k * n:(k + 1) * n].astype(_UINT[size]) << (8 * k)
            for k in range(size))
    return jax.lax.bitcast_convert_type(u, dtype)


def paged_attention_block(cfg: DeepseekV3Config, x: jax.Array,
                          lp: Params, li: jax.Array,
                          pool: Dict[str, jax.Array],
                          table: jax.Array, positions: jax.Array,
                          start_pos: jax.Array, valid_len: jax.Array,
                          window: int,
                          write_block: Optional[jax.Array],
                          write_pos: Optional[jax.Array]):
    """Pre-norm MLA residual block against the latent paged pool,
    carried whole: the new rows scatter to ``[li, block, offset]``
    (llama's three ways of naming the targets), then the context is
    gathered through the table ``window`` rows at a time. A chunk
    (B == 1, T == block_tokens) attends in the expanded form, a decode
    step or a verify window in the absorbed form. Returns
    (x + attention, pool)."""
    b, t = x.shape[0], x.shape[1]
    bt = pool["c_kv"].shape[2]
    nb_win = window // bt
    if nb_win * bt != window:
        raise ValueError(f"window {window} must be a multiple of the "
                         f"block size {bt}")
    with jax.named_scope("stpu.mla"):
        q_nope, q_rope, c_kv, k_r = _project(cfg, x, lp, positions)
        blk, off = llama.paged_write_targets(
            table, bt, b, t, start_pos, write_block, write_pos)
        pool = {
            "c_kv": pool["c_kv"].at[li, blk, off].set(
                c_kv.astype(pool["c_kv"].dtype)),
            "k_r": pool["k_r"].at[li, blk, off].set(
                _to_bytes(k_r.astype(cfg.dtype)))}

        def tiles(s0):
            phys = jax.lax.dynamic_slice(
                table, (jnp.int32(0), s0 // bt), (b, nb_win))
            kpos = s0 + jnp.arange(window)
            msk = ((kpos[None, None, :] <= positions[..., None]) &
                   (kpos[None, None, :] < valid_len[:, None, None]))
            return (pool["c_kv"][li, phys].reshape(b, window, -1),
                    _from_bytes(pool["k_r"][li, phys].reshape(
                        b, window, -1), cfg.dtype), msk)

        limit = jnp.max(jnp.minimum(positions[:, -1] + 1, valid_len))
        attn = _attend(cfg, q_nope, q_rope, lp, tiles,
                       jnp.minimum(limit, table.shape[1] * bt),
                       absorbed=t == 1 or write_pos is not None)
        return x + attn @ lp["wo"], pool


# ------------------------------------------------------- forward passes
def _dense_block(cfg, x, lp, counts, layer):
    return llama.mlp_block(cfg, x, lp), None


def _scanned(stack: Params):
    """A stack of layers as (what a scan slices a layer at a time, the
    sparse layers' expert matrices, which stay whole: moe_block reads
    them by [layer, expert])."""
    return ({k: v for k, v in stack.items() if k not in _EXPERTS},
            {k: stack[k] for k in _EXPERTS if k in stack})


def forward(cfg: DeepseekV3Config, params: Params, tokens: jax.Array,
            positions: Optional[jax.Array] = None) -> jax.Array:
    """Token ids (B, S) -> float32 logits (B, S, vocab), no cache.
    Every row counts."""
    b, s = tokens.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    x = llama._decode_embed(cfg, params, tokens)
    for stack, mlp in ((params["dense_layers"], _dense_block),
                       (params["moe_layers"], moe_block)):
        sliced, whole = _scanned(stack)

        def layer_fn(x, scanned):
            lp, i = scanned
            x = attention_block(cfg, x, lp, positions)
            return mlp(cfg, x, {**lp, **whole}, None, i)[0], None
        x, _ = jax.lax.scan(
            layer_fn, x,
            (sliced, jnp.arange(stack["attn_norm"].shape[0])))
    return llama.lm_head(cfg, params, x, lambda a, _spec: a)


def forward_with_paged_cache(cfg: DeepseekV3Config, params: Params,
                             tokens: jax.Array,
                             cache: Dict[str, jax.Array],
                             table: jax.Array, start_pos: jax.Array,
                             valid_len: Optional[jax.Array] = None,
                             logits_at: Optional[jax.Array] = None, *,
                             window: int,
                             write_block: Optional[jax.Array] = None,
                             write_pos: Optional[jax.Array] = None):
    """llama.forward_with_paged_cache's contract over the latent pool,
    with a third result: (logits, pool, (chosen, computed)), ``chosen``
    (B, T, sparse layers, held) bool — which held experts each token
    chose — and ``computed`` (sparse layers, held) bool — which of them
    the program computed; the engine counts both per decode step. Two
    scans, dense layers then sparse ones, both carrying the pool.

    The rows that COUNT for the expert layer: the positions under
    ``valid_len`` (a chunk's padded tail does not), and in a decode
    step or a verify window only the rows whose table names a block of
    their own. The engine hands such a step a table in which every row
    that does not decode names block 0, the scratch block
    (decode_engine._step_table), so the step learns its decoding rows
    from an argument it already has."""
    b, t = tokens.shape
    start_pos, valid_len, positions = llama.slot_positions(
        b, t, start_pos, valid_len)
    counts = positions < valid_len[:, None]
    if t == 1 or write_pos is not None:
        counts &= table[:, :1] != 0
    x = llama._decode_embed(cfg, params, tokens)

    def stack_scan(x, pool, stack, first, mlp):
        sliced, whole = _scanned(stack)

        def layer_fn(carry, scanned):
            x, pool = carry
            lp, i = scanned
            x, pool = paged_attention_block(
                cfg, x, lp, first + i, pool, table, positions,
                start_pos, valid_len, window, write_block, write_pos)
            x, chosen = mlp(cfg, x, {**lp, **whole}, counts, i)
            return (x, pool), chosen
        n = stack["attn_norm"].shape[0]
        return jax.lax.scan(layer_fn, (x, pool), (sliced, jnp.arange(n)))

    (x, pool), _ = stack_scan(x, dict(cache), params["dense_layers"], 0,
                              _dense_block)
    (x, pool), chosen = stack_scan(x, pool, params["moe_layers"],
                                   cfg.n_dense_layers, moe_block)
    computed = jax.vmap(experts_asked, (0, None))(chosen, counts)
    logits = llama.lm_head(cfg, params, llama.read_out(x, logits_at),
                           lambda a, _spec: a)
    return logits, pool, (chosen.transpose(1, 2, 0, 3), computed)


def verify_step_paged(cfg: DeepseekV3Config, params: Params,
                      tokens: jax.Array, cache, table, start_pos,
                      spec_len, *, window: int):
    """llama.verify_step_paged over the latent pool: the window's rows
    scatter through the table (``write_pos``) and every column attends
    in the absorbed form, like the decode step it replaces."""
    b, t = tokens.shape
    start_pos = jnp.broadcast_to(jnp.asarray(start_pos, jnp.int32), (b,))
    spec_len = jnp.broadcast_to(jnp.asarray(spec_len, jnp.int32), (b,))
    span = table.shape[1] * cache["c_kv"].shape[2]
    wpos = llama._verify_write_positions(t, start_pos, spec_len, span)
    logits, cache, _ = forward_with_paged_cache(
        cfg, params, tokens, cache, table, start_pos,
        valid_len=start_pos + spec_len + 1, window=window,
        write_pos=wpos)
    return logits, cache


def cache_specs(cfg: DeepseekV3Config):
    """Asked for by gang_replica.cache_shardings alone, to lay a cache
    over a mesh."""
    refuse("tp > 1", "a latent row has no head axis to shard")

